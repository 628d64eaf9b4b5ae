"""Multi-process GoSGD over TCP peers (SURVEY §3.3 — the reference ran
one gossip worker per MPI rank with isend/probe pushes).

Two real OS processes join via ``jax.distributed``; each trains its
own replica at its own pace, pushes (params, score/2) to the peer with
Bernoulli probability, polls its inbox each iteration, and merges
arrivals score-weighted.  No barrier in training.
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

CHILD = textwrap.dedent(
    """
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; ckpt = sys.argv[3]
    sys.path.insert(0, {repo!r})
    from theanompi_tpu.launcher import init_distributed
    init_distributed(f"127.0.0.1:{{port}}", 2, pid)
    import jax
    assert jax.process_count() == 2
    from theanompi_tpu.workers import gosgd_worker
    out = gosgd_worker.run(
        modelfile="theanompi_tpu.models.wresnet", modelclass="WResNet",
        config={{"batch_size": 2, "n_epochs": 2, "depth": 10, "widen": 1,
                 "n_train": 32, "n_val": 8,
                 "exch_strategy": "ici16"}},  # bf16 gossip wire
        push_prob=0.6, seed=pid * 13 + 5,
        checkpoint_dir=ckpt,
        verbose=False,
    )
    print(f"RESULT {{pid}} {{out['delivered']}} {{out['merges']}} "
          f"{{out['score']:.6f}} {{out['final_train_loss']:.6f}}",
          flush=True)
    for ep, s in enumerate(out["epoch_scores"]):
        print(f"EPOCHSCORE {{pid}} {{ep}} {{s:.9e}}", flush=True)
    for ms in out["mid_saves"]:
        print(f"MIDSAVE {{pid}} {{ms['epoch']}} {{ms['score']:.9e}}",
              flush=True)
    """
).format(repo=str(REPO))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_gosgd(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    port = _free_port()
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        # keep worst-case quiesce inside the subprocess timeout so a
        # lost delivery fails with diagnostics, not TimeoutExpired
        TM_GOSGD_QUIESCE_S="60",
    )
    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port),
             str(ckpt_dir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=str(tmp_path),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
            assert p.returncode == 0, f"child failed:\n{out[-3000:]}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    results = {}
    epoch_scores: dict[tuple[int, int], float] = {}
    mid_saves: dict[int, list[tuple[int, float]]] = {0: [], 1: []}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, delivered, merges, score, loss = line.split()
                results[pid] = (
                    int(delivered), int(merges), float(score), float(loss)
                )
            elif line.startswith("EPOCHSCORE"):
                _, pid, ep, s = line.split()
                epoch_scores[(int(pid), int(ep))] = float(s)
            elif line.startswith("MIDSAVE"):
                _, pid, ep, s = line.split()
                mid_saves[int(pid)].append((int(ep), float(s)))
    assert set(results) == {"0", "1"}, outs
    total_delivered = sum(r[0] for r in results.values())
    total_merges = sum(r[1] for r in results.values())
    assert total_delivered >= 2, results  # gossip actually happened
    # every payload that LEFT a sender got merged somewhere (the
    # receive-side ack drained the wire before notes were compared)
    assert total_merges == total_delivered, results
    for pid, (delivered, merges, score, loss) in results.items():
        assert np.isfinite(loss), results
        assert 0.0 < score < 1.0, results
    # score mass is conserved across the cluster (sends halve, merges
    # add — undelivered mass would show up here)
    total_score = sum(r[2] for r in results.values())
    np.testing.assert_allclose(total_score, 1.0, rtol=1e-5)

    # mid-run checkpoints carry the MAX-SCORE worker's weights
    # (VERDICT r2 item 10): for every epoch, exactly one process saved,
    # and it is the argmax of the published epoch scores
    import json

    all_saves = sorted(
        (ep, pid, s) for pid, lst in mid_saves.items() for ep, s in lst
    )
    assert all_saves, outs  # checkpointing happened mid-run
    for ep in {ep for ep, _, _ in all_saves}:
        savers = [pid for e, pid, _ in all_saves if e == ep]
        assert len(savers) == 1, all_saves
        best = max((0, 1), key=lambda p: epoch_scores[(p, ep)])
        assert savers[0] == best, (all_saves, epoch_scores)
    # the best-marker sidecar records one of the mid-run saves (save
    # order across processes is only softly synchronized, so the
    # winner of the final write is any recorded save, not a fixed one)
    marker = json.loads((ckpt_dir / "gosgd_best.json").read_text())
    assert (marker["epoch"], marker["pid"]) in {
        (ep, pid) for ep, pid, _ in all_saves
    }, (marker, all_saves)
