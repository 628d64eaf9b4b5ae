"""Async rules at scale: 4 EASGD processes with a mid-run worker
death, and GoSGD score-mass conservation under outbox drops
(VERDICT r3 #5 — the asynchrony semantics the 2-process smokes don't
reach: center contention with >2 clients, a dead peer mid-run, and
the bounded outbox actually dropping).
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


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


EASGD_CHILD = textwrap.dedent(
    """
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; cport = sys.argv[3]
    n = int(sys.argv[4]); ckpt = sys.argv[5]
    sys.path.insert(0, {repo!r})
    from theanompi_tpu.launcher import init_distributed
    init_distributed(f"127.0.0.1:{{port}}", n, pid)
    import jax, json
    assert jax.process_count() == n
    from theanompi_tpu.workers import easgd_worker
    out = easgd_worker.run(
        modelfile="theanompi_tpu.models.wresnet", modelclass="WResNet",
        config={{"batch_size": 2, "n_epochs": 2, "depth": 10, "widen": 1,
                 "n_train": 16, "n_val": 8, "exch_strategy": "ici16"}},
        tau=2, center_addr=f"127.0.0.1:{{cport}}",
        checkpoint_dir=(ckpt if pid == 0 else None),
        verbose=False,
    )
    print(f"RESULT {{pid}} {{out['exchanges']}} "
          f"{{out['final_train_loss']:.6f}}", flush=True)
    if out.get("center_stats"):
        print("STATS " + json.dumps(out["center_stats"]), flush=True)
    for cv in out.get("center_vals") or []:
        print(f"CENTERVAL {{pid}} {{cv['epoch']}} {{cv['loss']:.6f}}",
              flush=True)
    # skip the coordination shutdown barrier: with a dead peer it can
    # never pass and would abort THIS completed worker (launcher doc)
    from theanompi_tpu.launcher import finish_distributed
    finish_distributed(ok=True)
    """
).format(repo=str(REPO))


@pytest.mark.slow
def test_four_process_easgd_with_midrun_death(tmp_path):
    """4 workers against one TCP center; worker 2 is killed mid-epoch
    (TM_FAULT_AT -> os._exit(137), the preemption drill).  The run
    must COMPLETE: survivors train both epochs, the center's
    backpressure stats stay bounded, the center checkpoint lands, and
    the center validates to a finite loss each epoch."""
    script = tmp_path / "child.py"
    script.write_text(EASGD_CHILD)
    port, cport = _free_port(), _free_port()
    ckpt = str(tmp_path / "ck")
    n = 4
    base_env = dict(os.environ)
    base_env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        # a dead worker never sends 'stop' — bound the center's wait
        TM_EASGD_STOP_TIMEOUT_S="30",
    )
    procs = []
    for i in range(n):
        env = dict(base_env)
        if i == 2:
            env["TM_FAULT_AT"] = "1:3"  # dies in epoch 1, iter 3
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(i), str(port), str(cport),
             str(n), ckpt],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=str(tmp_path),
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    # the injected death exits 137; everyone else completes
    assert procs[2].returncode == 137, outs[2][-2000:]
    for i in (0, 1, 3):
        assert procs[i].returncode == 0, (
            f"survivor {i} failed:\n{outs[i][-3000:]}"
        )
    results, stats, center_vals = {}, None, []
    import json

    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, nex, loss = line.split()
                results[int(pid)] = (int(nex), float(loss))
            elif line.startswith("STATS"):
                stats = json.loads(line[len("STATS "):])
            elif line.startswith("CENTERVAL"):
                _, _, ep, loss = line.split()
                center_vals.append(float(loss))
    assert set(results) == {0, 1, 3}, results
    for pid, (nex, loss) in results.items():
        assert nex >= 2 and np.isfinite(loss), results
    # center served >2 clients: contention stayed bounded (no exchange
    # queued behind the serialized lock for pathological time)
    assert stats is not None, outs[0][-2000:]
    assert stats["exchanges"] >= 6, stats
    assert stats["n_workers"] == 4, stats
    assert stats["stopped_workers"] == 3, stats   # the dead one never stops
    assert 0.0 <= stats["mean_wait_s"] < 5.0, stats
    assert 0.0 <= stats["max_wait_s"] < 30.0, stats
    assert 0.0 <= stats["mean_hold_s"] < 1.0, stats
    # per-epoch center validation ran and is sane
    assert len(center_vals) == 2 and all(
        np.isfinite(v) for v in center_vals
    ), center_vals
    # the center checkpoint landed despite the death
    ck = Path(ckpt)
    assert ck.exists(), "checkpoint dir never created"
    assert any(ck.iterdir()), sorted(ck.iterdir())


def test_gossip_outbox_drop_conserves_score_mass():
    """GoSGD's bounded outbox drops payloads under pressure; the
    design invariant (gossip_net.py push/cancel_pending): a dropped or
    undeliverable push refunds its score mass to the sender, so the
    cluster's scores keep summing to 1 no matter what the network
    does.  Exercised against a DEAD peer (connects refused) with a
    tiny outbox, so BOTH refund channels fire: overflow-drop at
    enqueue and failed-send in the drain thread."""
    from theanompi_tpu.parallel.gossip_net import GossipPeer

    rng = np.random.default_rng(0)
    leaves = [rng.standard_normal((256, 64)).astype(np.float32)]
    # a peer that is gone: bind to grab a port, then close it
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_addr = probe.getsockname()
    probe.close()
    peer = GossipPeer(host="127.0.0.1", max_pending=2)
    try:
        score = 1.0
        n_push = 32
        for _ in range(n_push):
            half = score / 2.0
            peer.push(dead_addr, half, leaves)   # isend semantics
            score = half                          # sender keeps half
        # let the drain thread exhaust the queue (each send fails fast
        # with ECONNREFUSED); then cancel anything still queued
        assert peer.flush(timeout=60.0)
        peer.cancel_pending()
        refunds = peer.take_refunds()
        # nothing was ever delivered; every halved-away unit of score
        # must come home through the refund channel — conservation is
        # EXACT (powers of two)
        assert peer.sent == 0
        assert peer.dropped == n_push, (peer.dropped, n_push)
        assert score + refunds == 1.0, (score, refunds)
    finally:
        peer.close()
