"""Multi-process distributed smoke tests (SURVEY §4e).

The reference validated multi-node on real clusters only; the rebuild
spawns real OS processes on localhost, joins them with
``jax.distributed.initialize`` (the mpirun/NCCL-clique replacement —
launcher.init_distributed), and trains over the resulting GLOBAL mesh.
Each child disables this image's TPU bootstrap so the processes
aggregate virtual CPU devices (2 procs x 2 devices = 4-device mesh).
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

CHILD = textwrap.dedent(
    """
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]
    sys.path.insert(0, {repo!r})
    from theanompi_tpu.launcher import init_distributed
    init_distributed(f"127.0.0.1:{{port}}", 2, pid)
    import jax
    assert jax.device_count() == 4, jax.devices()
    assert jax.process_count() == 2
    from theanompi_tpu.workers import bsp_worker
    out = bsp_worker.run(
        modelfile="theanompi_tpu.models.wresnet", modelclass="WResNet",
        config={{"batch_size": 2, "n_epochs": 1, "depth": 10, "widen": 1,
                 "n_train": 16, "n_val": 8}},
        verbose=False,
    )
    print(f"RESULT {{pid}} {{out['final_train_loss']:.6f}}", flush=True)
    """
).format(repo=str(REPO))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_bsp_training(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    port = _free_port()
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port)],
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
        for p in procs:  # no orphans on hang/failure
            if p.poll() is None:
                p.kill()
                p.wait()
    losses = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, loss = line.split()
                losses[pid] = float(loss)
    assert set(losses) == {"0", "1"}, outs
    # SPMD: every process computes the identical global training result
    assert losses["0"] == losses["1"], losses
