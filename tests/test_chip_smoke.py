"""Rehearsal 1 of ``chip_smoke.py`` (guide ``on-chip-measurement``,
section 2): its phases at tiny sizes on the CPU mesh, with the kernels
interpreted — wrong paths, arguments and control flow show up here
and cost no chip time.  What the rehearsal cannot be is a chip run:
the facts of every phase say so (``off_chip``), and the script as a
whole reports ``"ok": false`` off the chip, and with nothing of the
repo beside it.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY_CLASSIFIER = dict(
    # the smallest zoo classifier with BatchNorm: ResNet-50 is a
    # twenty-minute XLA:CPU compile
    modelfile="theanompi_tpu.models.wresnet", modelclass="WResNet",
    config=dict(batch_size=4, depth=10, widen=1, exch_strategy="ici16"),
    n_batches=3, steps_per_call=2,
)
TINY_LLAMA = dict(
    modelfile="theanompi_tpu.models.llama", modelclass="Llama",
    config=dict(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
        vocab=256, seq_len=64, batch_size=2, remat=True,
        exch_strategy="ici16", n_val=0,
        # buckets at this size too: the proxy's 4 MiB default buckets
        # only at its real width
        exchange_bucket_mb=0.05,
    ),
    n_batches=3, steps_per_call=2,
)
TINY_SERVE = dict(
    prompt_lens=(5, 9, 16, 21), max_tokens=6, max_slots=2,
    block_size=4, seed=0,
)


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def _phase_line(capsys, name, meter, fn, *args, **kw) -> dict:
    """Run a phase as ``main`` does; its printed line must be one
    JSON object and equal to what ``run_phase`` returns."""
    line = chip_smoke.run_phase(name, meter, fn, *args, **kw)
    printed = capsys.readouterr().out.strip().splitlines()
    assert len(printed) == 1 and json.loads(printed[0]) == line
    assert line["ok"], line.get("error")
    for key in ("wall_s", "compile_s", "run_s"):
        assert line[key] >= 0.0
    assert line["cache_hits"] >= 0 and line["cache_misses"] >= 0
    return line


def test_train_phase_line(capsys, meter):
    line = _phase_line(capsys, "train_resnet50", meter,
                       chip_smoke.train_phase, TINY_CLASSIFIER, [0])
    # one scan chunk of 2 and a single step
    assert line["steps"] == 3 and line["scan_chunk"] == 2
    assert line["global_batch"] == 4
    assert line["param_platforms"] == ["cpu"]
    assert "val_loss" in line
    assert chip_smoke.off_chip(line) == ["parameters on ['cpu']"]


@pytest.fixture(scope="module")
def llama_checkpoint(tmp_path_factory):
    return str(tmp_path_factory.mktemp("smoke_ckpt"))


def test_llama_then_serve_phase_lines(capsys, meter, llama_checkpoint):
    line = _phase_line(
        capsys, "train_llama", meter, chip_smoke.train_phase,
        TINY_LLAMA, [0], llama_checkpoint,
    )
    assert line["steps"] == 3
    assert os.listdir(llama_checkpoint)
    # dense attention on the CPU, and the facts say so
    assert line["hlo_tpu_custom_call"] is False
    assert line["dense_attention_choices"] >= 1
    assert "flash kernels are not in the compiled step" in (
        chip_smoke.off_chip(line)
    )

    line = _phase_line(
        capsys, "serve", meter, chip_smoke.serve, TINY_SERVE,
        TINY_LLAMA["config"], llama_checkpoint,
        jax.devices()[0], pallas_interpret=True,
    )
    assert line["requests"] == 4 and line["tokens"] == 24
    for impl in ("gather", "pallas"):
        assert line[impl]["tokens_within_tol"] == 24
    assert line["pallas_kernel"] == "interpreted"
    assert "paged-attention kernel was interpreted" in (
        chip_smoke.off_chip(line)
    )


def test_failed_check_is_a_failed_phase(capsys, meter):
    """A phase that raises prints ``ok: false`` with the error and
    does not propagate: ``main`` turns it into the exit status."""
    def phase():
        chip_smoke._require(False, "loss went sideways")

    line = chip_smoke.run_phase("broken", meter, phase)
    assert line["ok"] is False
    assert "CheckFailed: loss went sideways" in line["error"]
    assert json.loads(capsys.readouterr().out.strip()) == line


def test_four_chip_comparisons_on_virtual_devices(capsys, meter):
    """Rehearsal 2: the ``--chips 4`` phases on four virtual devices —
    meshes, sharding rules and both comparisons."""
    line = _phase_line(capsys, "resnet50_dp4", meter,
                       chip_smoke.compare_data_parallel,
                       TINY_CLASSIFIER, 4)
    assert line["mesh"] == {"data": 4} and line["global_batch"] == 16
    assert line["hlo_all_reduce"] and line["param_devices"] == 4
    assert line["first_loss_rel_diff"] <= chip_smoke.LOSS_RTOL

    line = _phase_line(capsys, "llama_dp2_tp2", meter,
                       chip_smoke.compare_tensor_parallel,
                       TINY_LLAMA, 2, 2)
    assert line["mesh"] == {"data": 2, "model": 2}
    assert line["tp_shard_fraction"] == 0.5
    assert line["first_loss_rel_diff"] <= chip_smoke.LOSS_RTOL


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")], ids=["1", "4"])
def test_script_fails_off_the_chip(args):
    r = _run_script(ROOT, *args)
    assert r.returncode == 1, r.stdout + r.stderr
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": lines[-1]["device"]["count"]},
    }
    setup = lines[0]
    assert setup["phase"] == "setup" and setup["compile_cache_dir"]
    assert setup["native_loader"] in (
        "built from loader.cc", "python fallback"
    )
    # no phase ran on the wrong platform
    assert [l["phase"] for l in lines[:-1]] == [
        "setup", "failed", "total"
    ]
    assert "not 'tpu'" in lines[1]["error"]


def test_script_alone_fails(tmp_path):
    """In a directory that holds the script and nothing else of the
    repo there is no system to start."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": None}
