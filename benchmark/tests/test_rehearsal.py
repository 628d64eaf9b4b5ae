"""A tiny-size rehearsal of each driver on the CPU (rehearsals 1 and 2
of the ``on-chip-measurement`` guide): the whole control flow of a
run, the last line's keys, and the refusal to name a device metric
from a CPU run.  Each rehearsal is a process of its own, as a run is."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(code_or_args, tmp_path, devices=1, module=False):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    cmd = ([sys.executable, *code_or_args] if module
           else [sys.executable, "-c", code_or_args])
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_rehearsal_prints_the_contracts_line_and_withholds_metrics(
        cell, trace, tmp_path):
    chips = next(c["chips"] for c in BENCH["workloads"] if c["name"] == cell)
    done = _run(
        "import json; from benchmark import run; "
        f"print(json.dumps(run.run_cell({cell!r}, seed=5, seconds=1.0, "
        f"trace={bool(trace)}, rehearsal=True)))",
        tmp_path, devices=chips,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["metrics"] == {} and "breakdown" not in line
    declared = harness_metrics(cell, trace)
    if trace:
        # the recorded chip trace stands in for the CPU's; what a CPU
        # run has nothing to read for (memory, kernels) is left out
        assert set(line["withheld"]) <= declared and line["withheld"]
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
    else:
        assert set(line["withheld"]) == declared


def harness_metrics(cell: str, trace: int) -> set:
    sys.path.insert(0, str(ROOT))
    from benchmark import run as harness

    got = harness.load_cell(cell)
    return {m["name"] for m in got["per_layer" if trace else "end_to_end"]}


def test_the_command_refuses_anything_but_the_cells_tpu_chips(tmp_path):
    done = _run([*BENCH["command"][1:], "--workload", "resnet50_bsp_1chip",
                 "--seed", "1", "--seconds", "1", "--trace", "0"],
                tmp_path, module=True)
    assert done.returncode != 0
    assert "refused" in done.stderr
    assert not any(l.startswith("{") and "metrics" in l
                   for l in done.stdout.splitlines())


def test_the_command_fails_where_the_program_is_absent(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` there is no system to measure."""
    import shutil

    bare = tmp_path / "bare"
    shutil.copytree(ROOT / "benchmark", bare / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", bare / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         "resnet50_bsp_1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=bare, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert not any(l.startswith("{") and "metrics" in l
                   for l in done.stdout.splitlines())
