"""``BENCHMARK.json`` against the contract's limits, the harness's
look-up of a cell from data, and the proof that a fifth cell is new
files and new entries only."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_the_contracts_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = BENCH["workloads"]
    # a full check with the full 24 cells must fit into 43200 s
    assert ((2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)
    assert 2 <= len(cells) <= 24
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    used = {c["config"] for c in cells}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert (ROOT / c["file"]).is_file()
        held = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in held
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate"
                                 r"|head_dim|width)", key)
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in cells}


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_resolves_from_data(cell):
    got = harness.load_cell(cell)
    reported = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert got["per_layer"], "a cell reports at least one per-layer metric"
    for m in got["per_layer"]:
        assert m["moves"] in reported
        reader = harness._module("layer_metrics", m["name"])
        assert callable(reader.read)
        # a reader with nothing to read returns nothing
        assert reader.read({"cell": got, "peaks": None}) is None
    assert harness._module("drivers", got["traffic"]["kind"]).run
    assert {"source", "reduced", "assumed", "deployment", "program",
            "rehearsal"} <= set(got["config"])


def test_an_unknown_cell_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no_such_cell")


def _hashes(root: Path) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
        and ".bench_scratch" not in p.parts
    }


def _train_cell(root: Path, bench: dict) -> tuple[set, set]:
    """A training cell with a configuration, a traffic mix and a
    per-layer metric of its own."""
    base = json.loads(
        (ROOT / "benchmark/configs/mistral_7b_v0.1_train_l2.json").read_text()
    )
    tiny = dict(base, **base["rehearsal"])
    tiny["rehearsal"] = base["rehearsal"]
    (root / "benchmark/configs/tiny_decoder.json").write_text(json.dumps(tiny))
    (root / "benchmark/traffic/bsp_short.json").write_text(json.dumps({
        "kind": "train", "trace_chunks": 2,
        "rehearsal": {"recorded_trace": "train_step.trace.json.gz",
                      "recorded_device_kind": "TPU v5 lite"},
    }))
    (root / "benchmark/layer_metrics/tokens_per_step.py").write_text(
        '"""model step: tokens one optimizer step consumes."""\n\n\n'
        "def read(facts):\n    return facts.get('items_per_step')\n"
    )
    bench["configs"].append({
        "name": "tiny_decoder", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_decoder.json", "why": "a test"})
    bench["workloads"].append({
        "name": "fifth", "config": "tiny_decoder", "traffic": "bsp_short",
        "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "tokens_per_step", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "train_throughput", "workloads": ["fifth"]})
    # ... and the cell's name in the list of every metric it reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mistral7b_train_t4096" in m.get("workloads", []) \
                and m["name"] != "flash_attention_roofline":
            m["workloads"].append("fifth")
    return ({"configs/tiny_decoder.json", "traffic/bsp_short.json",
             "layer_metrics/tokens_per_step.py"},
            {"tokens_per_step", "mfu", "step_device_ms", "stall_share"})


#: the serving metrics a later ``benchmark`` PR brings with its first
#: serving cell; their readers and the driver are already here
SERVING_END_TO_END = [("serve_tokens_per_s", "tokens/s", "higher"),
                      ("ttft_p90_ms", "ms", "lower"),
                      ("tpot_p90_ms", "ms", "lower")]
SERVING_PER_LAYER = [
    ("serve_device_idle_share", "share", "device", "tpot_p90_ms"),
    ("serve_peak_hbm_gib", "GiB", "device", "serve_tokens_per_s"),
    ("slot_occupancy", "share", "serving engine", "serve_tokens_per_s"),
    ("queue_wait_p90_ms", "ms", "serving engine", "ttft_p90_ms"),
    ("decode_device_ms", "ms", "decoder", "tpot_p90_ms"),
    ("decode_host_ms", "ms", "decoder", "tpot_p90_ms"),
]


def _serving_cell(root: Path, bench: dict) -> tuple[set, set]:
    """A serving cell: the training configuration's widths with a
    ``serving`` group, an open-loop mix, and entries for the serving
    metrics.  Two new files; driver, generator and readers exist."""
    base = json.loads(
        (ROOT / "benchmark/configs/mistral_7b_v0.1_train_l2.json").read_text()
    )
    names = {"decode": "decode_body", "prefill": "prefill_body"}
    tiny = dict(base["rehearsal"], program={
        "seq_len": 128, "batch_size": 1, "remat": False, "optimizer": "sgd",
        "compute_dtype": "float32"}, serving={
        "decoder": {"max_slots": 4, "block_size": 4, "max_seq": 128,
                    "n_blocks": 256, "prefill_chunk": 8},
        "engine": {}, "trace_names": names})
    real = dict(base, rehearsal=tiny, serving={
        "decoder": {"max_slots": 32, "block_size": 16, "max_seq": 4096,
                    "n_blocks": 10000},
        "engine": {}, "trace_names": names})
    (root / "benchmark/configs/tiny_served.json").write_text(json.dumps(real))
    lengths = {"median": 12, "sigma": 0.5, "min": 4, "max": 40}
    (root / "benchmark/traffic/chat_tiny.json").write_text(json.dumps({
        "kind": "open_loop", "rate_rps": 1.0,
        "prompt_tokens": {"median": 512, "sigma": 0.8, "min": 64,
                          "max": 3072},
        "output_tokens": {"median": 128, "sigma": 0.6, "min": 16, "max": 512},
        "max_total_tokens": 4096, "drain_s": 60, "trace_seconds": 6,
        "rehearsal": {
            "rate_rps": 6.0, "prompt_tokens": lengths,
            "output_tokens": {"median": 6, "sigma": 0.4, "min": 2, "max": 12},
            "max_total_tokens": 64, "drain_s": 60, "trace_seconds": 1,
            "recorded_trace": "serve.trace.json.gz",
            "recorded_device_kind": "TPU v5 lite"},
    }))
    bench["configs"].append({
        "name": "tiny_served", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_served.json", "why": "a test"})
    bench["workloads"].append({
        "name": "fifth", "config": "tiny_served", "traffic": "chat_tiny",
        "chips": 1, "why": "a test"})
    for name, unit, better in SERVING_END_TO_END:
        bench["end_to_end"].append({
            "name": name, "unit": unit, "better": better, "bound": 0.05,
            "source": "host_clock", "workloads": ["fifth"]})
    for name, unit, layer, moves in SERVING_PER_LAYER:
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": ["fifth"]})
    return ({"configs/tiny_served.json", "traffic/chat_tiny.json"},
            {"slot_occupancy", "decode_device_ms", "decode_host_ms",
             "serve_device_idle_share"})


@pytest.mark.parametrize("add_cell", [_train_cell, _serving_cell])
def test_a_fifth_cell_is_new_files_and_new_entries_only(add_cell, tmp_path):
    """A later PR's cell, of either kind of traffic: new data files
    (and a reader where it brings a per-layer metric), new entries in
    ``configs``, ``workloads`` and the metric lists, and the cell's
    name in the ``workloads`` list of each metric it reports.  No file
    that exists is edited, and the harness runs the cell (here as a
    tiny CPU rehearsal)."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(root / "benchmark")
    bench = json.loads(json.dumps(BENCH))
    new_files, withheld = add_cell(root, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    code = (
        "import json; from benchmark import run; "
        "print(json.dumps(run.run_cell('fifth', seed=1, seconds=0.5, "
        "trace=True, rehearsal=True)))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{ROOT}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}            # a CPU run names no metric
    assert withheld <= set(line["withheld"])
    after = _hashes(root / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == new_files


def test_the_serving_cell_reports_its_end_to_end_metrics(tmp_path):
    """The untraced line of the serving rehearsal withholds exactly
    the cell's end-to-end metrics."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    _serving_cell(root, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import json; from benchmark import run; "
        "print(json.dumps(run.run_cell('fifth', seed=2, seconds=1.0, "
        "trace=False, rehearsal=True)))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{ROOT}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["withheld"]) == {
        "setup_s", *(name for name, _, _ in SERVING_END_TO_END)}


def test_chunk_rate_trims_both_tenths_and_says_what_it_left_out():
    from benchmark.drivers.train import chunk_rate

    quiet = chunk_rate([0.4] * 20)
    assert quiet["chunk_mean_s"] == pytest.approx(0.4)
    assert quiet["chunks_kept"] == 16 and quiet["stall_share"] == 0.0
    # one chunk in twenty stalls for 2 s: the rate does not move, and
    # the stall is the share of the window it took
    stalled = chunk_rate([0.4] * 10 + [2.4] + [0.4] * 9)
    assert stalled["chunk_mean_s"] == pytest.approx(0.4)
    assert stalled["stall_share"] == pytest.approx(2.0 / 10.0)
    assert stalled["chunk_s_max"] == 2.4
    # a stall in every fifth chunk is more than the trimming hides
    periodic = chunk_rate([0.4, 0.4, 0.4, 0.4, 0.9] * 4)
    assert periodic["chunk_mean_s"] > 0.45
    # fewer than ten chunks: nothing is trimmed
    assert chunk_rate([0.4, 0.6])["chunk_mean_s"] == pytest.approx(0.5)


def test_learns_holds_the_last_chunk_to_the_configurations_share():
    from benchmark.drivers.train import learns

    falling = [6.9, 6.8, 6.0, 5.0, 4.2, 4.0]
    spec = {"last_chunk_loss_over_first": 0.9}
    assert learns(falling, 2, spec)["ok"]
    assert learns(falling, 2, spec)["last_chunk_loss"] == pytest.approx(4.1)
    assert not learns([6.9, 6.9, 6.8, 6.9, 6.85, 6.9], 2, spec)["ok"]
    assert learns([6.9, 7.5], 1, None)["ok"]      # nothing asked
