"""The five readers of the program's memory account
(``layer_metrics/_memory.py``) on a stubbed account with a known
answer, the cells ``BENCHMARK.json`` names for each, and ``None`` —
never an exception — where there is nothing to read."""

import json
from pathlib import Path

import pytest

from benchmark.layer_metrics import (_memory, hbm_resident_gib,
                                     hbm_step_peak_gib,
                                     keep_account_over_peak_gib,
                                     remat_kept_gib, remat_unkept_gib)

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
GIB = 2 ** 30
READERS = {"hbm_resident_gib": hbm_resident_gib,
           "hbm_step_peak_gib": hbm_step_peak_gib,
           "remat_kept_gib": remat_kept_gib,
           "remat_unkept_gib": remat_unkept_gib,
           "keep_account_over_peak_gib": keep_account_over_peak_gib}

RULE = {"bytes_limit": 16 * GIB, "reserve_bytes": GIB,
        "terms": {"params_grads_opt": 9 * GIB, "call_inputs": GIB,
                  "flash_outputs": GIB // 2, "head": GIB // 2},
        "kept_bytes": 2 * GIB, "unkept_bytes": GIB // 4,
        "free_bytes": 2 * GIB,
        "eligible": {"mlp": 4, "attn": 4, "moe": 0},
        "kept": {"mlp": 3, "attn": 0, "moe": 0}}
ACCOUNT = {"rule": RULE, "n_devices": 1, "n_samples": 8, "sample_s": 1e-3,
           "samples": [], "resident_bytes": 7 * GIB,
           "step_peak_bytes": 12 * GIB}


def _facts(account=ACCOUNT):
    """A training run's facts with a recorded trace's own account."""
    return {"scan_k": 2, "trace": {"devices": {}, "memory": account}}


@pytest.mark.parametrize("name, value", [
    ("hbm_resident_gib", 7.0), ("hbm_step_peak_gib", 12.0),
    ("remat_kept_gib", 2.0), ("remat_unkept_gib", 0.25),
    # 11 GiB of terms + 2 kept, over a peak of 12
    ("keep_account_over_peak_gib", 1.0),
])
def test_a_reader_reads_its_bytes_in_gib(name, value):
    assert READERS[name].read(_facts()) == pytest.approx(value)


def test_nothing_kept_or_left_reads_zero_not_none():
    rule = dict(RULE, kept_bytes=0, unkept_bytes=0)
    facts = _facts(dict(ACCOUNT, rule=rule))
    assert remat_kept_gib.read(facts) == 0.0
    assert remat_unkept_gib.read(facts) == 0.0
    assert keep_account_over_peak_gib.read(facts) == pytest.approx(-1.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_is_none(name, monkeypatch):
    from theanompi_tpu import obs

    read = READERS[name].read
    assert read({}) is None                         # not a training run
    assert read({"trace": {"memory": ACCOUNT}}) is None
    # an account without its runtime side: a CPU run
    bare = dict(ACCOUNT, resident_bytes=None, step_peak_bytes=None)
    assert read(_facts(bare)) is None
    # no recorded account: the process's own
    monkeypatch.setattr(obs, "last_memory_account", lambda: None)
    assert read({"scan_k": 2}) is None
    monkeypatch.setattr(obs, "last_memory_account", lambda: dict(ACCOUNT))
    assert read({"scan_k": 2}) is not None
    monkeypatch.delattr(obs, "last_memory_account")  # an older program
    assert read({"scan_k": 2}) is None
    # a model without a keep rule has the device's two and no other
    got = read(_facts(dict(ACCOUNT, rule=None)))
    assert (got is None) == (not name.startswith("hbm_"))


def test_the_benchmark_names_the_cells_of_each():
    cells = [w["name"] for w in BENCH["workloads"]]
    decoders = [c for c in cells if not c.startswith("resnet50")]
    assert len(cells) == 9 and len(decoders) == 7
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    # appended, in this order, behind what was there
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == [
        "hbm_resident_gib", "hbm_step_peak_gib", "remat_kept_gib",
        "remat_unkept_gib", "keep_account_over_peak_gib"]
    for name in READERS:
        m = entries[name]
        device = name.startswith("hbm_")
        assert m["workloads"] == (cells if device else decoders)
        assert (m["unit"], m["source"], m["moves"], m["layer"]) == (
            "GiB", "program_counter", "train_throughput",
            "device" if device else "model step")
        assert m["better"] == ("higher" if name == "remat_kept_gib"
                               else "lower")
    assert _memory.GIB == GIB
