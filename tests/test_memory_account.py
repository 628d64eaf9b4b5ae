"""The run's memory account (``obs/memory.py``, ``Llama.keep_account``,
the worker's summary): the keep rule's side from shapes, the runtime's
side from a stub (the CPU's runtime reports no memory) — counts and
bytes only; what the fields read on the chip is a chip run's to give
(docs/OBSERVABILITY.md, "Memory account")."""

import re
from pathlib import Path

import jax
import pytest
from test_flash_remat import _cell_model

from theanompi_tpu import obs
from theanompi_tpu.models import llama
from theanompi_tpu.models.llama import Llama
from theanompi_tpu.obs import memory
from theanompi_tpu.workers import bsp_worker

GIB = 1 << 30
ROOT = Path(__file__).resolve().parents[1]
#: ``bytes_limit`` of a TPU v5 lite chip, as its runtime reports it
CHIP_LIMIT = 16_909_336_064
TINY = dict(dim=32, n_layers=2, n_heads=2, n_kv_heads=1, ffn_dim=64,
            vocab=64, seq_len=16, batch_size=2, compute_dtype="float32",
            n_train=8, n_val=2, steps_per_call=2, device_data_cache=True)


# -- the rule's side ---------------------------------------------------------


@pytest.mark.parametrize("cell, kept, eligible, kept_gib, unkept_gib, free_gib", [
    # the seven decoder cells at the chip's limit (PERF.md §4)
    ("mistral7b_train_t4096", (2, 2, 0), (2, 2, 0), 1.188, 0.0, 1.925),
    ("olmoe_train_t4096", (0, 1, 1), (0, 1, 1), 1.251, 0.0, 0.978),
    ("ouro_train_t4096", (10, 1, 0), (32, 32, 0), 1.844, 7.656, 0.013),
    ("glm47flash_train_t8192", (1, 0, 4), (1, 0, 4), 1.252, 0.0, 0.217),
    ("mellum2_train_t8192", (0, 4, 4), (0, 4, 4), 2.910, 0.0, 0.680),
    ("granite4h_micro_train_t8192", (10, 0, 0), (10, 1, 0), 2.5, 0.078, 0.014),
    ("laguna_s21_train_t8192", (1, 5, 1), (1, 5, 4), 1.424, 0.148, 0.002),
], ids=lambda v: v if isinstance(v, str) else None)
def test_keep_account_of_the_decoder_cells(cell, kept, eligible, kept_gib,
                                           unkept_gib, free_gib):
    model = _cell_model(cell)
    account = model.keep_account(CHIP_LIMIT)
    kinds = ("mlp", "attn", "moe")
    assert model.remat_keep_calls(CHIP_LIMIT) == kept
    assert account["kept"] == dict(zip(kinds, kept))
    assert account["eligible"] == dict(zip(kinds, eligible))
    # the terms are the estimate, to the byte
    assert set(account["terms"]) == {
        "params_grads_opt", "call_inputs", "flash_outputs", "head"}
    assert account["terms"] == model.step_peak_terms()
    assert sum(account["terms"].values()) == model.step_peak_estimate()
    # kept and unkept are every eligible call's bytes, each at its own
    every = (
        eligible[0] * model.remat_kept_bytes_per_call
        + sum(model._gqa_call_bytes)
        + eligible[2] * model.remat_kept_moe_bytes_per_call)
    assert account["kept_bytes"] + account["unkept_bytes"] == every
    (model.remat_kept_calls, model.remat_kept_attn_calls,
     model.remat_kept_moe_calls) = kept
    assert account["kept_bytes"] == model.remat_kept_bytes
    # what the third count left of the room under the reserve
    assert (account["bytes_limit"], account["reserve_bytes"]) == (
        CHIP_LIMIT, llama.REMAT_RESERVE_BYTES)
    assert account["free_bytes"] == (
        CHIP_LIMIT - llama.REMAT_RESERVE_BYTES - model.step_peak_estimate()
        - account["kept_bytes"])
    assert account["kept_bytes"] / GIB == pytest.approx(kept_gib, abs=5e-4)
    assert account["unkept_bytes"] / GIB == pytest.approx(unkept_gib, abs=5e-4)
    assert account["free_bytes"] / GIB == pytest.approx(free_gib, abs=5e-4)


@pytest.mark.parametrize("over, limit", [
    ({}, None), ({}, 0), ({"pp": 2}, 64 * GIB), ({"remat": False}, 64 * GIB),
], ids=["no_limit", "zero_limit", "pipeline", "no_remat"])
def test_no_rule_where_it_does_not_run(over, limit):
    assert Llama(dict(TINY, **over)).keep_account(limit) is None


def test_a_model_without_a_rule_has_no_account():
    from theanompi_tpu.models.base import TMModel
    from theanompi_tpu.models.resnet50 import ResNet50

    assert ResNet50.keep_account is TMModel.keep_account
    assert TMModel().keep_account(64 * GIB) is None
    assert TMModel.keep_bytes_limit is None


def test_a_limit_below_the_estimate_keeps_nothing_and_leaves_nothing():
    account = Llama(TINY).keep_account(llama.REMAT_RESERVE_BYTES + 1)
    assert account["kept"] == {"mlp": 0, "attn": 0, "moe": 0}
    assert (account["kept_bytes"], account["free_bytes"]) == (0, 0)
    assert account["unkept_bytes"] > 0


# -- the runtime's side, from a stub -----------------------------------------


class _Device:
    """A device whose runtime answers from a list of readings (the
    last one again once they are used up) and counts its calls."""

    def __init__(self, *readings, id=None):
        self.readings = list(readings)
        self.calls = 0
        if id is not None:
            self.id = id

    def memory_stats(self):
        self.calls += 1
        got = self.readings[min(self.calls, len(self.readings)) - 1]
        if isinstance(got, Exception):
            raise got
        return got


def _stats(in_use, peak=None, reserved=0, peak_reserved=0, limit=100):
    return {"bytes_in_use": in_use, "peak_bytes_in_use": peak or in_use,
            "bytes_reserved": reserved, "peak_bytes_reserved": peak_reserved,
            "bytes_limit": limit, "largest_free_block_bytes": limit - in_use,
            "num_allocs": 3}


@pytest.mark.parametrize("stats, limit", [
    ([{"bytes_limit": 7}, {"bytes_limit": 5}], 5),
    ([{"bytes_limit": 7}, None], None),         # the CPU reports none
    ([{"bytes_limit": 7}, {}], None),
    ([jax.errors.JaxRuntimeError("described device")], None),
], ids=["least", "cpu", "no_key", "described"])
def test_device_bytes_limit(stats, limit):
    assert memory.device_bytes_limit(map(_Device, stats)) == limit
    # the model reads its limit through the same reader
    assert llama.device_bytes_limit is memory.device_bytes_limit


def test_a_reading_names_every_device_and_a_missing_key_reads_none():
    got = memory.read_memory_stats([
        _Device({"bytes_in_use": 4, "bytes_limit": 9}, id=7),
        _Device(_stats(5)),
    ])
    assert [d["device"] for d in got] == [7, 1]     # its id, else its place
    assert set(got[0]) == {"device", *memory.FIELDS}
    assert got[0]["bytes_in_use"] == 4 and got[0]["peak_bytes_in_use"] is None
    assert got[0]["largest_free_block_bytes"] is None
    assert "num_allocs" not in got[1]
    assert memory.read_memory_stats([_Device(_stats(5)), _Device(None)]) is None
    assert memory.read_memory_stats([]) is None
    assert memory.read_memory_stats(jax.devices()[:1]) is None      # the CPU


def test_the_program_asks_the_runtime_in_one_place():
    asking = [
        str(p.relative_to(ROOT))
        for p in sorted((ROOT / "theanompi_tpu").rglob("*.py"))
        if re.search(r"\.memory_stats\(", p.read_text())
    ]
    assert asking == ["theanompi_tpu/obs/memory.py"]
    source = (ROOT / "theanompi_tpu/obs/memory.py").read_text()
    assert len(re.findall(r"\.memory_stats\(", source)) == 1


def test_samples_name_the_fullest_device_and_carry_every_device():
    ticks = iter(range(100))
    a = _Device(_stats(10), _stats(30, peak=60), _stats(30, peak=60), id=0)
    b = _Device(_stats(12), _stats(20, peak=40), _stats(35, peak=60), id=1)
    account = memory.MemoryAccount([a, b], clock=lambda: next(ticks))
    for at in ("setup.build_model", "setup.stage_data", "summary"):
        account.sample(at)
    got = account.as_dict()
    assert [(s["at"], s["fullest"]) for s in got["samples"]] == [
        ("setup.build_model", 1), ("setup.stage_data", 0), ("summary", 1)]
    assert [len(s["devices"]) for s in got["samples"]] == [2, 2, 2]
    # when a peak field rose reads off the samples
    assert [max(d["peak_bytes_in_use"] for d in s["devices"])
            for s in got["samples"]] == [12, 60, 60]
    assert (got["n_devices"], got["n_samples"]) == (2, 3)
    assert (a.calls, b.calls) == (3, 3)
    assert got["sample_s"] == 3         # the stub clock: a tick a sample
    assert got["resident_bytes"] == 35
    assert set(got) == {"rule", "n_devices", "n_samples", "sample_s",
                        "samples", "resident_bytes", "step_peak_bytes"}


@pytest.mark.parametrize("before, after, step_peak, lifetime", [
    # set-up held 9 before the step; the step reserves 6 beside 3
    (_stats(5, peak=9), _stats(3, peak=9, reserved=6, peak_reserved=6), 9, 15),
    # a decoder: the state stands, the step sets both peaks
    (_stats(7), _stats(7, peak=8, reserved=5, peak_reserved=5), 12, 13),
    # a set-up program had reserved more than the step, whose 4 stand
    (_stats(7, peak_reserved=6), _stats(7, reserved=4, peak_reserved=6),
     11, 13),
    # a runtime without the reservation's keys
    (_stats(7), {"bytes_in_use": 7, "peak_bytes_in_use": 12}, 7, 12),
], ids=["setup_peak", "decoder", "older_reservation", "no_keys"])
def test_step_peak_is_the_resident_bytes_and_the_standing_reservation(
        before, after, step_peak, lifetime):
    account = memory.MemoryAccount([_Device(before, after)])
    assert account.step_peak_bytes() is None        # nothing read yet
    account.sample("setup.resume")
    account.sample(memory.FIRST_FENCE)
    account.sample(memory.SUMMARY)
    got = account.as_dict()
    assert got["step_peak_bytes"] == step_peak
    # never over the benchmark's sum of the two peak fields
    # (benchmark/run.py ``memory_peak_bytes``), which the sample carries
    last = got["samples"][-1]["devices"][0]
    assert lifetime == ((last["peak_bytes_in_use"] or 0)
                        + (last["peak_bytes_reserved"] or 0))
    assert got["resident_bytes"] <= step_peak <= lifetime
    assert "memory: resident" in account.format()


def test_an_account_without_a_runtime_has_a_rule_and_no_bytes():
    account = memory.MemoryAccount(jax.devices()[:1])
    account.sample("setup.build_model")
    account.rule = {"kept_bytes": 1}
    got = account.as_dict()
    assert (got["n_samples"], got["samples"]) == (1, [])
    assert got["rule"] == {"kept_bytes": 1}
    assert (got["resident_bytes"], got["step_peak_bytes"]) == (None, None)


# -- the worker -----------------------------------------------------------------


def _run(n_epochs, **over):
    return bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama", config=dict(TINY, n_epochs=n_epochs, seed=3, **over),
        verbose=False,
    )


def test_a_cpu_run_has_the_rules_side_and_no_runtime_side(monkeypatch):
    monkeypatch.setattr(llama, "device_bytes_limit", lambda devices: 64 * GIB)
    res = _run(1)
    got = res["memory"]
    assert obs.last_memory_account() == got
    assert got["samples"] == [] and got["resident_bytes"] is None
    assert got["n_samples"] > 2 and got["sample_s"] > 0
    rule, model = got["rule"], res["model"]
    assert rule == model.keep_account(64 * GIB)
    assert rule["kept"] == {"mlp": 2, "attn": 2, "moe": 0}
    assert rule["kept"] == rule["eligible"]
    assert (rule["kept_bytes"], rule["unkept_bytes"]) == (
        res["remat_kept_bytes"], 0)
    assert rule["bytes_limit"] == model.keep_bytes_limit == 64 * GIB


def test_a_cpu_run_without_a_limit_has_no_rule():
    res = _run(1)
    assert res["memory"]["rule"] is None
    assert res["model"].keep_bytes_limit is None


@pytest.mark.parametrize("n_epochs", [1, 3])
def test_samples_at_phase_ends_the_first_fence_and_the_summary(
        monkeypatch, n_epochs):
    """``phases + 2`` samples a device whatever the run's length:
    nothing asks the runtime in the loop."""
    stubs = []

    def begin(devices):
        # the step's peak shows at the first fence; staging's before
        stubs.extend(
            _Device(*[_stats(10 + i, peak=20 + i) for i in range(5)],
                    _stats(30, peak=50), id=d.id)
            for d in devices)
        return memory.begin_memory_account(stubs)

    monkeypatch.setattr(bsp_worker, "begin_memory_account", begin)
    res = bsp_worker.run(
        devices=[0, 1], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama", config=dict(TINY, n_epochs=n_epochs, seed=3),
        verbose=False,
    )
    got = res["memory"]
    assert res["epochs"] == n_epochs and res["iterations"] == 2 * n_epochs
    at = [s["at"] for s in got["samples"]]
    # a phase's sample as it closes (nested ones first), under its name
    assert at == ["setup.data", "setup.build_model", "setup.stage_data",
                  "setup.compile_iter_fns", "setup.resume",
                  "first_fence", "summary"]
    assert set(at[:-2]) == set(res["setup_phases"]) - {"setup", "setup.warmup"}
    assert got["n_samples"] == len(at)
    assert [d.calls for d in stubs] == [len(at)] * 2
    assert got["n_devices"] == 2
    # the last reading's: the step's peak shows from the first fence on
    assert (got["resident_bytes"], got["step_peak_bytes"]) == (30, 30)
    assert obs.last_memory_account() == got
