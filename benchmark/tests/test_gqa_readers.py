"""``gqa_proj_ms`` on a small synthetic trace with a known answer: a
number where the step's compiled text holds the scope ``gqa_proj``
(forward, replay, backward, a weight gradient fused with its Adam
update by its product; a window layer's scope around it), ``None``
without the scope, as every program from before PR 45 is."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.layer_metrics import (_scopes, attn_block_ms, attn_sliding_ms,
                                     gqa_proj_ms)
from test_glm_readers import CALL, MS, STEP, _line

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("mellum2_train_t8192", "ouro_train_t4096", "mistral7b_train_t4096",
         "olmoe_train_t4096")

HLO = "\n".join([
    "%fused_wgrad (p: f32[8]) -> f32[8] {",
    _line("dot.1", "f32[2304,4096]{1,0}", "convolution",
          f"{STEP}/transpose(jvp(blk_attn))/attn_sliding/gqa_proj/"
          "dot_general"),
    _line("mul.1", "f32[2304,4096]{1,0}", "multiply",
          f"{STEP}/opt_update/mul"),
    "}",
    "%body (p: f32[8]) -> f32[8] {",
    # q's product, written in the kernels' layout
    _line("fusion.1", "bf16[2,32,8192,128]{3,2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/attn_sliding/gqa_proj/dot_general"),
    # the rotation pass, replayed
    _line("fusion.2", "bf16[2,32,8192,128]{3,2,1,0}", "fusion",
          f"{STEP}/transpose(jvp(blk_attn))/checkpoint/"
          "rematted_computation/blk_attn/attn_full/gqa_proj/add"),
    # a weight gradient fused with its Adam update: by its product
    _line("fusion.3", "f32[2304,4096]{1,0}", "fusion",
          f"{STEP}/opt_update/mul", ", calls=%fused_wgrad"),
    # the kernel and ``wo`` lie outside the scope
    _line("_flash_window_jit.4",
          "(bf16[64,8192,128]{2,1,0}, f32[64,1,8192]{2,1,0})",
          "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_sliding/jit(_flash_window_jit)/"
          "pallas_call", CALL),
    _line("fusion.5", "bf16[2,8192,2304]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/dot_general"),
    _line("fusion.6", "bf16[16384,24576]{1,0}", "fusion",
          f"{STEP}/jvp(blk_head)/dot_general"),
    "}",
])


def _facts(cell, hlo=HLO):
    """One run of a 2-step scan, 60 ms long: a ``while`` that holds
    every op."""
    at = [0]

    def op(name, ms):
        start = at[0]
        at[0] += int(ms * MS)
        return [name, start, at[0]]

    ops = [op("fusion.1", 10), op("fusion.2", 6), op("fusion.3", 4),
           op("_flash_window_jit.4", 20), op("fusion.5", 6),
           op("fusion.6", 14)]
    ops.insert(0, ["while.1", 0, 60 * MS])
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_scan_steps(1)", 0, 60 * MS]]}},
        "host": [], "text": {},
    }
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": None}


def test_the_benchmark_names_the_metric_in_the_four_gqa_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    like = next(m for m in bench["per_layer"] if m["name"] == "mla_proj_ms")
    assert entry["name"] == "gqa_proj_ms"
    assert {k: v for k, v in entry.items() if k not in ("name", "workloads")
            } == {k: v for k, v in like.items()
                  if k not in ("name", "workloads")}
    assert set(entry["workloads"]) == set(CELLS)


def test_the_scope_is_read_from_the_compiled_text():
    under = set(_scopes._under(HLO, "gqa_proj"))
    # forward, replay, and the Adam-fused weight gradient by its product
    assert under == {"dot.1", "fusion.1", "fusion.2", "fusion.3"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_number_with_the_scope(cell):
    facts = _facts(cell)
    # 10 + 6 + 4 = 20 ms over 2 steps
    assert gqa_proj_ms.read(facts) == pytest.approx(10.0)
    # part of the block: 10 + 6 + 4 + 20 + 6 = 46 ms over 2 steps
    assert attn_block_ms.read(facts) == pytest.approx(23.0)
    # and nested inside a window layer's scope: 10 + 4 + 20 over 2
    assert attn_sliding_ms.read(facts) == pytest.approx(17.0)


@pytest.mark.parametrize("cell", CELLS)
def test_nothing_to_read_is_none_and_never_raises(cell):
    """A text without the scope (the parent's), no trace: no metric
    and no error."""
    plain = HLO.replace("gqa_proj/", "")
    assert gqa_proj_ms.read(_facts(cell, plain)) is None
    assert gqa_proj_ms.read(
        {"cell": harness.load_cell(cell), "peaks": None}) is None
