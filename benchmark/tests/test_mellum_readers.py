"""The ``mellum`` cell's counts against a hand count and a count by
enumeration, its two readers on a small synthetic trace with a known
answer (and the accepted readers beside them, which must keep the full
layer's calls and the window layers' apart), and its check tool at the
rehearsal sizes."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_window
from benchmark import run as harness
from benchmark.layer_metrics import (attn_block_ms, attn_sliding_ms,
                                     block_named_share,
                                     flash_attention_roofline,
                                     moe_held_matmul_roofline,
                                     moe_held_rows_share,
                                     window_attention_roofline)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "mellum2_train_t8192"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_configuration_keeps_the_published_widths_and_cuts_three_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mellum2_12b_a2.5b_train_ep4_l4")
    config = harness.load_cell(CELL)["config"]
    published = config["published"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 98304 // 4)
    knobs = harness.program_knobs(config)
    assert knobs["n_experts"] == published["num_experts"] == 64
    assert (knobs["dim"], knobs["n_heads"], knobs["n_kv_heads"],
            knobs["head_dim"], knobs["ffn_dim"], knobs["moe_top_k"],
            knobs["moe_renormalize"], knobs["moe_experts_held"],
            knobs["sliding_window"], knobs["norm_eps"]) == (
        2304, 32, 4, 128, 896, 8, True, 16, 1024, 1e-6)
    # the lists stand whole; the stack is their first period
    assert knobs["layer_types"] == published["layer_types"]
    assert knobs["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert config["reference"]["kwargs"]["layer_types"] == (
        knobs["layer_types"][:4])
    assert knobs["rope_parameters"] == published["rope_parameters"]
    assert set(published["mlp_layer_types"]) == {"sparse"}
    # every size set here and not published is under ``assumed``
    assert {"moe_aux_coef", "router_gradient", "batch_size", "seq_len",
            "memory_analysis", "intermediate_size", "no_mtp_no_qk_norm",
            "sliding_window", "max_window_layers",
            "rope_layout"} <= set(config["assumed"])
    assert config["learns"]["last_chunk_loss_over_first"] < 1


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_published_group_is_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"Mellum2-12B-A2.5B-Instruct"' in line)
    config = harness.load_cell(CELL)["config"]
    assert config["published"] == row["config"]
    assert config["source"] == row["source_url"]


def test_the_flop_count_is_the_models_sum():
    """``flops_per_item`` through the dense function at kwargs that
    reproduce, per token forward: GQA attention of 4 layers at heads
    of 128 over a width of 2304, the held experts at balance (8 x
    16/64 = 2 experts a layer), the head over the vocabulary slice,
    and the attention products of one full layer (the triangle) and
    three window layers (the band) at T 8192."""
    kw = harness.load_cell(CELL)["config"]["flops_per_item"]["kwargs"]
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    expert = 3 * 2304 * 896
    assert (attn, expert) == (21_233_664, 6_193_152)
    multiplied = 4 * attn + 4 * 2 * expert + 2304 * 24576
    assert multiplied == 191_102_976
    full = 2 * 8192 * 4096
    window = 4 * (1024 - 1024 ** 2 / (2 * 8192)) * 4096
    assert (full, window) == (67_108_864, 15_728_640)
    attention = full + 3 * window
    assert flops.decoder_matmul_params(
        **{k: v for k, v in kw.items() if k != "seq_len"}) == multiplied
    per_token = flops.decoder_train_flops_per_token(**kw)
    assert per_token == 3 * (2 * multiplied + attention) == 1_489_502_208
    assert 16384 * per_token == pytest.approx(24.4e12, rel=1e-3)
    forward = per_token / 3
    # the shares the cell's ``why`` and the file's ``reduced`` give
    assert 3 * window / forward == pytest.approx(0.095, abs=0.001)
    assert full / forward == pytest.approx(0.135, abs=0.001)
    assert 2 * 4 * 2 * expert / forward == pytest.approx(0.20, abs=0.005)
    assert 2 * 2304 * 24576 / forward == pytest.approx(0.23, abs=0.005)
    # without the window attention would be 268 M operations a token
    assert 4 * full == pytest.approx(268e6, rel=2e-3)
    # what the chip holds
    held = (4 * (attn + 16 * expert + 2304 * 64 + 2 * 2304)
            + 2 * 2304 * 24576 + 2304)
    assert held == 595_153_152
    # the kernel's need is the same count a call: three window layers'
    # forward products a token
    spec = harness.load_cell(CELL)["config"]["kernels"]["window_attention"]
    ops, _ = flops_window.window_flash_call_need("fwd", **spec["shape"])
    assert 3 * ops / 16384 == 3 * window


@pytest.mark.parametrize("t,w", [(64, 1), (64, 8), (64, 24), (64, 64),
                                 (64, 100), (96, 33)], ids=str)
def test_the_bands_need_against_a_count_by_enumeration(t, w):
    """``T W - W^2 / 2`` pairs a head: the visible pairs counted one
    by one, less half a pair a key on the band's diagonal edge — the
    triangle's own convention (``T^2 / 2``), so a kernel that computes
    at least the visible pairs cannot read over 100 %; at ``W >= T``
    the need IS ``flops.flash_call_need``'s."""
    visible = sum(1 for i in range(t) for j in range(t)
                  if j <= i and i - j < w)
    shape = dict(batch=2, n_heads=3, seq_len=t, head_dim=16)
    for kind, products in (("fwd", 2), ("dkv", 3), ("dq", 1)):
        ops, nbytes = flops_window.window_flash_call_need(
            kind, window=w, **shape)
        pairs = ops / (products * 2 * 2 * 3 * 16)
        assert pairs == visible - min(w, t) / 2
        assert pairs < visible
        causal_ops, causal_bytes = flops.flash_call_need(kind, **shape)
        assert nbytes == causal_bytes       # a window moves no tensor less
        if w >= t:
            assert ops == causal_ops
        else:
            assert ops < causal_ops


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
CALL = ', custom_call_target="tpu_custom_call"'
FWD = "(bf16[64,8192,128]{2,1,0}, f32[64,1,8192]{2,1,0})"
DKV = "(bf16[64,8192,128]{2,1,0}, bf16[64,8192,128]{2,1,0})"
DQ = "bf16[64,8192,128]{2,1,0}"
BWD = "transpose(jvp(blk_attn))"
HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[2,8192,4096]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/attn_sliding/dot_general"),
    _line("window.2", FWD, "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_sliding/jit(_flash_window_jit)/"
          "pallas_call", CALL),
    _line("window.3", DKV, "custom-call",
          f"{STEP}/{BWD}/attn_sliding/jit(_flash_window_jit)/pallas_call",
          CALL),
    _line("window.4", DQ, "custom-call",
          f"{STEP}/{BWD}/attn_sliding/jit(_flash_window_jit)/pallas_call",
          CALL),
    _line("fusion.5", "bf16[2,8192,4096]{2,1,0}", "fusion",
          f"{STEP}/{BWD}/checkpoint/rematted_computation/blk_attn/"
          "attn_sliding/mul"),
    _line("fusion.6", "bf16[2,8192,4096]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/attn_full/dot_general"),
    _line("full.7", FWD, "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_full/jit(_flash_jit)/pallas_call",
          CALL),
    _line("full.8", DQ, "custom-call",
          f"{STEP}/{BWD}/attn_full/jit(_flash_jit)/pallas_call", CALL),
    _line("fusion.9", "bf16[2,8192,2304]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/dot_general"),       # wo: neither scope
    _line("ragged-dot-fwd.10", "bf16[131072,896]{1,0}", "custom-call",
          f"{STEP}/jvp(blk_ffn)/moe_experts/jit(_grouped_jit)/"
          "ragged-dot-fwd/pallas_call", CALL),
    _line("fusion.11", "bf16[16384,24576]{1,0}", "fusion",
          f"{STEP}/jvp(blk_head)/dot_general"),
    "}",
])
ROWS_HELD = [32000, 33500, 34000, 31572]
COUNTERS = {
    "moe_picks_per_step": 131072, "moe_experts_held": 16,
    "moe_rows_held": ROWS_HELD, "moe_load_max_over_mean": 1.7,
    "moe_rows_per_expert": [], "moe_dropped_picks": 0,
}
TIMES = [("fusion.1", 6), ("window.2", 4), ("window.3", 9), ("window.4", 5),
         ("fusion.5", 2), ("fusion.6", 3), ("full.7", 12), ("full.8", 14),
         ("fusion.9", 5), ("ragged-dot-fwd.10", 10), ("fusion.11", 20)]


def _facts(cell=CELL, hlo=HLO, counters=COUNTERS):
    """One run of a 2-step scan, 100 ms long: a ``while`` that holds
    every op."""
    at = [0]

    def op(name, ms):
        start = at[0]
        at[0] += int(ms * MS)
        return [name, start, at[0]]

    ops = [["while.1", 0, 100 * MS]] + [op(n, ms) for n, ms in TIMES]
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_scan_steps(1)", 0, 100 * MS]]}},
        "host": [], "text": {},
    }
    if counters:
        trace["moe_counters"] = counters
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_the_two_readers_on_a_known_trace():
    facts = _facts()
    # attn_sliding: 6 + 4 + 9 + 5 + 2 = 26 ms over 2 steps
    assert attn_sliding_ms.read(facts) == pytest.approx(13.0)
    # blk_attn holds both kinds and the output projection: 60 ms
    assert attn_block_ms.read(facts) == pytest.approx(30.0)
    spec = facts["cell"]["config"]["kernels"]["window_attention"]["shape"]
    least = sum(
        flops.least_seconds(
            *flops_window.window_flash_call_need(kind, **spec), PEAKS)[0]
        for kind in ("fwd", "dkv", "dq"))
    assert window_attention_roofline.read(facts) == pytest.approx(
        100 * least / 18e-3)
    # the accepted roofline reads the FULL layer's calls alone (a
    # forward and a dQ here), against the triangle's count
    full = facts["cell"]["config"]["kernels"]["flash_attention"]["shape"]
    least = sum(
        flops.least_seconds(*flops.flash_call_need(kind, **full), PEAKS)[0]
        for kind in ("fwd", "dq"))
    assert flash_attention_roofline.read(facts) == pytest.approx(
        100 * least / 26e-3)
    # a window call held to the triangle's count would read 4.3 times
    # its share: the two names keep them apart
    ops_band, _ = flops_window.window_flash_call_need("fwd", **spec)
    ops_full, _ = flops.flash_call_need("fwd", **full)
    assert ops_full / ops_band == pytest.approx(4096 / 960)
    # the window calls take their block from their own op_name
    assert block_named_share.read(facts) == pytest.approx(1.0)
    assert moe_held_rows_share.read(facts) == pytest.approx(34000 / 131072)
    assert 0 < moe_held_matmul_roofline.read(facts) < 100


def test_nothing_to_read_is_none_and_never_raises():
    """A text without the window calls and the scope (every parent's:
    its program knows no window and runs four full layers under
    ``_flash_jit``), another cell, no trace: no metric and no error."""
    parent = (HLO.replace("_flash_window_jit", "_flash_jit")
              .replace("attn_sliding/", "").replace("attn_full/", ""))
    for facts in (_facts(hlo=parent), _facts("olmoe_train_t4096", parent)):
        assert window_attention_roofline.read(facts) is None
        assert attn_sliding_ms.read(facts) is None
    assert flash_attention_roofline.read(_facts(hlo=parent)) is not None
    cell = harness.load_cell(CELL)
    for reader in (window_attention_roofline, attn_sliding_ms):
        assert reader.read({"cell": cell, "peaks": None}) is None
        assert reader.read(
            {"cell": cell, "peaks": PEAKS, "hlo_text": HLO,
             "trace": {"devices": {}, "host": [], "text": {}}}) is None


def test_the_check_tool_holds_the_rehearsal_and_fails_every_wrong_variant():
    from benchmark.tools import glm_check, mellum_check

    out = mellum_check.check(CELL, 7, sorted(mellum_check.VARIANTS),
                             rehearsal=True, control=True)
    right = out["right"]
    assert out["ok"] and right["ok"]
    assert right["loss_rel"] < 1e-6             # float32 on the CPU
    assert right["grad_rel_worst"] < 1e-3       # (the routers: a float32
    assert right["grad_rel_worst_routed"] < 1e-4    # subtraction's ulp)
    assert right["count_rel_worst"] == 0
    assert {"layers.0.wq", "layers.3.wo", "layers.2.router",
            "layers.1.we_down", "lm_head"} <= set(right["grad_rel"])
    assert len(right["rows_held"]) == 4
    # every wrong program fails a limit — in float32 the window off by
    # one too — and so does the reference itself at 3 mantissa bits
    assert out["failed"] == dict.fromkeys(
        [*sorted(mellum_check.VARIANTS), glm_check.CONTROL], True)
    assert set(mellum_check.SEPARABLE) < set(mellum_check.VARIANTS)
