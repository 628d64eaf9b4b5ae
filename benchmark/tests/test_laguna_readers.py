"""The ``laguna`` cell's file against the catalog and a hand count,
its two readers on a small synthetic trace with a known answer (and
the accepted readers beside them, which must read the two kinds'
calls at their own head counts), and its check tool at the rehearsal
sizes."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_window
from benchmark import run as harness
from benchmark.layer_metrics import (attn_block_ms, attn_gate_ms,
                                     attn_gate_open_min, attn_sliding_ms,
                                     flash_attention_roofline, gqa_proj_ms,
                                     moe_held_matmul_roofline,
                                     moe_held_rows_share, moe_shared_ms,
                                     window_attention_roofline)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "laguna_s21_train_t8192"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_configuration_keeps_the_published_widths_and_cuts_three_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "laguna_s_2.1_train_ep32_l5")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("bsp_steps", 1)
    config = harness.load_cell(CELL)["config"]
    published = config["published"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 100352 // 8)
    knobs = harness.program_knobs(config)
    assert knobs["n_experts"] == published["num_experts"] == 256
    assert (knobs["dim"], knobs["n_heads"], knobs["n_kv_heads"],
            knobs["head_dim"], knobs["ffn_dim"], knobs["dense_ffn_dim"],
            knobs["moe_top_k"], knobs["moe_renormalize"],
            knobs["moe_route_scale"], knobs["moe_experts_held"],
            knobs["sliding_window"], knobs["norm_eps"],
            knobs["attention_gate"]) == (
        3072, 48, 8, 128, 1024, 12288, 10, True, 2.5, 8, 512, 1e-6,
        "per-head")
    # the lists stand whole; the stack is the dense layer and the
    # period after it
    assert knobs["layer_types"] == published["layer_types"]
    assert knobs["n_heads_per_layer"] == (
        published["num_attention_heads_per_layer"])
    kw = config["reference"]["kwargs"]
    assert kw["layer_types"] == knobs["layer_types"][:5] == [
        "full_attention", *["sliding_attention"] * 3, "full_attention"]
    assert kw["heads_per_layer"] == knobs["n_heads_per_layer"][:5] == [
        48, 72, 72, 72, 48]
    assert published["mlp_layer_types"][:5] == ["dense", *["sparse"] * 4]
    assert knobs["rope_parameters"] == published["rope_parameters"]
    assert (knobs["seq_len"], knobs["batch_size"], knobs["steps_per_call"],
            knobs["first_k_dense"], knobs["moe_shared_experts"]) == (
        8192, 1, 2, 1, 1)
    # every reading of the config that is not a published key's value
    assert {"attention_gate", "heads_per_layer", "router", "shared_expert",
            "dense_first_layer", "rope_layout", "sliding_window",
            "no_qk_norm_no_mtp", "router_gradient", "moe_aux_coef",
            "batch_size", "seq_len", "memory_analysis"} <= set(
        config["assumed"])
    assert "32-way expert-parallel" in config["deployment"]
    assert config["learns"]["last_chunk_loss_over_first"] < 1


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_published_group_is_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"Laguna-S-2.1"' in line)
    config = harness.load_cell(CELL)["config"]
    assert config["published"] == row["config"]
    assert config["source"] == row["source_url"]


def test_what_the_chip_holds_and_the_kernels_needs():
    """The parameters of the cut by hand (12.09 GiB at 16 bytes each),
    and the kernels' shapes in the file: the two kinds' calls at their
    own head counts, the band's need the three sliding layers' forward
    products a token."""
    d, hd = 3072, 128
    attn = {h: 2 * d * h * hd + 2 * d * 8 * hd + d * h for h in (48, 72)}
    assert attn == {48: 44_187_648, 72: 63_135_744}
    expert, router, norms = 3 * d * 1024, d * 256, 2 * d
    layer0 = attn[48] + 3 * d * 12288 + norms
    sliding = attn[72] + 9 * expert + router + norms
    full = attn[48] + 9 * expert + router + norms
    assert (layer0, sliding, full) == (157_440_000, 148_862_976, 129_914_880)
    held = layer0 + 3 * sliding + full + 2 * d * 12544 + d
    assert held == 811_017_216
    assert 16 * held / 2 ** 30 == pytest.approx(12.09, abs=0.005)
    kernels = harness.load_cell(CELL)["config"]["kernels"]
    assert kernels["flash_attention"]["shape"]["n_heads"] == 48
    band = kernels["window_attention"]["shape"]
    assert (band["n_heads"], band["window"]) == (72, 512)
    ops, _ = flops_window.window_flash_call_need("fwd", **band)
    assert 3 * ops / 8192 == 3 * 18_284_544
    grouped = kernels["moe_grouped_matmul"]["shape"]
    assert grouped["rows"] == 81920 * 8 // 256 == 2560
    # the whole model by the same count: the name's 118B
    whole = (layer0 + 35 * (attn[72] + 257 * expert + router + norms)
             + 12 * (attn[48] + 257 * expert + router + norms)
             + 2 * d * 100352 + d)
    assert whole == pytest.approx(117.6e9, rel=1e-3)


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
CALL = ', custom_call_target="tpu_custom_call"'
FWD72 = "(bf16[72,8192,128]{2,1,0}, f32[72,1,8192]{2,1,0})"
DQ72 = "bf16[72,8192,128]{2,1,0}"
FWD48 = "(bf16[48,8192,128]{2,1,0}, f32[48,1,8192]{2,1,0})"
BWD = "transpose(jvp(blk_attn))"
HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[1,72,8192,128]{3,2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/attn_sliding/gqa_proj/dot_general"),
    _line("window.2", FWD72, "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_sliding/jit(_flash_window_jit)/"
          "pallas_call", CALL),
    _line("window.3", DQ72, "custom-call",
          f"{STEP}/{BWD}/attn_sliding/jit(_flash_window_jit)/pallas_call",
          CALL),
    _line("fusion.4", "f32[1,72,8192]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/attn_sliding/attn_gate/dot_general"),
    _line("fusion.5", "bf16[1,72,8192,128]{3,2,1,0}", "fusion",
          f"{STEP}/{BWD}/checkpoint/rematted_computation/blk_attn/"
          "attn_sliding/attn_gate/mul"),
    _line("fusion.6", "f32[1,48,8192]{2,1,0}", "fusion",
          f"{STEP}/{BWD}/attn_full/attn_gate/logistic"),
    _line("full.7", FWD48, "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_full/jit(_flash_jit)/pallas_call",
          CALL),
    _line("fusion.8", "bf16[1,8192,3072]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/dot_general"),       # wo: no inner scope
    _line("fusion.9", "bf16[8192,1024]{1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_shared/dot_general"),
    _line("ragged-dot-fwd.10", "bf16[5120,1024]{1,0}", "custom-call",
          f"{STEP}/jvp(blk_ffn)/moe_experts/jit(_grouped_jit)/"
          "ragged-dot-fwd/pallas_call", CALL),
    "}",
])
TIMES = [("fusion.1", 6), ("window.2", 4), ("window.3", 5), ("fusion.4", 1),
         ("fusion.5", 2), ("fusion.6", 3), ("full.7", 12), ("fusion.8", 5),
         ("fusion.9", 7), ("ragged-dot-fwd.10", 10)]
ROWS_HELD = [2400, 2700, 2610, 2530]
MOE = {
    "moe_picks_per_step": 81920, "moe_experts_held": 8,
    "moe_rows_held": ROWS_HELD, "moe_load_max_over_mean": 1.9,
    "moe_rows_per_expert": [], "moe_dropped_picks": 0,
}
GATE = {"attn_gate_open": [0.501, 0.498, 0.47, 0.503, 0.499]}


def _facts(cell=CELL, hlo=HLO, gate=GATE):
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
        "host": [], "text": {}, "moe_counters": MOE,
    }
    if gate:
        trace["attn_gate_counters"] = gate
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_the_two_readers_on_a_known_trace():
    facts = _facts()
    # attn_gate: 1 + 2 + 3 = 6 ms over 2 steps, both kinds' calls
    assert attn_gate_ms.read(facts) == pytest.approx(3.0)
    assert attn_gate_open_min.read(facts) == 0.47
    # the gate is inside the kind's scope and the block, outside
    # gqa_proj: 6 + 4 + 5 + 1 + 2 = 18 ms under attn_sliding
    assert attn_sliding_ms.read(facts) == pytest.approx(9.0)
    assert gqa_proj_ms.read(facts) == pytest.approx(3.0)
    assert attn_block_ms.read(facts) == pytest.approx(19.0)
    assert moe_shared_ms.read(facts) == pytest.approx(3.5)
    # each kind's calls against its own head count
    band = facts["cell"]["config"]["kernels"]["window_attention"]["shape"]
    least = sum(
        flops.least_seconds(
            *flops_window.window_flash_call_need(kind, **band), PEAKS)[0]
        for kind in ("fwd", "dq"))
    assert window_attention_roofline.read(facts) == pytest.approx(
        100 * least / 9e-3)
    full = facts["cell"]["config"]["kernels"]["flash_attention"]["shape"]
    least = flops.least_seconds(
        *flops.flash_call_need("fwd", **full), PEAKS)[0]
    assert flash_attention_roofline.read(facts) == pytest.approx(
        100 * least / 12e-3)
    assert moe_held_rows_share.read(facts) == pytest.approx(2700 / 81920)
    assert 0 < moe_held_matmul_roofline.read(facts) < 100


def test_nothing_to_read_is_none_and_never_raises(monkeypatch):
    """A text without the scope (every parent's, every ungated
    model's), a run without the counter, another cell, no trace: no
    metric and no error."""
    import theanompi_tpu.obs as obs

    parent = HLO.replace("attn_gate/", "")
    monkeypatch.setattr(obs, "last_gate_counters", lambda: None)
    for facts in (_facts(hlo=parent, gate=None),
                  _facts("mellum2_train_t8192", parent, None)):
        assert attn_gate_ms.read(facts) is None
        assert attn_gate_open_min.read(facts) is None
    # a program from before PR 50 has no such function at all
    monkeypatch.delattr(obs, "last_gate_counters")
    assert attn_gate_open_min.read(_facts(gate=None)) is None
    cell = harness.load_cell(CELL)
    for reader in (attn_gate_ms, attn_gate_open_min):
        assert reader.read({"cell": cell, "peaks": None}) is None
        assert reader.read(
            {"cell": cell, "peaks": PEAKS, "hlo_text": parent, "scan_k": 2,
             "trace": {"devices": {}, "host": [], "text": {}}}) is None


def test_the_check_tool_holds_the_rehearsal_and_fails_every_wrong_variant():
    from benchmark.tools import glm_check, laguna_check

    out = laguna_check.check(CELL, 7, sorted(laguna_check.VARIANTS),
                             rehearsal=True, control=True)
    right = out["right"]
    assert out["ok"] and right["ok"]
    assert right["loss_rel"] < 1e-6             # float32 on the CPU
    assert right["grad_rel_worst"] < 1e-3       # (the routers: a float32
    assert right["grad_rel_worst_routed"] < 1e-4    # subtraction's ulp)
    assert right["count_rel_worst"] == 0
    assert {"layers.0.w_attn_gate", "layers.0.w_gate", "layers.4.wo",
            "layers.2.router", "layers.1.we_down", "layers.3.ws_up",
            "lm_head"} <= set(right["grad_rel"])
    assert len(right["rows_held"]) == 4 and len(right["attn_gate_open"]) == 5
    # every wrong program fails a limit, and so does the reference
    # itself at 3 mantissa bits; all but ``bf16_statistics``, which at
    # 8 experts and 64 tokens flips no pick: inside the chip's limits
    # (they are bf16's own scatter) and four orders from the right
    # program all the same
    failed = dict(out["failed"])
    assert failed.pop("bf16_statistics") is False
    assert failed == dict.fromkeys(
        [*sorted(set(laguna_check.VARIANTS) - {"bf16_statistics"}),
         glm_check.CONTROL], True)
    bf16 = out["variants"]["bf16_statistics"]
    assert bf16["loss_rel"] > 100 * right["loss_rel"]
    assert bf16["grad_rel_worst"] > 1000 * right["grad_rel_worst"]
    # the builds without a leaf were held on the leaves they have
    assert "layers.0.w_attn_gate" not in out["variants"]["no_gate"]["grad_rel"]
    assert "layers.1.ws_up" not in (
        out["variants"]["no_shared_expert"]["grad_rel"])
