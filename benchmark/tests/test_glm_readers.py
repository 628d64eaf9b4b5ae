"""The ``glm4_moe_lite`` cell's counts against a hand count, its five
readers on a small synthetic trace with a known answer, and its check
tool at the rehearsal sizes."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_moe
from benchmark import run as harness
from benchmark.layer_metrics import (_scopes, attn_block_ms, mla_proj_ms,
                                     moe_held_matmul_roofline,
                                     moe_held_rows_share, moe_shared_ms,
                                     moe_step_share, mtp_step_share)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "glm47flash_train_t8192"


def test_the_configuration_keeps_the_published_widths_and_cuts_three_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "glm_4.7_flash_train_ep8_l5")
    config = harness.load_cell(CELL)["config"]
    published = config["published"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 154880 // 8)
    knobs = harness.program_knobs(config)
    assert knobs["n_experts"] == published["n_routed_experts"] == 64
    assert (knobs["dim"], knobs["n_heads"], knobs["q_lora_rank"],
            knobs["kv_lora_rank"], knobs["qk_nope_head_dim"],
            knobs["qk_rope_head_dim"], knobs["v_head_dim"],
            knobs["dense_ffn_dim"], knobs["ffn_dim"], knobs["moe_top_k"],
            knobs["moe_route_scale"], knobs["moe_shared_experts"],
            knobs["mtp_depth"], knobs["moe_experts_held"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 4, 1.8, 1, 1, 8)
    # every size set here and not published is under ``assumed``
    assert {"moe_bias_rate", "mtp_coef", "batch_size", "memory_analysis",
            "eh_proj_order", "balance_loss"} <= set(config["assumed"])


def test_the_flop_count_is_the_models_sum():
    """``flops_per_item`` through the dense function at kwargs that
    reproduce, per token forward: latent attention of 6 blocks, the
    dense FFN, 5 shared experts, the held routed experts at balance
    (k x 8/64 = half an expert an expert layer), ``eh_proj`` and two
    heads over the vocabulary slice, and the causal attention of 6
    blocks at T 8192 and 20 heads of 256."""
    kw = harness.load_cell(CELL)["config"]["flops_per_item"]["kwargs"]
    mla = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
           + 5120 * 2048)
    expert = 3 * 2048 * 1536
    assert (mla, expert) == (21_757_952, 9_437_184)
    multiplied = (6 * mla + 3 * 2048 * 10240 + 5 * expert
                  + 5 * 4 * 8 / 64 * expert + 4096 * 2048
                  + 2 * 2048 * 19360)
    assert multiplied == 351_928_320
    attention = 2 * 2 * 8192 * 20 * 256 * 6 / 2
    assert attention == pytest.approx(503.3e6, rel=1e-4)
    assert flops.decoder_matmul_params(
        **{k: v for k, v in kw.items() if k != "seq_len"}) == multiplied
    per_token = flops.decoder_train_flops_per_token(**kw)
    assert per_token == 3 * (2 * multiplied + attention)
    assert 16384 * per_token == pytest.approx(59.3e12, rel=2e-3)
    # attention (projections, scores, values) is 63 % of the forward
    assert (2 * 6 * mla + attention) / (per_token / 3) == pytest.approx(
        0.63, abs=0.01)
    # what the chip holds: 706.5 M parameters
    held = (mla + 3 * 2048 * 10240 + 2 * 2048
            + 5 * (mla + 9 * expert + 2048 * 64 + 2 * 2048)
            + 2 * 2048 * 19360 + 2048
            + 4096 * 2048 + 3 * 2048)
    assert held == pytest.approx(706.5e6, rel=1e-3)


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
CALL = ', custom_call_target="tpu_custom_call"'
HLO = "\n".join([
    "%fused_wgrad (p: f32[8]) -> f32[8] {",
    _line("dot.1", "f32[2048,768]{1,0}", "convolution",
          f"{STEP}/transpose(jvp(blk_attn))/mla_proj/dot_general"),
    _line("mul.1", "f32[2048,768]{1,0}", "multiply",
          f"{STEP}/opt_update/mul"),
    "}",
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[2,8192,5120]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/mla_proj/dot_general"),
    _line("fusion.2", "bf16[2,8192,768]{2,1,0}", "fusion",
          f"{STEP}/transpose(jvp(blk_attn))/checkpoint/"
          "rematted_computation/blk_attn/mla_proj/mul"),
    # a weight gradient fused with its Adam update: by its product
    _line("fusion.3", "f32[2048,768]{1,0}", "fusion",
          f"{STEP}/opt_update/mul", ", calls=%fused_wgrad"),
    _line("_flash_jit.4", "(bf16[40,8192,256]{2,1,0}, f32[40,1,8192]{2,1,0})",
          "custom-call", f"{STEP}/jvp(blk_attn)/jit(_flash_jit)/pallas_call",
          CALL),
    _line("fusion.5", "bf16[2,8192,1536]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_shared/dot_general"),
    _line("ragged-dot-fwd.6", "bf16[65536,1536]{1,0}", "custom-call",
          f"{STEP}/jvp(blk_ffn)/moe_experts/jit(_grouped_jit)/"
          "ragged-dot-fwd/pallas_call", CALL),
    _line("ragged-dot-drhs.7", "f32[8,2048,1536]{2,1,0}", "custom-call",
          f"{STEP}/transpose(jvp(blk_ffn))/moe_experts/jit(_grouped_jit)/"
          "ragged-dot-drhs/pallas_call", CALL),
    _line("fusion.8", "bf16[2,8192,2048]{2,1,0}", "fusion",
          f"{STEP}/jvp(mtp)/blk_mtp_in/dot_general"),
    _line("_flash_jit.9", "(bf16[40,8192,256]{2,1,0}, f32[40,1,8192]{2,1,0})",
          "custom-call",
          f"{STEP}/jvp(mtp)/blk_attn/jit(_flash_jit)/pallas_call", CALL),
    _line("fusion.10", "bf16[2,8192,5120]{2,1,0}", "fusion",
          f"{STEP}/transpose(jvp(mtp))/jvp(mtp)/checkpoint/blk_attn/"
          "mla_proj/dot_general"),
    _line("fusion.11", "bf16[16384,19360]{1,0}", "fusion",
          f"{STEP}/jvp(blk_head)/while/body/dot_general"),
    "}",
])
ROWS_HELD = [8000, 8400, 9000, 7900, 8100]
COUNTERS = {
    "moe_picks_per_step": 65536, "moe_experts_held": 8,
    "moe_rows_held": ROWS_HELD, "moe_load_max_over_mean": 1.4,
    "moe_rows_per_expert": [], "moe_dropped_picks": 0,
    "moe_bias_abs_max": 0.05,
}


def _facts(cell=CELL, hlo=HLO, counters=COUNTERS):
    """One run of a 2-step scan, 100 ms long: a ``while`` that holds
    every op."""
    at = [0]

    def op(name, ms):
        start = at[0]
        at[0] += int(ms * MS)
        return [name, start, at[0]]

    ops = [
        op("fusion.1", 10), op("fusion.2", 6), op("fusion.3", 4),
        op("_flash_jit.4", 20), op("fusion.5", 3),
        op("ragged-dot-fwd.6", 2), op("ragged-dot-drhs.7", 3),
        op("fusion.8", 1), op("_flash_jit.9", 5), op("fusion.10", 2),
        op("fusion.11", 14),
    ]
    ops.insert(0, ["while.1", 0, 100 * MS])
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_scan_steps(1)", 0, 100 * MS]]}},
        "host": [], "text": {},
    }
    if counters:
        trace["moe_counters"] = counters
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_scopes_are_read_from_the_compiled_text():
    under = {s: set(_scopes._under(HLO, s)) & {
        f"fusion.{i}" for i in range(1, 12)} | (
        set(_scopes._under(HLO, s)) & {"_flash_jit.4", "_flash_jit.9"})
        for s in ("mla_proj", "moe_shared", "mtp")}
    # the Adam-fused weight gradient counts by its product
    assert under["mla_proj"] == {"fusion.1", "fusion.2", "fusion.3",
                                 "fusion.10"}
    assert under["moe_shared"] == {"fusion.5"}
    assert under["mtp"] == {"fusion.8", "_flash_jit.9", "fusion.10"}


def test_the_five_readers_on_a_known_trace():
    facts = _facts()
    # mla_proj: 10 + 6 + 4 + 2 = 22 ms over 2 steps
    assert mla_proj_ms.read(facts) == pytest.approx(11.0)
    assert moe_shared_ms.read(facts) == pytest.approx(1.5)
    # mtp: 1 + 5 + 2 = 8 of 100 ms
    assert mtp_step_share.read(facts) == pytest.approx(0.08)
    assert moe_held_rows_share.read(facts) == pytest.approx(9000 / 65536)
    # two grouped calls in 5 ms; each needs what 8280 rows (the mean
    # over the layers of the counter's last step) of 2048 x 1536 on 8
    # experts need
    ops, nbytes = flops_moe.grouped_matmul_need(
        rows=sum(ROWS_HELD) / 5, d_model=2048, d_expert=1536,
        n_experts=8)
    least = 2 * flops.least_seconds(ops, nbytes, PEAKS)[0]
    assert moe_held_matmul_roofline.read(facts) == pytest.approx(
        100 * least / 5e-3)
    assert 0 < moe_held_matmul_roofline.read(facts) < 100
    # and the accepted readers find the cell's kernels and blocks
    assert moe_step_share.read(facts) == pytest.approx(0.05)
    # blk_attn: 10 + 6 + 4 + 20 + 5 + 2 = 47 ms over 2 steps
    assert attn_block_ms.read(facts) == pytest.approx(23.5)


def test_nothing_to_read_is_none_and_never_raises():
    """A text without the scopes (every parent's), another cell, no
    trace, no counter: no metric and no error."""
    plain = (HLO.replace("mla_proj/", "").replace("moe_shared/", "")
             .replace("jvp(mtp)/", "").replace("transpose(jvp(mtp))/", ""))
    readers = (mla_proj_ms, moe_shared_ms, mtp_step_share)
    for facts in (_facts(hlo=plain), _facts("olmoe_train_t4096", plain)):
        for reader in readers:
            assert reader.read(facts) is None
    cell = harness.load_cell(CELL)
    for reader in readers + (moe_held_rows_share, moe_held_matmul_roofline):
        assert reader.read({"cell": cell, "peaks": None}) is None
    # a program whose experts are all held (OLMoE; every parent) has
    # no ``moe_rows_held``; one that never ran an expert step nothing
    from theanompi_tpu.obs import routing

    all_held = {k: v for k, v in COUNTERS.items()
                if not k.startswith(("moe_rows_held", "moe_experts_held"))}
    for counters in (all_held, None):
        routing._LAST = None
        facts = _facts(counters=counters)
        assert moe_held_rows_share.read(facts) is None
        assert moe_held_matmul_roofline.read(facts) is None


def test_the_check_tool_holds_the_rehearsal_and_fails_every_wrong_variant():
    from benchmark.tools import glm_check

    out = glm_check.check(CELL, 7, sorted(glm_check.VARIANTS),
                          rehearsal=True, control=True)
    right = out["right"]
    assert out["ok"] and right["ok"]
    assert right["loss_rel"] < 1e-6             # float32 on the CPU
    assert right["grad_rel_worst"] < 1e-4
    assert right["count_rel_worst"] == 0 and right["bias_moved_alike"] == 1
    assert {"layers.1.q_a_norm", "layers.1.kv_a_norm", "mtp.eh_proj",
            "layers.1.ws_gate", "layers.2.router", "mtp.block.router",
            "layers.0.w_gate"} <= set(right["grad_rel"])
    # a share's routers get no gradient, in the program as in the
    # reference, and are held to exactly that
    assert right["grad_rel"]["layers.2.router"] == 0
    # every wrong program fails a limit, and so does the reference
    # itself computed at 3 mantissa bits
    assert out["failed"] == dict.fromkeys(
        [*glm_check.VARIANTS, glm_check.CONTROL], True)
