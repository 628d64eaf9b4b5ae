"""The ``nemotron3`` cell's file against the catalog and a hand count,
its new reader (``moe_latent_ms``) on a small synthetic trace with a
known answer — and the accepted readers beside it, which must read
this model's scopes (the mixers' at a head share, the experts' products
in the latent) — and the reader's silence on a program without the
scope."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_moe, flops_ssd
from benchmark import run as harness
from benchmark.layer_metrics import (attn_block_ms, ffn_block_ms,
                                     flash_attention_roofline, gqa_proj_ms,
                                     moe_dispatch_ms,
                                     moe_held_matmul_roofline,
                                     moe_held_rows_share, moe_latent_ms,
                                     moe_shared_ms, ssd_scan_ms,
                                     ssd_scan_roofline, ssm_block_ms,
                                     ssm_conv_ms)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "nemotron3_super_train_t8192"
NAME = "nemotron_3_super_120b_a12b_train_ep64_tp8_l11"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers", "n_routed_experts", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "vocab_size", "num_nextn_predict_layers"}


def test_the_configuration_keeps_the_published_widths_and_cuts_eight_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("bsp_steps", 1)
    config = harness.load_cell(CELL)["config"]
    published = config["published"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == REDUCED
    assert set(config["reduced"]) == REDUCED
    assert {k: config[k] for k in REDUCED} == {
        "num_hidden_layers": 11, "n_routed_experts": 8,
        "mamba_num_heads": 16, "n_groups": 1, "num_attention_heads": 4,
        "num_key_value_heads": 1, "vocab_size": 131072 // 8,
        "num_nextn_predict_layers": 0}
    # no width among them: every hidden, latent, state and head size
    # and the experts a token are as published
    knobs = harness.program_knobs(config)
    assert (knobs["dim"], knobs["head_dim"], knobs["ffn_dim"],
            knobs["moe_latent_dim"], knobs["moe_shared_dim"],
            knobs["mamba_d_head"], knobs["mamba_d_state"],
            knobs["mamba_chunk_size"], knobs["mamba_d_conv"],
            knobs["moe_top_k"], knobs["norm_eps"]) == (
        4096, 128, 2688, 1024, 5376, 64, 128, 128, 4, 22, 1e-5)
    # the published counts beside the held ones
    assert (knobs["n_experts"], knobs["moe_experts_held"]) == (512, 8)
    assert (knobs["mamba_n_heads"], knobs["mamba_heads_held"],
            knobs["mamba_n_groups"], knobs["mamba_groups_held"]) == (
        128, 16, 8, 1)
    assert (knobs["n_heads"], knobs["n_heads_held"], knobs["n_kv_heads"],
            knobs["n_kv_heads_held"]) == (32, 4, 2, 1)
    assert (knobs["hidden_act"], knobs["moe_scoring"],
            knobs["moe_route_scale"], knobs["moe_renormalize"],
            knobs["position_embedding_type"], knobs["mtp_depth"]) == (
        "relu2", "sigmoid", 5, True, "nope", 0)
    # the pattern stands whole; the stack is its first 11 characters
    assert knobs["layer_types"] == published["hybrid_override_pattern"]
    assert len(knobs["layer_types"]) == 88
    kw = config["reference"]["kwargs"]
    assert kw["pattern"] == knobs["layer_types"][:11] == "MEMEMEM*EME"
    assert config["layer_types"] == [
        {"M": "mamba", "E": "experts", "*": "attention"}[b]
        for b in kw["pattern"]]
    assert (knobs["seq_len"], knobs["batch_size"],
            knobs["steps_per_call"]) == (8192, 2, 2)
    assert {"no_position", "router", "latent", "relu2", "gate_before_norm",
            "intermediate_size", "rescale_prenorm_residual",
            "bias_rate_and_balance_loss", "router_gradient",
            "layer_types"} <= set(config["assumed"])
    assert "8 pipeline stages of 11 blocks" in config["deployment"]
    assert "64 chips" in config["deployment"]
    assert config["learns"]["last_chunk_loss_over_first"] < 1


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_published_group_is_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
    config = harness.load_cell(CELL)["config"]
    assert config["published"] == row["config"]
    assert config["source"] == row["source_url"]


def test_what_the_chip_holds_and_the_operations_a_token():
    """The parameters of the cut by hand (10.44 GiB at 16 bytes each),
    the whole model by the same count (the name's 120B-A12B), and
    ``flops_per_item``'s kwargs against the sum they stand for."""
    d, lat, f = 4096, 1024, 2688
    m_block = (d * (1024 + 1024 + 128 + 128 + 16) + 1024 * d
               + 4 * 1280 + 1280 + 3 * 16 + 1024 + d)
    attn = d * (512 + 128 + 128) + 512 * d + d
    expert = 2 * lat * f
    e_block = d * 512 + 2 * d * lat + 2 * d * 5376 + 8 * expert + d
    assert (m_block, attn, e_block) == (13_708_592, 5_246_976, 98_570_240)
    held = 5 * m_block + attn + 5 * e_block + 2 * d * 16384 + d
    config = harness.load_cell(CELL)["config"]
    assert held == config["parameters"]["held"] == 700_862_960
    assert 16 * held / 2 ** 30 == pytest.approx(10.44, abs=0.005)
    m_whole = (d * (8192 + 10240 + 128) + 8192 * d + 5 * 10240 + 3 * 128
               + 8192 + d)
    attn_whole = d * (4096 + 256 + 256) + 4096 * d + d
    e_whole = e_block + 504 * expert
    whole = 40 * m_whole + 8 * attn_whole + 40 * e_whole + 2 * d * 131072 + d
    assert whole == pytest.approx(120.7e9, rel=2e-3)
    active = whole - 40 * (512 - 22) * expert - d * 131072
    assert active == pytest.approx(12.2e9, rel=1e-2)
    # a token's multiplied parameters forward, at the shares
    multiplied = (5 * (d * 2320 + 1024 * d) + d * 1280
                  + 5 * (d * 512 + 2 * d * lat + 2 * d * 5376
                         + 22 * 8 * expert // 512) + d * 16384)
    spec = config["flops_per_item"]
    widths = {k: v for k, v in spec["kwargs"].items() if k != "seq_len"}
    assert flops.decoder_matmul_params(**widths) == multiplied == 422_928_384
    scan = flops_ssd.ssd_flops_per_token(
        n_heads=16, head_dim=64, d_state=128, n_groups=1, chunk=128)
    assert scan == 671_744
    want = 3 * (2 * multiplied + 2 * 8192 * 4 * 128 + 5 * scan)
    assert flops.decoder_train_flops_per_token(**spec["kwargs"]) == want
    kernels = config["kernels"]
    assert kernels["moe_grouped_matmul"]["shape"] == {
        "rows": 22 * 16384 * 8 // 512, "d_model": lat, "d_expert": f,
        "n_experts": 8, "dtype_bytes": 2}
    assert kernels["ssd_scan"]["shape"]["n_heads"] == 16
    assert kernels["ssd_scan"]["shape"]["chunk"] == 128
    assert kernels["flash_attention"]["shape"]["n_heads"] == 4


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
CALL = ', custom_call_target="tpu_custom_call"'
BWD = "transpose(jvp(blk_ffn))"
HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[16384,1024]{1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_latent/dot_general"),
    _line("fusion.2", "bf16[16384,4096]{1,0}", "fusion",
          f"{STEP}/{BWD}/checkpoint/rematted_computation/blk_ffn/"
          "moe_latent/dot_general"),
    _line("fusion.3", "f32[4096,1024]{1,0}", "fusion",
          f"{STEP}/{BWD}/moe_latent/dot_general"),
    _line("fusion.4", "bf16[16384,5376]{1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_shared/dot_general"),
    _line("fusion.5", "f32[16384,512]{1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_route/dot_general"),
    _line("ragged-dot-fwd.6", "bf16[11264,2688]{1,0}", "custom-call",
          f"{STEP}/jvp(blk_ffn)/moe_experts/jit(_grouped_jit)/"
          "ragged-dot-fwd/pallas_call", CALL),
    _line("fusion.7", "bf16[11264,1024]{1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/moe_dispatch/gather"),
    _line("fusion.8", "bf16[2,8192,1280]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssm_conv/mul"),
    _line("ssd-chunk-fwd.9", "bf16[2,1024,8192]{2,1,0}", "custom-call",
          f"{STEP}/jvp(blk_ssm)/ssd_scan/ssd-chunk-fwd/pallas_call", CALL),
    _line("fusion.10", "bf16[2,8192,1024]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssm_proj/dot_general"),
    _line("fusion.11", "bf16[2,4,8192,128]{3,2,1,0}", "fusion",
          f"{STEP}/jvp(blk_attn)/gqa_proj/dot_general"),
    _line("flash.12",
          "(bf16[8,8192,128]{2,1,0}, f32[8,1,8192]{2,1,0})", "custom-call",
          f"{STEP}/jvp(blk_attn)/jit(_flash_jit)/pallas_call", CALL),
    "}",
])
TIMES = [("fusion.1", 2), ("fusion.2", 3), ("fusion.3", 5), ("fusion.4", 8),
         ("fusion.5", 1), ("ragged-dot-fwd.6", 6), ("fusion.7", 4),
         ("fusion.8", 2), ("ssd-chunk-fwd.9", 10), ("fusion.10", 4),
         ("fusion.11", 1), ("flash.12", 3)]
ROWS_HELD = [5600, 5700, 5500, 5800, 5650]
MOE = {
    "moe_picks_per_step": 22 * 16384, "moe_experts_held": 8,
    "moe_rows_held": ROWS_HELD, "moe_load_max_over_mean": 1.3,
    "moe_rows_per_expert": [], "moe_dropped_picks": 0,
}


def _facts(cell=CELL, hlo=HLO):
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
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_the_new_reader_and_the_accepted_ones_on_a_known_trace():
    facts = _facts()
    # moe_latent: 2 + 3 + 5 = 10 ms over 2 steps: forward, replay and
    # the weight gradient's product
    assert moe_latent_ms.read(facts) == pytest.approx(5.0)
    # inside the block, outside the shared expert's and the routed
    # path's scopes
    assert moe_shared_ms.read(facts) == pytest.approx(4.0)
    assert moe_dispatch_ms.read(facts) == pytest.approx((1 + 4) / 2)
    assert ffn_block_ms.read(facts) == pytest.approx(
        (2 + 3 + 5 + 8 + 1 + 6 + 4) / 2)
    # the mixers' scopes at the share, attention's beside them
    assert ssm_block_ms.read(facts) == pytest.approx(8.0)
    assert ssd_scan_ms.read(facts) == pytest.approx(5.0)
    assert ssm_conv_ms.read(facts) == pytest.approx(1.0)
    assert attn_block_ms.read(facts) == pytest.approx(2.0)
    assert gqa_proj_ms.read(facts) == pytest.approx(0.5)
    shapes = facts["cell"]["config"]["kernels"]
    # five M blocks' scans, forward and backward, at 16 heads, one
    # group, chunk 128 (the file's ``layer_types`` counts the blocks)
    least = 5 * sum(
        flops.least_seconds(*flops_ssd.ssd_call_need(
            kind, **shapes["ssd_scan"]["shape"]), PEAKS)[0]
        for kind in ("fwd", "bwd"))
    assert ssd_scan_roofline.read(facts) == pytest.approx(
        100 * 2 * least / 10e-3)
    least = flops.least_seconds(*flops.flash_call_need(
        "fwd", **shapes["flash_attention"]["shape"]), PEAKS)[0]
    assert flash_attention_roofline.read(facts) == pytest.approx(
        100 * least / 3e-3)
    # the grouped products at the LATENT's width and the counter's rows
    assert moe_held_rows_share.read(facts) == pytest.approx(
        5800 / (22 * 16384))
    least = flops.least_seconds(*flops_moe.grouped_matmul_need(
        rows=sum(ROWS_HELD) / 5, d_model=1024, d_expert=2688, n_experts=8),
        PEAKS)[0]
    assert moe_held_matmul_roofline.read(facts) == pytest.approx(
        100 * least / 6e-3)


def test_nothing_to_read_is_none_and_never_raises():
    """A text without the scope (the parent's, every model whose
    experts read the full width), another cell, no trace: no metric
    and no error."""
    parent = HLO.replace("moe_latent/", "")
    for facts in (_facts(hlo=parent),
                  _facts("glm47flash_train_t8192", parent)):
        assert moe_latent_ms.read(facts) is None
    cell = harness.load_cell(CELL)
    assert moe_latent_ms.read({"cell": cell, "peaks": None}) is None
    assert moe_latent_ms.read(
        {"cell": cell, "peaks": PEAKS, "hlo_text": HLO, "scan_k": 2,
         "trace": {"devices": {}, "host": [], "text": {}}}) is None


def test_the_cell_reports_the_new_metric_and_every_share_it_names():
    per_layer = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert {"moe_latent_ms", "mfu", "ssd_scan_roofline",
            "moe_held_matmul_roofline", "flash_attention_roofline",
            "moe_shared_ms", "ssm_block_ms", "hbm_step_peak_gib"} <= per_layer
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    latent = bench["per_layer"][-1]
    assert latent == {
        "name": "moe_latent_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "moe",
        "moves": "train_throughput", "workloads": [CELL]}
