"""The ``granite4h`` cell's counts against hand counts and a count by
enumeration, and its five readers on a small synthetic trace with a
known answer (the accepted block readers beside them, which must keep
the mamba layers' time and the attention layer's apart)."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_ssd
from benchmark import run as harness
from benchmark.layer_metrics import (attn_block_ms, block_named_share,
                                     ffn_block_ms, flash_attention_roofline,
                                     remat_replay_ms, ssd_scan_ms,
                                     ssd_scan_roofline, ssm_block_ms,
                                     ssm_conv_ms, ssm_log_decay_min)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "granite4h_micro_train_t8192"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_configuration_keeps_the_published_widths_and_cuts_two_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite_4.0_h_micro_train_l10")
    config = harness.load_cell(CELL)["config"]
    published = config["published"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "vocab_size"}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        10, 100352 // 8)
    knobs = harness.program_knobs(config)
    assert (knobs["dim"], knobs["n_heads"], knobs["n_kv_heads"],
            knobs["ffn_dim"], knobs["mamba_n_heads"], knobs["mamba_d_head"],
            knobs["mamba_d_state"], knobs["mamba_n_groups"],
            knobs["mamba_d_conv"], knobs["mamba_chunk_size"],
            knobs["norm_eps"]) == (
        2048, 32, 8, 8192, 64, 64, 128, 1, 4, 256, 1e-5)
    assert (knobs["embedding_multiplier"], knobs["residual_multiplier"],
            knobs["attention_multiplier"], knobs["logits_scaling"],
            knobs["tie_word_embeddings"], knobs["position_embedding_type"]
            ) == (12, 0.22, 0.015625, 8, True, "nope")
    # the list stands whole; the stack is its first period
    assert knobs["layer_types"] == published["layer_types"]
    assert knobs["layer_types"][:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    ref = config["reference"]["kwargs"]
    assert ref["layer_types"] == knobs["layer_types"][:10]
    assert (ref["embedding_multiplier"], ref["residual_multiplier"],
            ref["attention_multiplier"], ref["logits_scaling"]) == (
        12, 0.22, 0.015625, 8)
    # the cell's parameters are the issue's
    assert (knobs["seq_len"], knobs["batch_size"], knobs["steps_per_call"],
            knobs["compute_dtype"], knobs["optimizer"]) == (
        8192, 1, 2, "bfloat16", "adam")
    # every size set here and not published is under ``assumed``
    assert {"intermediate_size", "time_step_limit", "initial_values",
            "gate_inside_the_norm", "head_dim", "batch_size", "seq_len",
            "memory_analysis", "validate"} <= set(config["assumed"])
    assert config["learns"]["last_chunk_loss_over_first"] < 1


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_published_group_is_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"granite-4.0-h-micro"' in line)
    config = harness.load_cell(CELL)["config"]
    assert config["published"] == row["config"]
    assert config["source"] == row["source_url"]


def test_the_flop_count_is_the_models_sum():
    """``flops_per_item`` through the dense function at kwargs that
    reproduce, per token forward: nine mamba layers' two projections
    and SwiGLU, the attention layer's four projections and SwiGLU, the
    tied head over the slice, the attention layer's products at T 8192
    (the triangle) and nine scans by ``flops_ssd``'s own count."""
    config = harness.load_cell(CELL)["config"]
    kw = config["flops_per_item"]["kwargs"]
    in_proj = 2048 * (2 * 4096 + 2 * 1 * 128 + 64)
    out_proj, swiglu = 4096 * 2048, 3 * 2048 * 8192
    assert (in_proj, out_proj, swiglu) == (17_432_576, 8_388_608, 50_331_648)
    mamba = in_proj + out_proj + swiglu
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + swiglu
    head = 2048 * 12544
    assert (mamba, attn, head) == (76_152_832, 60_817_408, 25_690_112)
    multiplied = 9 * mamba + attn + head
    assert multiplied == 771_883_008
    scores = 2 * 8192 * 2048
    scan = flops_ssd.ssd_flops_per_token(
        n_heads=64, head_dim=64, d_state=128, n_groups=1, chunk=256)
    assert scan == 2 * 128 * 128 + 2 * 128 * 4096 + 2 * 2 * 128 * 4096
    assert (scores, scan) == (33_554_432, 3_178_496)
    assert flops.decoder_matmul_params(
        **{k: v for k, v in kw.items() if k != "seq_len"}) == multiplied
    per_token = flops.decoder_train_flops_per_token(**kw)
    assert per_token == 3 * (2 * multiplied + scores + 9 * scan)
    assert per_token == 4_817_780_736
    assert 8192 * per_token == pytest.approx(39.47e12, rel=1e-3)
    forward = per_token / 3
    # the shares the cell's ``why`` gives
    mixers = 9 * (2 * (in_proj + out_proj) + scan)
    assert mixers / forward == pytest.approx(0.307, abs=0.001)
    assert 10 * 2 * swiglu / forward == pytest.approx(0.627, abs=0.001)
    assert (2 * (attn - swiglu) + scores) / forward == pytest.approx(
        0.034, abs=0.001)
    assert 2 * head / forward == pytest.approx(0.032, abs=0.001)
    # what the chip holds, by the issue's table
    layer_m = (in_proj + out_proj + (4096 + 256) * 4 + (4096 + 256)
               + 3 * 64 + 4096 + 2 * 2048 + swiglu)
    layer_a = attn + 2 * 2048
    assert (layer_m, layer_a) == (76_182_976, 60_821_504)
    assert 9 * layer_m + layer_a + head + 2048 == 772_160_448
    # the kernel's need is the same count a call
    spec = config["kernels"]["ssd_scan"]["shape"]
    ops, _ = flops_ssd.ssd_call_need("fwd", **spec)
    assert ops / 8192 == scan


@pytest.mark.parametrize("t,chunk", [(16, 4), (16, 16), (24, 8), (8, 32)],
                         ids=str)
def test_the_scans_need_against_a_count_by_enumeration(t, chunk):
    """Inside a chunk a token meets the tokens up to itself: the
    visible pairs counted one by one, less half a pair a token (the
    triangle's convention), so a form that computes at least the
    visible pairs cannot read over 100 %."""
    h, p, n, g = 3, 4, 5, 1
    ln = min(chunk, t)
    visible = sum(1 for i in range(t) for j in range(t)
                  if j <= i and i // ln == j // ln)
    shape = dict(batch=2, seq_len=t, n_heads=h, head_dim=p, d_state=n,
                 n_groups=g, chunk=chunk)
    ops, nbytes = flops_ssd.ssd_call_need("fwd", **shape)
    pairs = visible - t / 2
    want = 2 * (2 * pairs * (n * g + h * p) + t * 2 * 2 * n * h * p)
    assert ops == want
    assert nbytes == 2 * t * (2 * (2 * h * p + 2 * g * n) + 4 * h)
    back, back_bytes = flops_ssd.ssd_call_need("bwd", **shape)
    assert back == 2 * ops
    assert back_bytes == 2 * t * (2 * (3 * h * p + 4 * g * n) + 8 * h)


def test_the_cells_scan_is_memory_bound_forward_and_compute_bound_backward():
    spec = harness.load_cell(CELL)["config"]["kernels"]["ssd_scan"]["shape"]
    fwd = flops.least_seconds(*flops_ssd.ssd_call_need("fwd", **spec), PEAKS)
    bwd = flops.least_seconds(*flops_ssd.ssd_call_need("bwd", **spec), PEAKS)
    assert fwd[1] == "memory" and bwd[1] == "compute"
    assert 9 * (fwd[0] + bwd[0]) == pytest.approx(3.9e-3, rel=0.03)


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
CALL = ', custom_call_target="tpu_custom_call"'
BWD = "transpose(jvp(blk_ssm))"
REPLAY = f"{STEP}/{BWD}/checkpoint/rematted_computation/blk_ssm"
HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[1,8192,4352]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssm_proj/dot_general"),
    _line("fusion.2", "bf16[1,8192,4352]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssm_conv/mul"),
    _line("fusion.3", "f32[1,32,64,256,256]{4,3,2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssd_scan/exp"),
    _line("fusion.4", "f32[1,32,256,64,64]{4,3,2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssd_scan/dot_general"),
    _line("fusion.5", "bf16[1,8192,4096]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ssm)/ssm_gate_norm/mul"),
    _line("fusion.6", "f32[1,32,64,256,256]{4,3,2,1,0}", "fusion",
          f"{REPLAY}/ssd_scan/exp"),
    _line("fusion.7", "f32[1,32,64,256,256]{4,3,2,1,0}", "fusion",
          f"{STEP}/{BWD}/ssd_scan/dot_general"),
    _line("fusion.8", "f32[4,4352]{1,0}", "fusion",
          f"{STEP}/{BWD}/ssm_conv/reduce_sum"),
    _line("flash.9", "(bf16[32,8192,64]{2,1,0}, f32[32,1,8192]{2,1,0})",
          "custom-call",
          f"{STEP}/jvp(blk_attn)/attn_full/jit(_flash_jit)/pallas_call",
          CALL),
    _line("fusion.10", "bf16[1,8192,8192]{2,1,0}", "fusion",
          f"{STEP}/jvp(blk_ffn)/dot_general"),
    _line("fusion.11", "bf16[8192,12544]{1,0}", "fusion",
          f"{STEP}/jvp(blk_head)/dot_general"),
    "}",
])
TIMES = [("fusion.1", 6), ("fusion.2", 2), ("fusion.3", 7), ("fusion.4", 3),
         ("fusion.5", 1), ("fusion.6", 5), ("fusion.7", 9), ("fusion.8", 4),
         ("flash.9", 8), ("fusion.10", 20), ("fusion.11", 10)]
COUNTERS = {"ssm_log_decay_min": [-120.5, -397.25, -80.0],
            "ssm_state_rms": [0.5, 0.25, 0.125]}


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
        trace["ssm_counters"] = counters
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_the_five_readers_on_a_known_trace():
    facts = _facts()
    # blk_ssm: 6 + 2 + 7 + 3 + 1 + 5 + 9 + 4 = 37 ms over 2 steps
    assert ssm_block_ms.read(facts) == pytest.approx(18.5)
    # ssd_scan: 7 + 3 forward, 5 in the replay, 9 backward
    assert ssd_scan_ms.read(facts) == pytest.approx(12.0)
    assert ssm_conv_ms.read(facts) == pytest.approx(3.0)
    assert ssm_log_decay_min.read(facts) == -397.25
    # the need of 2 steps x 9 layers, forward and backward, over the
    # 24 ms under the scope, the replay's 5 among them
    spec = facts["cell"]["config"]["kernels"]["ssd_scan"]["shape"]
    least = sum(
        flops.least_seconds(*flops_ssd.ssd_call_need(k, **spec), PEAKS)[0]
        for k in ("fwd", "bwd"))
    assert ssd_scan_roofline.read(facts) == pytest.approx(
        100 * 2 * 9 * least / 24e-3)
    # the accepted readers keep the kinds of layer apart
    assert attn_block_ms.read(facts) == pytest.approx(4.0)
    assert ffn_block_ms.read(facts) == pytest.approx(10.0)
    assert remat_replay_ms.read(facts) == pytest.approx(2.5)
    assert block_named_share.read(facts) == pytest.approx(1.0)
    assert 0 < flash_attention_roofline.read(facts) < 100


def test_nothing_to_read_is_none_and_never_raises():
    """A text without the scopes (every parent's program: it knows no
    mamba layer), another cell, no trace, no counters: no metric and
    no error."""
    parent = (HLO.replace("blk_ssm", "blk_attn").replace("ssd_scan/", "")
              .replace("ssm_conv/", "").replace("ssm_proj/", "")
              .replace("ssm_gate_norm/", ""))
    readers = (ssm_block_ms, ssd_scan_ms, ssm_conv_ms, ssd_scan_roofline)
    for facts in (_facts(hlo=parent), _facts("mistral7b_train_t4096", parent)):
        for reader in readers:
            assert reader.read(facts) is None, reader.__name__
    cell = harness.load_cell(CELL)
    for reader in (*readers, ssm_log_decay_min):
        assert reader.read({"cell": cell, "peaks": None}) is None
        assert reader.read(
            {"cell": cell, "peaks": PEAKS, "hlo_text": HLO,
             "trace": {"devices": {}, "host": [], "text": {}}}) is None
    # a training run whose program counted nothing
    from theanompi_tpu.obs import ssm as obs_ssm

    was, obs_ssm._LAST = obs_ssm._LAST, None
    try:
        assert ssm_log_decay_min.read(_facts(counters=None)) is None
    finally:
        obs_ssm._LAST = was


def test_the_counter_reader_falls_back_to_the_programs_own():
    from theanompi_tpu.obs import last_ssm_counters, ssm as obs_ssm

    was = obs_ssm._LAST
    try:
        obs_ssm.ssm_counters([[-3.0, 0.5], [-7.5, 0.25]])
        assert last_ssm_counters()["ssm_state_rms"] == [0.5, 0.25]
        assert ssm_log_decay_min.read(_facts(counters=None)) == -7.5
    finally:
        obs_ssm._LAST = was
