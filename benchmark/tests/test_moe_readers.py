"""The expert layer's counts against a hand count, and its four
readers on a small synthetic trace with a known answer."""

import json
from pathlib import Path

import pytest

from benchmark import flops, flops_moe
from benchmark import run as harness
from benchmark.layer_metrics import (_moe, moe_dispatch_ms,
                                     moe_grouped_matmul_roofline,
                                     moe_load_max_over_mean, moe_step_share)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "olmoe_train_t4096"
SHAPE = dict(rows=131072, d_model=2048, d_expert=1024, n_experts=64,
             dtype_bytes=2)


def test_grouped_product_need_by_hand():
    # 4 x 4096 tokens x 8 picks = 131072 rows, each against one
    # expert's 2048 x 1024 matrix: 2 x 131072 x 2048 x 1024
    ops, nbytes = flops_moe.grouped_matmul_need(**SHAPE)
    assert ops == 549_755_813_888
    # bf16: the rows in (2048 wide) and out (1024 wide), 805 306 368
    # bytes, and all 64 experts' matrices once, 268 435 456: 1 GiB
    assert nbytes == 805_306_368 + 268_435_456 == 2 ** 30
    least, bound = flops.least_seconds(ops, nbytes, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(2.7906e-3, rel=1e-4)    # 1.311 ms of bytes


def test_the_configuration_counts_the_active_expert_products():
    """The cell's ``flops_per_item`` is the dense count at ``ffn_dim``
    8 x 1024: per token exactly the nine products' share (three
    forward, six backward) of one step's 16384 tokens."""
    config = harness.load_cell(CELL)["config"]
    assert config["kernels"]["moe_grouped_matmul"]["shape"] == SHAPE
    kw = config["flops_per_item"]["kwargs"]
    assert kw["ffn_dim"] == (config["num_experts_per_tok"]
                             * config["intermediate_size"])
    dense = dict(kw, ffn_dim=0)
    experts = (flops.decoder_train_flops_per_token(**kw)
               - flops.decoder_train_flops_per_token(**dense))
    ops, _ = flops_moe.grouped_matmul_need(**SHAPE)
    assert experts == 9 * ops / (4 * 4096) == 301_989_888
    # 17.5 TFLOP a step, of which the experts 4.95
    step = 4 * 4096 * flops.decoder_train_flops_per_token(**kw)
    assert step == pytest.approx(17.5e12, rel=5e-3)
    assert 9 * ops == pytest.approx(4.95e12, rel=2e-3)


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "f32[16384,64]{1,0}", "fusion",
          "jit(scan_steps)/while/body/closed_call/jvp(moe_route)/dot_general"),
    _line("sort.2", "(s32[131072]{0}, s32[131072]{0})", "sort",
          "jit(scan_steps)/while/body/closed_call/jvp(moe_dispatch)/sort"),
    _line("ragged-dot-metadata",
          "(s32[65]{0}, s32[319]{0}, s32[319]{0}, s32[1]{0})", "custom-call",
          "ragged-dot-metadata", ', custom_call_target="tpu_custom_call"'),
    _line("ragged-dot-none.3", "bf16[131072,1024]{1,0}", "custom-call",
          "ragged-dot-none", ', custom_call_target="tpu_custom_call"'),
    _line("ragged-dot-none.4", "bf16[64,2048,1024]{2,1,0}", "custom-call",
          "ragged-dot-none", ', custom_call_target="tpu_custom_call"'),
    _line("fusion.5", "bf16[131072,1024]{1,0}", "fusion",
          "jit(scan_steps)/while/body/closed_call/jvp(moe_experts)/mul"),
    # a backward rule's own scope inside the transposed forward scope
    _line("fusion.6", "bf16[131072,2048]{1,0}", "fusion",
          "jit(scan_steps)/while/body/closed_call/transpose(jvp(moe_experts))"
          "/moe_combine/gather"),
    _line("_flash_jit.7", "bf16[64,4096,128]{2,1,0}", "custom-call",
          "jit(scan_steps)/while/body/closed_call/jit(_flash_jit)/pallas_call",
          ', custom_call_target="tpu_custom_call"'),
    _line("fusion.8", "f32[64,2048,1024]{2,1,0}", "fusion",
          "jit(scan_steps)/while/body/closed_call/opt_update/mul"),
    "}",
])


def _facts():
    """One run of a 4-step scan, 100 ms long: a ``while`` that holds
    every op, and 30 ms in which nothing ran."""
    at = [0]

    def op(name, ms):
        start = at[0]
        at[0] += int(ms * MS)
        return [name, start, at[0]]

    ops = [
        op("fusion.1", 2), op("sort.2", 3), op("ragged-dot-metadata", 0.5),
        op("ragged-dot-none.3", 5), op("ragged-dot-none.4", 6.5),
        op("fusion.5", 4), op("fusion.6", 7), op("_flash_jit.7", 12),
        op("fusion.8", 30),
    ]
    ops.insert(0, ["while.1", 0, 100 * MS])
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_scan_steps(1)", 0, 100 * MS]]}},
        "host": [], "text": {},
        "moe_counters": {"moe_load_max_over_mean": 1.25,
                         "moe_dropped_picks": 0},
    }
    return {"trace": trace, "hlo_text": HLO, "scan_k": 4,
            "cell": harness.load_cell(CELL), "peaks": PEAKS}


def test_scopes_are_read_from_the_compiled_text():
    scopes = _moe.instruction_scopes(_facts())
    assert scopes == {
        "fusion.1": "moe_route", "sort.2": "moe_dispatch",
        "ragged-dot-metadata": "moe_experts",
        "ragged-dot-none.3": "moe_experts", "ragged-dot-none.4": "moe_experts",
        "fusion.5": "moe_experts", "fusion.6": "moe_combine",
    }
    assert _moe.grouped_kernels(_facts()) == (
        {"ragged-dot-none.3", "ragged-dot-none.4"}, {"ragged-dot-metadata"})


def test_the_four_readers_on_a_known_trace():
    facts = _facts()
    # route 2 + dispatch 3 + experts 0.5 + 5 + 6.5 + 4 + combine 7 = 28 of 100
    assert moe_step_share.read(facts) == pytest.approx(0.28)
    # what is not matrix work: 2 + 3 + 7 ms over 4 steps
    assert moe_dispatch_ms.read(facts) == pytest.approx(3.0)
    # two products of 2.7906 ms at the least in 5 + 6.5 + 0.5 ms
    assert moe_grouped_matmul_roofline.read(facts) == pytest.approx(
        100 * 2 * 2.7906 / 12.0, rel=1e-4)
    assert moe_load_max_over_mean.read(facts) == 1.25


def test_nothing_to_read_is_none_and_never_raises():
    facts = _facts()
    dense = dict(facts, cell=harness.load_cell("mistral7b_train_t4096"),
                 hlo_text=HLO.replace("moe_", "mlp_"))
    assert moe_step_share.read(dense) is None
    assert moe_dispatch_ms.read(dense) is None
    assert moe_grouped_matmul_roofline.read(dense) is None
    for reader in (moe_step_share, moe_dispatch_ms,
                   moe_grouped_matmul_roofline, moe_load_max_over_mean):
        assert reader.read({"cell": facts["cell"], "peaks": None}) is None
    # a program that never ran a MoE step has no counters
    from theanompi_tpu.obs import routing

    routing._LAST = None
    no_counter = dict(facts, trace=dict(facts["trace"]))
    del no_counter["trace"]["moe_counters"]
    assert moe_load_max_over_mean.read(no_counter) is None


def test_the_check_tool_holds_the_rehearsal_and_fails_a_wrong_variant():
    from benchmark.tools import olmoe_check

    right = olmoe_check.check(CELL, 7, None, rehearsal=True)
    assert right["ok"] and right["pick_agreement"] == 1.0
    assert right["dropped_picks_in_the_step"] == 0
    assert right["grad_rel_worst"] < 1e-4       # float32 on the CPU
    wrong = olmoe_check.check(CELL, 7, "renormalised", rehearsal=True)
    assert not wrong["ok"] and wrong["grad_rel_worst"] > 0.1
