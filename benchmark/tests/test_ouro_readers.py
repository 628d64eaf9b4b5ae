"""The looped decoder's counts against a hand count, its three
readers on a small synthetic trace with a known answer, and its check
tool at the rehearsal sizes."""

import json
from pathlib import Path

import pytest

from benchmark import flops
from benchmark import run as harness
from benchmark.layer_metrics import (_ut, flash_attention_roofline,
                                     ut_exit_step_share, ut_mean_exit_step,
                                     ut_pass_ms)

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CELL = "ouro_train_t4096"


def test_the_configuration_counts_four_passes_and_four_exits():
    """``flops_per_item`` is the dense count at 8 x 4 layer calls and
    4 x 49152 head columns: by hand, per token forward, 32 layer
    calls of 51.38 M matrix parameters, 4 heads of 100.66 M, and the
    causal scores and values of 32 attention calls."""
    config = harness.load_cell(CELL)["config"]
    kw = config["flops_per_item"]["kwargs"]
    passes, layers = config["total_ut_steps"], config["num_hidden_layers"]
    assert (passes, layers) == (4, 8)
    assert kw["n_layers"] == passes * layers
    assert kw["vocab"] == passes * config["vocab_size"]
    assert kw["dim"] == config["hidden_size"] == 2048
    assert kw["ffn_dim"] == config["intermediate_size"] == 5632
    assert kw["head_dim"] == config["head_dim"] == 128
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    head = 2048 * 49152
    assert (layer, head) == (51_380_224, 100_663_296)
    attention = 2 * 2 * 4096 * 16 * 128 * 32 / 2
    forward = 2 * (32 * layer + 4 * head) + attention
    assert forward == pytest.approx(4.63e9, rel=2e-3)
    assert flops.decoder_train_flops_per_token(**kw) == 3 * forward
    # 113.8 TFLOP a step of 2 x 4096 tokens; the head's four exits
    # are 17 % of it (3.4 % at the published 48 layers)
    assert 8192 * 3 * forward == pytest.approx(113.8e12, rel=1e-3)
    assert 2 * 4 * head / forward == pytest.approx(0.174, abs=2e-3)
    # what is held: 612.4 M parameters, the gate's 2049 and the norms aside
    assert 8 * layer + 2 * head == 612_368_384


MS = 10 ** 9        # picoseconds


def _line(name, result, kind, op_name, extra=""):
    return (f"  %{name} = {result} {kind}(%p.1){extra}, "
            f'metadata={{op_name="{op_name}" stack_frame_id=1}}')


STEP = "jit(scan_steps)/while/body/closed_call"
HLO = "\n".join([
    "%body (p: f32[8]) -> f32[8] {",
    _line("fusion.1", "bf16[2,4096,2048]{2,1,0}", "fusion",
          f"{STEP}/jvp(ut_stack)/checkpoint/dot_general"),
    _line("fusion.2", "bf16[2,4096,2048]{2,1,0}", "fusion",
          f"{STEP}/transpose(jvp(ut_stack))/checkpoint/rematted_computation/mul"),
    # a kernel's line may or may not carry the scope: counted anyway
    _line("_flash_jit.3", "(bf16[32,4096,128]{2,1,0}, f32[32,1,4096]{2,1,0})",
          "custom-call", f"{STEP}/jit(_flash_jit)/pallas_call",
          ', custom_call_target="tpu_custom_call"'),
    _line("_flash_jit.4", "bf16[32,4096,128]{2,1,0}", "custom-call",
          f"{STEP}/transpose(jvp(ut_stack))/jit(_flash_jit)/pallas_call",
          ', custom_call_target="tpu_custom_call"'),
    _line("fusion.5", "bf16[8192,49152]{1,0}", "fusion",
          f"{STEP}/jvp(ut_exit)/while/body/checkpoint/dot_general"),
    _line("fusion.6", "f32[2048,49152]{1,0}", "fusion",
          f"{STEP}/transpose(jvp(ut_exit))/while/body/dot_general"),
    _line("fusion.7", "f32[3,8192]{1,0}", "fusion",
          f"{STEP}/jvp(ut_exit)/log_sigmoid"),
    _line("fusion.8", "f32[49152,2048]{1,0}", "fusion",
          f"{STEP}/opt_update/mul"),
    "}",
])


def _facts(cell=CELL, hlo=HLO):
    """One run of a 2-step scan, 100 ms long: a ``while`` that holds
    every op, and 10 ms in which nothing ran."""
    at = [0]

    def op(name, ms):
        start = at[0]
        at[0] += int(ms * MS)
        return [name, start, at[0]]

    ops = [
        op("fusion.1", 20), op("fusion.2", 30), op("_flash_jit.3", 4),
        op("_flash_jit.4", 6), op("fusion.5", 8), op("fusion.6", 9),
        op("fusion.7", 1), op("fusion.8", 12),
    ]
    ops.insert(0, ["while.1", 0, 100 * MS])
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_scan_steps(1)", 0, 100 * MS]]}},
        "host": [], "text": {},
        "ut_counters": {"ut_exit_mass": [0.5, 0.25, 0.125, 0.125],
                        "ut_exit_loss": [10.8, 10.8, 10.8, 10.8],
                        "ut_mean_exit_step": 1.875},
    }
    return {"trace": trace, "hlo_text": hlo, "scan_k": 2,
            "cell": harness.load_cell(cell), "peaks": PEAKS}


def test_scopes_are_read_from_the_compiled_text():
    assert _ut.instruction_scopes(_facts()) == {
        "fusion.1": "ut_stack", "fusion.2": "ut_stack",
        "_flash_jit.3": "ut_stack", "_flash_jit.4": "ut_stack",
        "fusion.5": "ut_exit", "fusion.6": "ut_exit", "fusion.7": "ut_exit",
    }


def test_the_three_readers_on_a_known_trace():
    facts = _facts()
    # the exits: 8 + 9 + 1 = 18 of 100 ms
    assert ut_exit_step_share.read(facts) == pytest.approx(0.18)
    # the stack: 20 + 30 + 4 + 6 = 60 ms over 2 steps and 4 passes
    assert ut_pass_ms.read(facts) == pytest.approx(7.5)
    assert ut_mean_exit_step.read(facts) == 1.875
    # and the cell's flash kernels are told apart as in the others
    assert flash_attention_roofline.read(facts) is not None


def test_nothing_to_read_is_none_and_never_raises():
    """A plain decoder's text (the parent commit's, too) has neither
    scope: no metric, no error, whatever the cell."""
    plain = HLO.replace("ut_stack", "layers").replace("ut_exit", "head")
    for facts in (_facts(hlo=plain), _facts("mistral7b_train_t4096", plain)):
        assert _ut.instruction_scopes(facts) == {}
        assert ut_exit_step_share.read(facts) is None
        assert ut_pass_ms.read(facts) is None
    cell = harness.load_cell(CELL)
    for reader in (ut_exit_step_share, ut_pass_ms, ut_mean_exit_step):
        assert reader.read({"cell": cell, "peaks": None}) is None
    # a program that never ran a looped step has no counters
    from theanompi_tpu.obs import exits

    exits._LAST = None
    no_counter = _facts()
    del no_counter["trace"]["ut_counters"]
    assert ut_mean_exit_step.read(no_counter) is None


def test_the_check_tool_holds_the_rehearsal_and_fails_a_wrong_variant():
    from benchmark.tools import ouro_check

    right = ouro_check.check(CELL, 7, None, rehearsal=True)
    assert right["ok"] and right["passes"] == 3
    assert right["grad_rel_worst"] < 1e-4       # float32 on the CPU
    assert right["counter_rel_worst"] < 1e-5
    assert sum(right["exit_mass"]) == pytest.approx(1.0, abs=1e-6)
    assert {"exit_gate_w", "exit_gate_b", "layers.0.attn_out_norm",
            "layers.1.mlp_out_norm"} <= set(right["grad_rel"])
    for variant in ouro_check.VARIANTS:
        wrong = ouro_check.check(CELL, 7, variant, rehearsal=True)
        assert not wrong["ok"], variant
