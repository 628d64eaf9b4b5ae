"""The benchmark's block readers (``benchmark/layer_metrics/_blocks.py``)
on a hand-written compiled text and trace with a known answer, and the
two older scope readers (``_moe.py``, ``_ut.py``) on ``op_name``s that
nest their names inside the block names: they read what they read
without them."""

import importlib
import json
import re
from pathlib import Path

import pytest

from benchmark.layer_metrics import (_blocks, _moe, _ut, attn_block_ms,
                                     block_named_share, bn_block_ms,
                                     ffn_block_ms, head_loss_ms,
                                     opt_update_ms, remat_replay_ms)

MS = 10 ** 9        # picoseconds
STEP = "jit(scan_steps)/while/body/closed_call"
BWD = f"{STEP}/transpose(jvp(jvp()))/checkpoint"


def _line(name, kind, op_name=None, extra=""):
    meta = ""
    if op_name:
        meta = f', metadata={{op_name="{op_name}" stack_frame_id=1}}'
    return f"  %{name} = f32[8,8]{{1,0}} {kind}(%p.1){extra}{meta}"


def _computation(name, lines, entry=False):
    head = f"%{name} (p.1: f32[8,8]) -> f32[8,8] {{"
    return [("ENTRY " if entry else "") + head, *lines, "}", ""]


TEXT = "\n".join([
    "HloModule jit_scan_steps, is_scheduled=true",
    "",
    # a weight-gradient product of the MLP, fused with its Adam update
    *_computation("fused_computation.1", [
        _line("convolution.1", "convolution", f"{BWD}/blk_ffn/dot_general"),
        _line("subtract.1", "subtract", f"{STEP}/opt_update/sub"),
    ]),
    # a root that lost its name: most of what it fused says blk_attn
    *_computation("fused_computation.6", [
        _line("multiply.6", "multiply", f"{STEP}/jvp(blk_attn)/mul"),
        _line("add.6", "add", f"{STEP}/jvp(blk_attn)/add"),
        _line("convert.6", "convert"),
    ]),
    *_computation("body", [
        _line("fusion.1", "fusion", f"{STEP}/opt_update/sub",
              ", kind=kOutput, calls=%fused_computation.1"),
        # the three phase forms of this JAX
        _line("fusion.2", "fusion", f"{STEP}/jvp(blk_attn)/mul"),
        _line("fusion.3", "fusion", f"{BWD}/rematted_computation/blk_attn/mul"),
        _line("fusion.4", "fusion", f"{BWD}/blk_ffn/mlp_act_grad/mul"),
        _line("fusion.5", "fusion", f"{STEP}/opt_update/div"),
        _line("fusion.6", "fusion", None,
              ", kind=kLoop, calls=%fused_computation.6"),
        _line("fusion.7", "fusion", f"{STEP}/jvp(blk_head)/ut_exit/dot_general"),
        _line("fusion.8", "fusion", f"{STEP}/transpose(jvp(blk_head))/mul"),
        # a kernel's line need not name its block: found by its part
        _line("_flash_jit.9", "custom-call", f"{BWD}/jit(_flash_jit)/pallas_call",
              ', custom_call_target="tpu_custom_call"'),
        _line("copy.10", "copy"),
        _line("fusion.11", "fusion", f"{STEP}/jvp(blk_embed)/gather"),
        _line("fusion.12", "fusion", f"{STEP}/exchange_b0/opt_update/add"),
    ]),
    *_computation("main", [
        _line("while.1", "while", "jit(scan_steps)/while",
              ", condition=%cond, body=%body"),
    ], entry=True),
])

# one run of the step program of 2 steps (a K = 2 scan): 100 ms, of
# which the ``while`` holds 97 in its instructions and 3 between them
_OPS = [
    ("fusion.1", 10), ("fusion.2", 8), ("fusion.3", 6), ("fusion.4", 4),
    ("fusion.5", 12), ("fusion.6", 2), ("fusion.7", 20), ("fusion.8", 10),
    ("_flash_jit.9", 14), ("copy.10", 1), ("fusion.11", 4), ("fusion.12", 6),
]


def _trace():
    ops, t = [["while.1", 0, 100 * MS]], 1 * MS
    for name, ms in _OPS:
        ops.append([name, t, t + ms * MS])
        t += ms * MS
    assert t == 98 * MS
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_scan_steps(1)", 0, 100 * MS]],
    }}, "host": [], "text": {}}


def _facts(text=TEXT):
    return {
        "hlo_text": text, "trace": _trace(), "scan_k": 2,
        "cell": {"name": "a_cell", "config": {"kernels": {
            "flash_attention": {"hlo_part": "_flash_jit"}}}},
    }


def test_every_instruction_has_one_block_and_one_phase():
    got = _blocks.instruction_blocks(_facts())
    top = ("fusion", "_flash", "copy", "while")     # not the fused ones
    assert {k: (v["block"], v["phase"], v["carries_opt"])
            for k, v in got.items() if k.startswith(top)} == {
        # the product decides the fusion's block and phase; the
        # update it carries is flagged
        "fusion.1": ("blk_ffn", "bwd", True),
        "fusion.2": ("blk_attn", "fwd", False),
        "fusion.3": ("blk_attn", "replay", False),
        "fusion.4": ("blk_ffn", "bwd", False),
        "fusion.5": ("opt_update", "fwd", True),
        "fusion.6": ("blk_attn", "fwd", False),
        "fusion.7": ("blk_head", "fwd", False),
        "fusion.8": ("blk_head", "bwd", False),
        "_flash_jit.9": ("blk_attn", "bwd", False),
        "copy.10": ("other", "fwd", False),
        "fusion.11": ("blk_embed", "fwd", False),
        # the innermost name
        "fusion.12": ("opt_update", "fwd", True),
        "while.1": ("other", "fwd", False),
    }


def test_block_seconds_count_a_fused_update_once_in_its_block_and_once_in_opt():
    got = _blocks.block_seconds(_facts())
    ms = {b: {p: round(s * 1e3, 6) for p, s in by.items()}
          for b, by in got["blocks"].items()}
    assert ms == {
        "blk_ffn": {"fwd": 0, "replay": 0, "bwd": 14},       # 10 + 4
        "blk_attn": {"fwd": 10, "replay": 6, "bwd": 14},
        "opt_update": {"fwd": 18, "replay": 0, "bwd": 0},    # 12 + 6
        "blk_head": {"fwd": 20, "replay": 0, "bwd": 10},
        "blk_embed": {"fwd": 4, "replay": 0, "bwd": 0},
        "other": {"fwd": 1, "replay": 0, "bwd": 0},
    }
    assert got["opt_s"] == pytest.approx(0.028)     # 10 + 12 + 6
    # what fusion hides from a block's row: the update inside fusion.1
    assert got["carried"] == {"opt_update": pytest.approx(0.010)}
    assert got["held_s"] == pytest.approx(0.003)    # the while itself
    assert (got["program_s"], got["steps"]) == (pytest.approx(0.1), 2)
    assert got["others"] == {"copy.10": pytest.approx(0.001)}
    # the blocks, ``other`` and what the ``while`` held are the run
    busy = sum(sum(by.values()) for by in got["blocks"].values())
    assert busy + got["held_s"] == pytest.approx(got["program_s"])


def test_the_metrics_are_per_step():
    facts = _facts()
    assert attn_block_ms.read(facts) == pytest.approx(15.0)
    assert ffn_block_ms.read(facts) == pytest.approx(7.0)
    assert head_loss_ms.read(facts) == pytest.approx(15.0)
    assert remat_replay_ms.read(facts) == pytest.approx(3.0)
    assert opt_update_ms.read(facts) == pytest.approx(14.0)
    assert block_named_share.read(facts) == pytest.approx(96 / 97)
    assert bn_block_ms.read(facts) is None      # no such block here


@pytest.mark.parametrize("op_name, phase", [
    ("jit(f)/jvp(blk_ffn)/mul", "fwd"),
    ("jit(f)/jvp(jvp())/blk_ffn/mul", "fwd"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/blk_ffn/act_grad/mul", "bwd"),
    ("jit(f)/transpose(jvp(blk_head))/mul", "bwd"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/blk_ffn/mul",
     "replay"),
    ("jit(f)/opt_update/sub", "fwd"),
    ("", "fwd"),
])
def test_the_three_phase_forms(op_name, phase):
    assert _blocks.phase_of(op_name) == phase


BENCH = json.loads(
    (Path(__file__).resolve().parents[1] / "BENCHMARK.json").read_text())
BLOCK_READERS = (attn_block_ms, ffn_block_ms, head_loss_ms, remat_replay_ms,
                 opt_update_ms, bn_block_ms, block_named_share)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_nothing_to_read_is_none_not_an_exception(metric):
    """Every per-layer metric of ``BENCHMARK.json``: a reader file of
    its name, cells that exist, an end-to-end metric it is said to
    move, and ``None`` — no exception — from a run with nothing to
    read (the harness then leaves the metric out of the line)."""
    reader = importlib.import_module(
        f"benchmark.layer_metrics.{metric['name']}")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert reader.read({"cell": _facts()["cell"]}) is None
    if reader not in BLOCK_READERS:
        return
    # an older program, or a cached executable of one: no ``blk_`` name
    old = TEXT.replace("blk_", "b1k_")
    assert reader.read(_facts(old)) is None
    assert reader.read(dict(_facts(), trace=None)) is None
    # another program's trace (a rehearsal reads a recorded one)
    other = _facts()
    for op in other["trace"]["devices"]["/device:TPU:0"]["ops"]:
        op[0] = "elsewhere." + op[0]
    assert reader.read(other) is None


def test_setup_before_worker_reads_the_process_stamps(monkeypatch):
    from benchmark.layer_metrics import setup_before_worker_s
    from theanompi_tpu import obs

    phases = {"before_import": 9.0, "import": 0.5, "before_worker": 4.0}
    monkeypatch.setattr(obs, "last_process_phases", lambda: dict(phases))
    assert setup_before_worker_s.read({"scan_k": 2}) == pytest.approx(13.5)
    assert setup_before_worker_s.read({}) is None     # not a training run
    monkeypatch.setattr(obs, "last_process_phases",
                        lambda: dict(phases, before_import=None))
    assert setup_before_worker_s.read({"scan_k": 2}) is None
    monkeypatch.setattr(obs, "last_process_phases", lambda: None)
    assert setup_before_worker_s.read({"scan_k": 2}) is None
    monkeypatch.delattr(obs, "last_process_phases")   # an older program
    assert setup_before_worker_s.read({"scan_k": 2}) is None


# -- the older readers under the new names -----------------------------------

_NESTED = [
    ("fusion.1", "fusion", f"{STEP}/jvp(blk_ffn)/moe_route/dot_general"),
    ("fusion.2", "fusion", f"{BWD}/rematted_computation/blk_ffn/moe_dispatch/gather"),
    ("fusion.3", "fusion", f"{BWD}/blk_ffn/moe_experts/moe_tile_plan/add"),
    ("fusion.4", "fusion", f"{BWD}/blk_ffn/moe_combine/mul"),
    ("fusion.5", "fusion", f"{STEP}/jvp(ut_stack)/checkpoint/blk_attn/dot_general"),
    ("fusion.6", "fusion", f"{STEP}/transpose(jvp(ut_stack))/checkpoint/blk_ffn/mul"),
    ("fusion.7", "fusion", f"{STEP}/jvp(blk_head)/ut_exit/dot_general"),
    ("fusion.8", "fusion", f"{STEP}/transpose(jvp(blk_head))/ut_exit/mul"),
    ("fusion.9", "fusion", f"{STEP}/jvp(blk_head)/reduce_sum"),
    ("fusion.10", "fusion", f"{STEP}/jvp(blk_attn)/mul"),
]


@pytest.mark.parametrize("reader, expected", [
    (_moe, {"fusion.1": "moe_route", "fusion.2": "moe_dispatch",
            "fusion.3": "moe_experts", "fusion.4": "moe_combine"}),
    (_ut, {"fusion.5": "ut_stack", "fusion.6": "ut_stack",
           "fusion.7": "ut_exit", "fusion.8": "ut_exit"}),
], ids=["_moe", "_ut"])
def test_the_older_readers_read_through_the_block_names(reader, expected):
    """``blk_ffn/moe_experts``, ``ut_stack/blk_attn`` and
    ``blk_head/ut_exit``: each reader matches its own names only, so
    the same text with the block names taken out gives the same."""
    def scopes(lines):
        text = "\n".join(_line(*ln) for ln in lines)
        return reader.instruction_scopes(
            {"hlo_text": text, "cell": {"config": {}}})

    without = [(name, kind, re.sub(r"blk_\w+/?", "", op))
               for name, kind, op in _NESTED]
    assert not any("blk_" in op for _, _, op in without)
    assert scopes(_NESTED) == expected == scopes(without)
