"""The readers of the program's own spans and counters
(``layer_metrics/_program_spans.py`` and the eight metrics on it): a
trace made by hand with known answers, a recorded chip trace with the
program's spans beside it, and the cases in which there is nothing to
read — a program from before PR 24, a rehearsal on the CPU, an old
recorded trace — where every reader says ``None`` and none raises."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program_spans as ps

US = 1_000_000            # the neutral form counts picoseconds
MS = 1000 * US
RECORDED = Path(__file__).resolve().parents[1] / "recorded"
KNOWN = json.loads((RECORDED / "program_known_values.json").read_text())
READERS = ["setup_data_s", "setup_init_s", "setup_compile_s",
           "setup_warmup_s", "boundary_fence_ms", "boundary_host_ms",
           "dispatch_host_ms", "gap_named_share"]


def _read(name: str, facts: dict):
    return harness._module("layer_metrics", name).read(facts)


def _hand_facts() -> dict:
    # one device, three runs of a step program with gaps of 4 ms and
    # 2 ms; in the first gap the host fences for 1 ms after the device
    # has finished, works for 1.5 ms, and the dispatch takes 1 ms of
    # which the last 0.5 ms lie in the gap's end ... and 0.5 ms of the
    # gap lie under no span.  A small program (the permutation's copy)
    # runs inside the first gap.
    runs = [(0, 100 * MS), (104 * MS, 204 * MS), (206 * MS, 306 * MS)]
    ops = [["fusion.1", s, e] for s, e in runs]
    ops.append(["copy.1", 102 * MS, 102 * MS + 10 * US])
    modules = [["jit_step(1)", s, e] for s, e in runs]
    modules.append(["jit_copy(2)", 102 * MS, 102 * MS + 10 * US])
    program = [
        ["tm:worker.fence", 50 * MS, 101 * MS],
        ["tm:worker.end_epoch", 101 * MS, 101 * MS + 500 * US],
        ["tm:worker.adjust_hyperp", 101 * MS + 500 * US, 102 * MS],
        ["tm:worker.load", 102 * MS, 102 * MS + 500 * US],
        ["tm:worker.dispatch", 103 * MS, 104 * MS + 500 * US],
        ["tm:worker.fence", 150 * MS, 204 * MS + 200 * US],
        ["tm:worker.shuffle", 204 * MS + 200 * US, 204 * MS + 600 * US],
        ["tm:worker.dispatch", 205 * MS, 207 * MS],
        # from another profiler session: outside the device's window
        ["tm:worker.fence", 900 * MS, 901 * MS],
    ]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
             "host": [], "program": program}
    return {"trace": trace, "scan_k": 4, "cell": {"name": "by_hand"}}


def test_hand_made_spans_give_known_values():
    facts = _hand_facts()
    spans = ps.program_spans(facts)
    assert len(spans) == 8                  # the stray fence is left out
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    # gaps 100..104 ms and 204..206 ms: fence 1.0 and 0.2, median 0.6
    assert _read("boundary_fence_ms", facts) == pytest.approx(0.6)
    # host work 0.5 + 0.5 + 0.5 = 1.5 and 0.4: median 0.95
    assert _read("boundary_host_ms", facts) == pytest.approx(0.95)
    # dispatches of 1.5 and 2.0 ms
    assert _read("dispatch_host_ms", facts) == pytest.approx(1.75)
    # the part of each gap under the dispatch: 1.0 and 1.0
    assert ps.boundary_ms(facts, ps.DISPATCH) == pytest.approx(1.0)
    # idle gaps of the device: 100..102 (midpoint 101: end_epoch),
    # 102.01..104 (midpoint 103.005: dispatch), 204..206 (midpoint
    # 205: dispatch, just): all named
    assert _read("gap_named_share", facts) == pytest.approx(1.0)
    # without the first dispatch the second of them has no name
    facts["trace"]["program"].pop(4)
    assert _read("gap_named_share", facts) == pytest.approx(
        (2.0 + 2.0) / (2.0 + 1.99 + 2.0))


def test_only_leaf_spans_name_a_gap():
    outer = ["tm:worker.epoch", 0, 100]
    inner = ["tm:worker.load", 10, 20]
    alone = ["tm:worker.fence", 200, 300]
    assert ps.leaves([outer, inner, alone]) == [inner, alone]
    facts = _hand_facts()
    w0, w1 = tr.window_ps(facts["trace"])
    facts["trace"]["program"] = [["tm:worker.run", w0, w1],
                                 ["tm:worker.fence", 50 * MS, 101 * MS]]
    # the run-long span is no leaf: only the gap under the fence counts
    assert _read("gap_named_share", facts) == pytest.approx(2.0 / 5.99)


@pytest.mark.parametrize("name", READERS)
def test_recorded_chip_trace_reduces_to_known_values(name):
    """A piece of the builder's traced run of ``resnet50_bsp_1chip``
    (my chip run, PR 24): the neutral form of ``tools/dump_trace.py``
    plus the program's spans (``"program"``) and its set-up record
    (``"setup_phases"``)."""
    known = KNOWN["resnet50_boundary.trace.json.gz"]
    trace = tr.load_recorded(str(RECORDED / "resnet50_boundary.trace.json.gz"))
    facts = {"trace": trace, "scan_k": known["scan_k"],
             "cell": {"name": "resnet50_bsp_1chip"}, "peaks": None}
    assert _read(name, facts) == pytest.approx(known[name], rel=1e-9)


def test_the_recorded_piece_is_what_the_known_values_say():
    known = KNOWN["resnet50_boundary.trace.json.gz"]
    trace = tr.load_recorded(str(RECORDED / "resnet50_boundary.trace.json.gz"))
    facts = {"trace": trace, "scan_k": known["scan_k"],
             "cell": {"name": "resnet50_bsp_1chip"}}
    assert len(ps.program_spans(facts)) == known["program_spans"]
    gap_ms = 1e3 * tr.median(tr.gaps_between(
        tr.module_runs(trace, tr.busiest_module(trace))))
    assert gap_ms == pytest.approx(known["dispatch_gap_ms"], rel=1e-9)
    # the three parts of the gap add up to it (PERF.md, PR 24)
    parts = (known["boundary_fence_ms"] + known["boundary_host_ms"]
             + known["gap_under_dispatch_ms"])
    assert ps.boundary_ms(facts, ps.DISPATCH) == pytest.approx(
        known["gap_under_dispatch_ms"], rel=1e-9)
    assert abs(parts - gap_ms) <= 0.15 * gap_ms
    # the set-up record's own seconds partition its root
    phases = ps.setup_phases(facts)
    assert sum(p["self_s"] for p in phases.values()) == pytest.approx(
        phases["setup"]["s"])


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_none(name, monkeypatch, tmp_path):
    # no scratch directory of a run anywhere near
    monkeypatch.setattr(ps, "ROOT", tmp_path)
    old = tr.load_recorded(str(RECORDED / "train_step.trace.json.gz"))
    cell = {"name": "resnet50_bsp_dp4"}
    # facts of no run, of a serving run, of an empty trace
    for facts in ({"cell": cell, "peaks": None},
                  {"cell": cell, "trace": None},
                  {"cell": cell, "trace": {"devices": {}, "host": []}},
                  {"cell": cell, "trace": old}):
        assert _read(name, facts) is None
    # a training run's facts over a recorded trace without program
    # spans, in a process that has trained nothing
    facts = {"cell": cell, "trace": old, "scan_k": 4}
    if name.startswith("setup_"):
        from theanompi_tpu.obs import setup as program_setup

        monkeypatch.setattr(program_setup, "_LAST", None)
    assert _read(name, facts) is None


@pytest.mark.parametrize("name", [r for r in READERS if r.startswith("setup_")])
def test_a_program_from_before_pr_24_has_no_setup_record(name, monkeypatch):
    import theanompi_tpu.obs as obs

    monkeypatch.delattr(obs, "last_setup_phases")
    old = tr.load_recorded(str(RECORDED / "train_step.trace.json.gz"))
    facts = {"cell": {"name": "x"}, "trace": old, "scan_k": 4}
    assert _read(name, facts) is None


def test_a_cpu_profile_beside_a_recorded_chip_trace_is_not_read(
        monkeypatch, tmp_path):
    """A rehearsal: the run's own profiler session is of the CPU (no
    plane of the recorded trace's devices), whatever its clock says."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ps, "ROOT", tmp_path)
    directory = tmp_path / ".bench_scratch" / "rehearsed" / "trace"
    jax.profiler.start_trace(str(directory))
    try:
        with jax.profiler.TraceAnnotation("tm:worker.fence"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(directory))
    devices, spans = ps._read_xplane(path, 0)
    assert [s[0] for s in spans] == ["tm:worker.fence"]
    assert not devices
    old = tr.load_recorded(str(RECORDED / "train_step.trace.json.gz"))
    facts = {"cell": {"name": "rehearsed"}, "trace": old, "scan_k": 4}
    assert ps.program_spans(facts) is None
    assert _read("boundary_fence_ms", facts) is None
    assert _read("gap_named_share", facts) is None
