"""The reduction from trace to numbers: a trace made by hand with
known answers, then the recorded chip traces beside it."""

import json
from pathlib import Path

import pytest

from benchmark import hlo_read
from benchmark import trace_reduce as tr

NS = 1000                 # the neutral form counts picoseconds
RECORDED = Path(__file__).resolve().parents[1] / "recorded"


def _hand_trace():
    # one device, two runs of a step program, 50 ns apart; the first
    # holds a while of 200 ns whose body leaves 20 ns of its own, an
    # asynchronous all-reduce whose start and done are exposed
    ops = [
        ["while.1", 0, 200 * NS],
        ["fusion.1", 0, 100 * NS],
        ["all-reduce-start.1", 100 * NS, 110 * NS],
        ["fusion.2", 110 * NS, 150 * NS],
        ["all-reduce-done.1", 150 * NS, 180 * NS],
        ["fusion.3", 250 * NS, 300 * NS],
    ]
    modules = [["jit_step(1)", 0, 200 * NS], ["jit_step(1)", 250 * NS, 300 * NS]]
    host = [["bench:bsp_rule", 0, 400 * NS], ["bench:inner", 190 * NS, 260 * NS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_hand_made_trace_gives_known_values():
    trace = _hand_trace()
    assert tr.window_ps(trace) == (0, 300 * NS)
    assert tr.busy_seconds(trace) == pytest.approx(250e-9)
    summary = tr.summarize(trace)
    assert summary["window_s"] == pytest.approx(300e-9)
    assert 1 - summary["busy_s"] / summary["window_s"] == pytest.approx(1 / 6)
    self_s = tr.self_seconds(trace["devices"]["/device:TPU:0"]["ops"])
    assert self_s["while.1"] == pytest.approx(20e-9)
    assert self_s["fusion.1"] == pytest.approx(100e-9)
    assert summary["device_ops"][0] == [
        "fusion (3 instructions)", pytest.approx(190e-9)]
    assert ["fusion.1", pytest.approx(100e-9)] in summary["device_ops"]
    comm, exposed = tr.exposed_collective_seconds(trace)
    assert comm == pytest.approx(40e-9) and exposed == pytest.approx(40e-9)
    runs = tr.module_runs(trace, tr.busiest_module(trace))
    assert tr.gaps_between(runs) == [pytest.approx(50e-9)]
    # the one gap (200..250 ns) is 50 ns: under the 2 us floor it is
    # "between_ops"; with the floor lowered it goes to the innermost
    # host span over its midpoint
    assert tr.idle_gaps_by_host_span(trace) == [
        ["between_ops", pytest.approx(50e-9)]]
    assert tr.idle_gaps_by_host_span(trace, floor_ps=1) == [
        ["bench:inner", pytest.approx(50e-9)]]


def test_a_collective_under_compute_is_hidden():
    trace = _hand_trace()
    # a second device whose all-reduce lies wholly under a fusion
    trace["devices"]["/device:TPU:1"] = {
        "ops": [["fusion.9", 0, 100 * NS], ["all-reduce.2", 20 * NS, 60 * NS]],
        "modules": [["jit_step(1)", 0, 100 * NS]],
    }
    comm, exposed = tr.exposed_collective_seconds(trace)
    assert comm == pytest.approx((40e-9 + 40e-9) / 2)
    assert exposed == pytest.approx(40e-9 / 2)


def test_interval_arithmetic_and_names():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.is_collective("all-reduce.12")
    assert tr.is_collective("all-reduce-start.3")
    assert not tr.is_collective("all-reduce_fusion.1")    # fused epilogue
    assert not tr.is_collective("convolution.4")
    assert tr.is_container("while.2") and not tr.is_container("fusion.7")


def test_clip_keeps_whole_events_only():
    piece = tr.clip(_hand_trace(), 0, 200 * NS)
    dev = piece["devices"]["/device:TPU:0"]
    assert [o[0] for o in dev["ops"]][-1] == "all-reduce-done.1"
    assert len(dev["modules"]) == 1 and piece["host"] == []


def test_hlo_reader_counts_collectives_and_kernels():
    hlo = "\n".join([
        "  %all-reduce.1 = bf16[25557032]{0} all-reduce(bf16[25557032]{0} %x), replica_groups={}",
        "  %all-reduce-start.2 = (f32[16]{0}, f32[16]{0}) all-reduce-start(f32[16]{0} %y)",
        "  %all-reduce-done.2 = f32[16]{0} all-reduce-done(%all-reduce-start.2)",
        '  %custom-call.7 = bf16[64,4096,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"',
        "  %fusion.3 = f32[8]{0} fusion(%z), kind=kLoop",
    ])
    found = hlo_read.collectives(hlo)
    assert [(c["name"], c["op"], c["bytes"]) for c in found] == [
        ("all-reduce.1", "all-reduce", 2 * 25557032),
        ("all-reduce-start.2", "all-reduce", 64),
    ]
    assert list(hlo_read.custom_calls(hlo)) == ["custom-call.7"]


@pytest.mark.parametrize("name", ["train_step.trace.json.gz",
                                  "serve.trace.json.gz"])
def test_recorded_chip_trace_reduces_to_its_known_values(name):
    known = json.loads((RECORDED / "known_values.json").read_text())[name]
    trace = tr.load_recorded(str(RECORDED / name))
    summary = tr.summarize(trace)
    comm, exposed = tr.exposed_collective_seconds(trace)
    got = {
        "busy_s": summary["busy_s"],
        "window_s": summary["window_s"],
        "idle_share": 1 - summary["busy_s"] / summary["window_s"],
        "collective_s": comm,
        "exposed_collective_s": exposed,
        "program_runs": len(tr.module_runs(trace, tr.busiest_module(trace))),
        "top_kind": summary["device_ops"][0][0],
    }
    for key, value in known.items():
        if isinstance(value, float):
            assert got[key] == pytest.approx(value, rel=1e-9), key
        else:
            assert got[key] == value, key
