"""The training path's spans, set-up phases and compile counter
(``Recorder.phase``, ``obs/setup.py``, ``obs/compile_meter.py``):
counts and names only — a time is a chip run's to give."""

import glob
import importlib
import json
import os
import sys
import types

import pytest

from theanompi_tpu.obs import (
    CompileMeter,
    SetupRecord,
    Tracer,
    last_setup_phases,
    process_meter,
    setup_phase,
    span_tree,
)
from theanompi_tpu.utils.recorder import Recorder
from theanompi_tpu.workers import bsp_worker

TINY = {
    "batch_size": 2, "depth": 10, "widen": 1, "lr": 0.01,
    "n_train": 16, "n_val": 8,
    # the benchmark's path: device-resident data, K steps a dispatch
    "device_data_cache": True, "steps_per_call": 2,
}
EPOCHS = 2
CHUNKS = EPOCHS * 2         # 16 samples / (2 x 2 replicas) / K=2
SETUP_NAMES = ["setup", "setup.build_model", "setup.data",
               "setup.compile_iter_fns", "setup.stage_data",
               "setup.resume", "setup.warmup"]


def _run(config_extra=None, modelfile="theanompi_tpu.models.wresnet",
         modelclass="WResNet", n_epochs=EPOCHS):
    return bsp_worker.run(
        devices=[0, 1], modelfile=modelfile, modelclass=modelclass,
        config={**TINY, "n_epochs": n_epochs, **(config_extra or {})},
        verbose=False,
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny BSP run with the ring on, inside a profiler session."""
    import jax

    logdir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(logdir))
    try:
        export = logdir / "ring.json"
        res = _run({"trace": True, "trace_export": str(export)})
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(logdir / "plugins/profile/*/*.xplane.pb"))
    ring = json.loads(export.read_text())["traceEvents"]
    return {"res": res, "xplane": xplane, "ring": ring}


# -- Recorder.phase ----------------------------------------------------------


@pytest.mark.parametrize("name, mode", [
    ("load", "wait"), ("dispatch", "calc"), ("fence", "calc"),
    ("exchange", "comm"), ("shuffle", None), ("adjust_hyperp", None),
])
def test_phase_books_its_seconds_to_the_references_segment(name, mode):
    rec = Recorder(verbose=False)
    with rec.phase(name, epoch=3):
        pass
    booked = {m for m, s in rec.total_segments.items() if s > 0.0}
    assert booked == ({mode} if mode else set())
    assert rec.segments == rec.epoch_segments == rec.total_segments


def test_phase_records_into_the_ring_only_under_an_iteration_root():
    tracer = Tracer(process="bsp_worker", sample=1)
    rec = Recorder(verbose=False)
    with rec.phase("dispatch", first=0, k=2):     # no ring attached
        pass
    rec.attach_tracer(tracer)
    with rec.phase("dispatch", first=0, k=2):     # no root yet
        pass
    assert tracer.spans() == []
    rec.trace_boundary(0)
    with rec.phase("dispatch", first=2, k=2):
        pass
    rec.finish_trace()
    spans = {s["name"]: s for s in tracer.spans()}
    assert set(spans) == {"iteration", "dispatch"}
    assert spans["dispatch"]["attrs"] == {"first": 2, "k": 2}
    assert spans["dispatch"]["parent_id"] == spans["iteration"]["span_id"]


def test_start_end_pairs_get_the_new_ring_names():
    tracer = Tracer(process="easgd_worker", sample=1)
    rec = Recorder(verbose=False)
    rec.attach_tracer(tracer)
    rec.trace_boundary(0)
    for mode in ("wait", "calc", "comm"):
        rec.start()
        rec.end(mode)
    rec.finish_trace()
    names = sorted(s["name"] for s in tracer.spans())
    assert names == ["dispatch", "exchange", "iteration", "load"]


def test_fence_stamps_the_first_one_that_had_something_to_wait_for():
    rec = Recorder(verbose=False)
    rec.fence()                       # nothing pending: not a fence
    assert rec.first_fence_end is None
    rec.train_error(0, 1.0, 0.5)
    rec.fence()
    first = rec.first_fence_end
    assert first is not None and rec.train_losses == [1.0]
    rec.train_error(1, 0.9, 0.5)
    rec.fence()
    assert rec.first_fence_end == first


def test_the_profiler_handoff_nobody_called_is_gone():
    assert not hasattr(Recorder, "start_profiler")
    assert not hasattr(Recorder, "stop_profiler")


# -- the worker loop's spans -------------------------------------------------


@pytest.mark.parametrize("name, count", [
    ("load", CHUNKS), ("dispatch", CHUNKS),
    ("shuffle", EPOCHS), ("validate", EPOCHS), ("fence", EPOCHS),
    ("end_epoch", EPOCHS), ("adjust_hyperp", EPOCHS),
])
def test_ring_holds_each_span_of_the_worker_loop(traced, name, count):
    spans = [e for e in traced["ring"] if e.get("ph") == "X"]
    assert sum(e["name"] == name for e in spans) == count


def test_ring_spans_hang_under_connected_iteration_roots():
    tracer = Tracer(process="bsp_worker", sample=1)
    rec = Recorder(verbose=False)
    rec.attach_tracer(tracer)
    rec.trace_boundary()
    rec.start_epoch()
    for i in range(2):
        with rec.phase("load"):
            pass
        with rec.phase("dispatch", first=i, k=1):
            pass
        rec.train_error(i, 1.0, 0.5)
        rec.trace_boundary()
    rec.end_epoch(0)                  # fence, then end_epoch
    rec.finish_trace()
    spans = tracer.spans()
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s["name"])
    assert sorted(map(sorted, by_trace.values())) == [
        ["dispatch", "iteration", "load"],
        ["dispatch", "iteration", "load"],
        ["end_epoch", "fence", "iteration"],
    ]
    for tid in by_trace:
        assert span_tree(spans, tid)["connected"]


@pytest.mark.parametrize("name", [
    "tm:worker.load", "tm:worker.dispatch", "tm:worker.fence",
    "tm:worker.end_epoch", "tm:worker.adjust_hyperp", "tm:worker.shuffle",
    "tm:worker.validate",
])
def test_a_profiler_session_finds_the_span_in_the_host_plane(traced, name):
    from jax.profiler import ProfileData

    found = [
        e for plane in ProfileData.from_file(traced["xplane"]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name == name
    ]
    assert found, f"no {name} event in the host planes"
    if name == "tm:worker.dispatch":
        assert len(found) == CHUNKS
        assert {"first", "k"} <= {k for k, _ in found[0].stats}


# -- what the benchmark's readers look for -----------------------------------

SPAN_READERS = ["boundary_fence_ms", "boundary_host_ms", "dispatch_host_ms",
                "gap_named_share"]
SETUP_READERS = ["setup_data_s", "setup_init_s", "setup_warmup_s",
                 "setup_compile_s", "setup_before_worker_s"]


def _reader(metric):
    return importlib.import_module(f"benchmark.layer_metrics.{metric}")


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_the_spans_a_reader_names_are_spans_the_worker_opened(traced, metric):
    """The ``tm:`` names are taken from the reader's own module and
    looked up in the session as the benchmark reads it
    (``_program_spans._read_xplane``): a renamed span, another prefix
    or another plane makes the metric ``None`` on the chip."""
    from benchmark.layer_metrics import _program_spans as ps

    _, spans = ps._read_xplane(traced["xplane"],
                               os.stat(traced["xplane"]).st_mtime_ns)
    opened = {name for name, _, _ in spans}
    named = {n for v in vars(_reader(metric)).values()
             if isinstance(v, tuple) for n in v
             if isinstance(n, str) and n.startswith(ps.PROGRAM_SPAN_PREFIX)}
    assert named <= opened, sorted(named - opened)
    if named:
        return
    # ``gap_named_share`` takes any leaf: a device idle for the length
    # of one is idle under a name
    _, start, end = ps.leaves([list(s) for s in spans])[0]
    ops = [["a", start - 10, start], ["b", end, end + 10]]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
             "program": [list(s) for s in spans]}
    assert end - start >= 2_000_000      # the reader's floor, in ps
    assert _reader(metric).read({"trace": trace}) == 1.0


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_the_phases_a_reader_names_are_phases_the_worker_recorded(
        traced, metric, monkeypatch):
    """``setup_seconds`` adds up the phases it finds among those it is
    given and says nothing of the rest: a renamed phase reads 0.0, not
    ``None``.  So the names each reader hands it are held to the
    record itself."""
    reader = _reader(metric)
    recorded = traced["res"]["setup_phases"]
    asked = []
    if hasattr(reader, "setup_seconds"):
        with monkeypatch.context() as patched:
            patched.setattr(reader, "setup_seconds",
                            lambda facts, names: asked.extend(names))
            reader.read({"scan_k": 2})
        assert asked and set(asked) <= set(recorded), (asked, sorted(recorded))
    facts = {"scan_k": 2, "trace": {"setup_phases": recorded}}
    got = reader.read(facts)
    assert got is not None and got >= 0.0


# -- set-up phases -----------------------------------------------------------


def test_summary_holds_every_setup_phase_once_without_the_ring():
    res = _run(n_epochs=1)
    assert res["trace_spans"] is None          # ``trace`` is not set
    phases = res["setup_phases"]
    assert sorted(phases) == sorted(SETUP_NAMES)
    root = phases["setup"]
    assert root["t0"] == 0.0
    for name, p in phases.items():
        assert root["t0"] <= p["t0"] <= p["t1"] <= root["t1"], name
        assert p["self_s"] <= p["s"] + 1e-9, name
    # nesting: the data object inside build_model (a synthetic train
    # set is generated at its first use, inside compile_iter_fns: the
    # two parts are one entry), the staging inside compile_iter_fns;
    # the self seconds partition the root
    assert phases["setup.build_model"]["t0"] <= phases["setup.data"]["t0"]
    assert (phases["setup.data"]["t1"]
            <= phases["setup.stage_data"]["t0"]
            <= phases["setup.stage_data"]["t1"]
            <= phases["setup.compile_iter_fns"]["t1"])
    assert sum(p["self_s"] for p in phases.values()) == pytest.approx(
        root["s"])
    # the step program was compiled (or loaded) in the warm-up
    assert phases["setup.warmup"]["programs"] >= 1
    assert last_setup_phases() == phases
    assert res["compiles_after_warmup"] == []
    assert res["n_compiles_after_warmup"] == 0


def test_setup_record_nests_and_counts_with_a_given_clock():
    class Meter:
        compile_s, programs, hits, misses = 0.0, 0, 0, 0
        read, since = CompileMeter.read, CompileMeter.since

    now = [100.0]
    meter = Meter()
    rec = SetupRecord(meter, clock=lambda: now[0])
    with rec.phase("build_model"):
        now[0] += 1.0
        with rec.phase("data"):
            now[0] += 2.0
            meter.compile_s, meter.programs = 0.5, 1
        now[0] += 0.25
        meter.compile_s, meter.programs = 0.75, 2
    rec.open_phase("warmup")
    now[0] += 4.0
    meter.compile_s, meter.programs, meter.hits = 2.75, 3, 1
    rec.close(at=now[0] - 1.0)        # the fence was a second ago
    with rec.phase("data"):           # after the set-up: not recorded
        now[0] += 9.0
    got = rec.as_dict()
    assert list(got) == ["setup", "setup.build_model", "setup.data",
                         "setup.warmup"]
    assert got["setup.build_model"]["s"] == pytest.approx(3.25)
    assert got["setup.build_model"]["self_s"] == pytest.approx(1.25)
    assert got["setup.build_model"]["compile_s"] == pytest.approx(0.25)
    assert got["setup.data"]["compile_s"] == pytest.approx(0.5)
    assert got["setup.warmup"]["s"] == pytest.approx(3.0)
    assert got["setup.warmup"]["cache_hits"] == 1
    assert got["setup"]["s"] == pytest.approx(6.25)
    assert got["setup"]["self_s"] == pytest.approx(0.0)
    assert got["setup"]["programs"] == 0


def test_setup_phase_outside_a_setup_does_nothing():
    before = last_setup_phases()
    with setup_phase("data"):         # no record is open
        pass
    assert last_setup_phases() == before


# -- the compile counter -----------------------------------------------------


def test_compile_meter_counts_programs_and_differences():
    import jax
    import jax.numpy as jnp

    meter = process_meter()
    assert process_meter() is meter
    before = meter.read()
    jax.jit(lambda x: x * 3 + 1, inline=False)(jnp.ones(7)).block_until_ready()
    new = meter.since(before)
    assert new["programs"] >= 1 and new["compile_s"] > 0.0
    assert set(new) == {"compile_s", "programs", "cache_hits",
                        "cache_misses"}
    assert meter.since(meter.read())["programs"] == 0


def test_a_forced_recompile_is_reported_with_its_iteration():
    """A model whose caches are dropped at the first epoch boundary:
    the step program compiles again at the first dispatch of epoch 1,
    and the summary says at which iteration that was seen."""
    from theanompi_tpu.models.wresnet import WResNet

    class Forgetful(WResNet):
        def adjust_hyperp(self, epoch):
            import jax

            super().adjust_hyperp(epoch)
            if epoch == 1:
                jax.clear_caches()

    module = types.ModuleType("forgetful_model")
    module.Model = Forgetful
    sys.modules[module.__name__] = module
    try:
        res = _run(modelfile=module.__name__, modelclass="Model")
    finally:
        del sys.modules[module.__name__]
    notes = res["compiles_after_warmup"]
    assert notes and res["n_compiles_after_warmup"] >= 1
    first = notes[0]
    # epoch 0 ran 4 iterations; the first chunk of epoch 1 ends at 6
    assert first["epoch"] == 1 and first["iteration"] == 6
    assert first["programs"] >= 1 and first["compile_s"] > 0.0
    assert isinstance(first["last_program"], str)


# -- the seconds before the worker -------------------------------------------


def test_process_stamps_are_monotone_beside_the_phases():
    """Process start <= import start <= import end <= the worker's
    entry, on ``time.monotonic``; the summary carries the three
    differences beside the phase dict, whose keys are what they
    were."""
    import time

    import theanompi_tpu
    from theanompi_tpu.obs import last_process_phases
    from theanompi_tpu.obs.setup import process_start

    res = _run(n_epochs=1)
    got = res["process_phases"]
    assert list(got) == ["before_import", "import", "before_worker"]
    assert all(v is not None and v >= 0 for v in got.values()), got
    t0, t1 = theanompi_tpu._IMPORT_SPAN
    assert process_start() <= t0 <= t1 <= time.monotonic()
    assert got["import"] == t1 - t0
    assert got["before_import"] == pytest.approx(t0 - process_start())
    assert last_process_phases() == got
    # beside the phases, not among them
    assert sorted(res["setup_phases"]) == sorted(SETUP_NAMES)
    assert sorted(last_setup_phases()) == sorted(SETUP_NAMES)
    for phase in last_setup_phases().values():
        assert sorted(phase) == ["cache_hits", "cache_misses", "compile_s",
                                 "programs", "s", "self_s", "t0", "t1"]


def test_process_stamps_without_a_readable_start(monkeypatch):
    from theanompi_tpu.obs import setup as setup_mod

    def unreadable(*a, **k):
        raise OSError("no /proc here")

    # the module's own name for ``open`` shadows the builtin
    monkeypatch.setattr(setup_mod, "open", unreadable, raising=False)
    setup_mod.process_start.cache_clear()
    try:
        assert setup_mod.process_start() is None
        got = SetupRecord(process_meter()).process_phases()
    finally:
        monkeypatch.undo()
        setup_mod.process_start.cache_clear()
    assert got["before_import"] is None
    assert got["import"] >= 0 and got["before_worker"] >= 0
    assert setup_mod.process_start() is not None
