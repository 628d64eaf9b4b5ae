"""Where a training run's set-up seconds go: a handful of named
phases on the host clock, from the worker's entry to the first fence.

``workers/bsp_worker.run`` opens the record (:func:`begin_setup`) and
closes it at the fence that ends the first epoch; in between it and
the model code it calls bracket their work with
:func:`setup_phase` — no handle is passed down, the open record is
the process's.  The phases (names are a contract, docs/OBSERVABILITY.md):

- ``setup`` — the root: entry of ``run`` to the first fence;
- ``setup.build_model`` — ``Model(cfg)`` and ``build_model``;
- ``setup.data`` — the model's data object, inside ``build_model``,
  and the host generation of the train set, which a synthetic set
  puts off to its first use inside ``compile_iter_fns`` (one entry
  with the sum of the two);
- ``setup.compile_iter_fns`` — the step functions built, the weights
  and optimizer state placed (made, for models that make them under
  ``jit`` with sharded outputs);
- ``setup.stage_data`` — the device-resident train set with its
  cast, inside ``compile_iter_fns``;
- ``setup.resume`` — ``begin_resilient_run`` (a checkpoint restored);
- ``setup.warmup`` — the first epoch's first dispatch (which traces,
  compiles or loads the step program) to the first fence.

Every phase carries its seconds (``s``), its seconds less the phases
nested in it (``self_s``), and what the process's
:class:`~theanompi_tpu.obs.compile_meter.CompileMeter` counted during
its own part (``compile_s``, ``programs``, ``cache_hits``,
``cache_misses``).  The ``self_s`` of all phases add up to the
root's ``s``, the ``compile_s`` to the set-up's compile seconds.
The record goes into ``run``'s summary (``"setup_phases"``) and stays
readable afterwards with :func:`last_setup_phases`.

The seconds BEFORE the worker's entry stand beside the phases, not
among them (:meth:`SetupRecord.process_phases`, the summary's
``"process_phases"``, :func:`last_process_phases`): three stamps on
the same clock —

- ``before_import`` — the process's start (``/proc/self/stat``) to
  the start of ``import theanompi_tpu``: the interpreter, and what
  the caller imported and did first (``import jax``, the devices);
  ``None`` where the start cannot be read;
- ``import`` — ``import theanompi_tpu`` itself;
- ``before_worker`` — its end to the worker's entry: the model's
  module, the rule's ``init``.

A dozen stamps a process: always on.  Each phase is also a
``jax.profiler.TraceAnnotation`` (``tm:setup.<name>``), for a
profiler session that covers the start of a process.
"""

from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager, nullcontext

from theanompi_tpu.obs.compile_meter import CompileMeter, process_meter

ROOT = "setup"
_COUNTS = ("compile_s", "programs", "cache_hits", "cache_misses")


@functools.cache
def process_start() -> float | None:
    """The process's start on ``time.monotonic``'s clock, from its
    age by ``/proc/self/stat`` (field 22, ticks since boot); ``None``
    where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic() - age if age >= 0 else None


class SetupRecord:
    def __init__(self, meter: CompileMeter, clock=time.monotonic):
        self.meter = meter
        self.clock = clock
        #: the host's monotonic clock at the root's start
        self.t0 = clock()
        self._done: list[dict] = []
        self._stack: list[dict] = []
        #: called with a phase's full name at its end, inside it (the
        #: run's memory account samples there, obs/memory.py)
        self.on_phase_end = None
        self._open(ROOT, self.t0)

    @property
    def closed(self) -> bool:
        return not self._stack

    def _open(self, name: str, t: float) -> None:
        self._stack.append({
            "name": name, "t0": t, "meter": self.meter.read(),
            "nested_s": 0.0, "nested": dict.fromkeys(_COUNTS, 0),
        })

    def _close(self, t: float) -> None:
        top = self._stack.pop()
        s = t - top["t0"]
        counted = self.meter.since(top["meter"])
        if self._stack:
            parent = self._stack[-1]
            parent["nested_s"] += s
            for k in _COUNTS:
                parent["nested"][k] += counted[k]
        self._done.append({
            "name": top["name"], "t0": top["t0"] - self.t0,
            "t1": t - self.t0, "s": s, "self_s": s - top["nested_s"],
            **{k: counted[k] - top["nested"][k] for k in _COUNTS},
        })

    @contextmanager
    def phase(self, name: str):
        import jax

        if self.closed:     # after the first fence: not set-up any more
            yield
            return
        with jax.profiler.TraceAnnotation(f"tm:{ROOT}.{name}"):
            self._open(f"{ROOT}.{name}", self.clock())
            try:
                yield
            finally:
                if self.on_phase_end is not None:
                    self.on_phase_end(f"{ROOT}.{name}")
                self._close(self.clock())

    def open_phase(self, name: str) -> None:
        """A phase whose end is not in the function that starts it
        (the warm-up); ended by :meth:`close`."""
        self._open(f"{ROOT}.{name}", self.clock())

    def close(self, at: float | None = None) -> None:
        """End the root, and any phase still open, at ``at`` (the
        recorder's stamp of the first fence) or now."""
        global _CURRENT
        t = self.clock() if at is None else at
        while self._stack:
            self._close(t)
        if _CURRENT is self:
            _CURRENT = None

    def process_phases(self) -> dict:
        """``{before_import, import, before_worker}``: the seconds
        from the process's start to this record's ``t0`` (see the
        module docstring); ``before_import`` may be ``None``."""
        import theanompi_tpu

        started = process_start()
        t0, t1 = theanompi_tpu._IMPORT_SPAN
        return {
            "before_import": None if started is None else t0 - started,
            "import": t1 - t0,
            "before_worker": self.t0 - t1,
        }

    def as_dict(self) -> dict:
        """``{phase: {t0, t1, s, self_s, compile_s, programs,
        cache_hits, cache_misses}}``, times in seconds from the
        root's start; a phase entered twice is one entry with the
        sums.  A record read before its first fence has no root yet."""
        out: dict = {}
        for p in self._done:
            got = out.get(p["name"])
            if got is None:
                out[p["name"]] = {k: v for k, v in p.items() if k != "name"}
                continue
            got["t0"], got["t1"] = min(got["t0"], p["t0"]), max(got["t1"], p["t1"])
            for k in ("s", "self_s", *_COUNTS):
                got[k] += p[k]
        return dict(sorted(out.items(),
                           key=lambda kv: (kv[1]["t0"], -kv[1]["t1"])))


#: the record being written, and the newest one (open or closed)
_CURRENT: SetupRecord | None = None
_LAST: SetupRecord | None = None


def begin_setup() -> SetupRecord:
    """Open the process's set-up record at a worker's entry."""
    global _CURRENT, _LAST
    _CURRENT = _LAST = SetupRecord(process_meter())
    return _CURRENT


def setup_phase(name: str):
    """``with setup_phase("data"):`` in code a worker calls during
    set-up; does nothing outside a set-up."""
    return nullcontext() if _CURRENT is None else _CURRENT.phase(name)


def last_setup_phases() -> dict | None:
    """The set-up phases of the newest run of this process (the form
    of :meth:`SetupRecord.as_dict`), or None before any."""
    return None if _LAST is None else _LAST.as_dict()


def last_process_phases() -> dict | None:
    """The seconds before the worker's entry of the newest run of
    this process (:meth:`SetupRecord.process_phases`), or None before
    any."""
    return None if _LAST is None else _LAST.process_phases()
