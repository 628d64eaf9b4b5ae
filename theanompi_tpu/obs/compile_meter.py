"""The process's account of compilation, from JAX's own monitoring
events: programs handed to the backend compiler or fetched from the
persistent cache, the seconds that took (tracing and lowering are
host time and are not in it), and the cache's hits and misses.

JAX's listeners cannot be removed, so a process keeps ONE meter
(:func:`process_meter`) and its readers take differences
(:meth:`CompileMeter.since`).  ``workers/bsp_worker.py`` reads it
around set-up and at every iteration boundary — a compile after the
warm-up is the program's own answer to "which step recompiled";
``chip_smoke.py`` reads it around each phase.
"""

from __future__ import annotations

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        #: name of the function compiled last (``jit``'s ``fun_name``)
        self.last_program: str | None = None
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, duration: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compile_s += duration
            self.programs += 1
            self.last_program = kw.get("fun_name")

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def read(self) -> dict:
        return {"compile_s": self.compile_s, "programs": self.programs,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, before: dict) -> dict:
        """What was compiled or loaded since ``before`` (a ``read()``)."""
        now = self.read()
        return {k: now[k] - before[k] for k in now}


_PROCESS_METER: CompileMeter | None = None


def process_meter() -> CompileMeter:
    """The one meter of this process, made at the first call (events
    before it are not counted: call it before the work to be read)."""
    global _PROCESS_METER
    if _PROCESS_METER is None:
        _PROCESS_METER = CompileMeter()
    return _PROCESS_METER
