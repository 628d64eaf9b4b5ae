"""Streaming input pipeline: host work overlapped with the step.

The reference decoupled data loading from compute — one loading
process per training process (``proc_load_mpi``), batches arriving
over shared memory so the GPU never waited on disk or augmentation.
Our SPMD reproduction lost that: every ``train_iter`` fetched and
staged its batch INLINE, and PR 13's step-phase profiler priced the
loss precisely: most of a step went to the ``host_gap`` leg,
dwarfing geometry and exposed comm combined.

Two pieces restore the overlap:

- :class:`HostStager` — the ONE copy of the transfer discipline: a
  host ``(x, y)`` batch becomes device-resident arrays under the
  step's data sharding via async ``jax.device_put``, then passes
  through a tiny jitted ``lax.optimization_barrier`` identity under
  ``jax.named_scope("host_load")``.  ``device_put`` itself never
  appears in any HLO, so the staging executable is the one place the
  feed owns a compiled artifact: its HLO rides into the step
  profile's scope sets (``stage_hlo_text`` → ``aux_hlo_texts``), and
  any device-side residual the backend keeps attributes to the
  ``host_load`` leg instead of lumping into ``host_gap``.  The
  barrier is bitwise-identity (unlike ``x + 0``, which folds
  ``-0.0`` to ``+0.0``) at zero numeric cost; note XLA's barrier
  expander DOES strip it from the final executable once optimization
  passes ran, so on backends that alias the pass-through (CPU SPMD
  does) the leg honestly prices to ≈ 0 — the exposed feed cost the
  A/B asserts on is the train loop's wait segment, not this leg.
  Train, val, and replica-engine staging all route through here.

- :class:`StreamingLoader` — a producer thread pulls ``fetch(i)``
  (any source honoring the model-data contract's ``train_batch``)
  and stages into a bounded ring of DEVICE-resident batches, so
  iteration k's fetch + transfer ride under iteration k-1's compute.
  The consumer side is a drop-in :meth:`StreamingLoader.next` the
  worker loops call instead of the inline put.  The batch SEQUENCE
  is defined by the epoch permutation, not by the transport: the
  pipelined stream is bitwise-equal to the synchronous feed, and a
  starved consumer (producer stalled — the ``stall_loader`` fault
  drill) degrades to a synchronous fetch with a ``starved`` counter
  instead of deadlocking.

Fencing discipline (no per-step value fences; ``tmcheck`` TM104/TM105):
neither the producer nor ``next()`` ever reads a device value — the
ring bounds in-flight transfers by COUNT, and the consumer's compute
waits on the data dependency, not on a host fence.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "HostStager", "StreamingLoader", "engine_feed",
    "resolve_loader_depth",
]


def resolve_loader_depth(cfg: dict) -> int:
    """The ``loader_pipeline`` config knob, validated: 0/None/False =
    synchronous feed (the default), an int >= 2 = pipelined feed with
    that many ring slots (2 = classic double buffering).  The ONE
    resolver — workers validate through it before the model build so
    a bad value fails in milliseconds, and models size the ring with
    the same rule."""
    raw = cfg.get("loader_pipeline", 0)
    if raw is None or raw is False:
        return 0
    if raw is True:
        return 2
    try:
        depth = int(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"loader_pipeline must be an int ring depth (0 = off, "
            f">= 2 = pipelined), got {raw!r}"
        ) from None
    if depth == 0:
        return 0
    if depth < 2:
        raise ValueError(
            f"loader_pipeline needs at least 2 ring slots to overlap "
            f"(double buffering); got {depth}"
        )
    return depth


def engine_feed(cfg: dict, data, engine, *, epoch_of=None, world=None):
    """The in-process async loops' feed (EASGD/GoSGD): a
    :class:`StreamingLoader` over ``(data.train_batch,
    engine.put_batch)`` whose staged batches go straight to
    ``ReplicaEngine.train_step_staged``.  None when the
    ``loader_pipeline`` knob is off (the synchronous default)."""
    depth = resolve_loader_depth(cfg)
    if not depth:
        return None
    journal_meta = None
    if epoch_of is not None:
        def journal_meta():
            m = {"epoch": int(epoch_of())}
            if world is not None:
                m["world"] = int(world)
            return m
    return StreamingLoader(
        data.train_batch,
        engine.put_batch,
        n_batches=lambda: data.n_batch_train,
        depth=depth,
        global_batch=int(data.global_batch),
        sample_ids=getattr(data, "batch_indices", None),
        journal_meta=journal_meta,
    )


class HostStager:
    """One copy of the host→device transfer discipline (module doc).

    ``sharding`` — the step's data sharding (``NamedSharding`` over
    the mesh's data axis).  ``dtypes`` — optional per-array casts
    applied host-side (the Llama models feed int32 token ids).
    """

    def __init__(self, sharding, *, dtypes=None):
        self.sharding = sharding
        self.dtypes = dtypes

        def _mark(arrays):
            with jax.named_scope("host_load"):
                return lax.optimization_barrier(arrays)

        self._mark = jax.jit(_mark, donate_argnums=(0,))
        self._example = None

    def stage(self, batch):
        """Host ``(x, y, ...)`` tuple → device-resident tuple under
        ``self.sharding``, device ops labelled ``host_load``.  The
        ``device_put`` is asynchronous: the call returns while the
        copy is in flight, and downstream compute waits on the data
        dependency, never on a host fence."""
        arrays = tuple(batch)
        dtypes = self.dtypes or (None,) * len(arrays)
        put = tuple(
            jax.device_put(
                jnp.asarray(a) if dt is None else jnp.asarray(a, dt),
                self.sharding,
            )
            for a, dt in zip(arrays, dtypes)
        )
        if self._example is None:
            self._example = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                for a in put
            )
        return self._mark(put)

    def hlo_text(self) -> str | None:
        """Optimized HLO of the staging executable — merged into the
        step profile's scope sets (``profile_scope_sets`` aux texts)
        so any device-side residual the backend keeps attributes to
        the ``host_load`` leg (≈ 0 where the barrier expander aliased
        the pass-through — see module doc).  None before the first
        :meth:`stage` call (shapes unknown)."""
        if self._example is None:
            return None
        from theanompi_tpu.utils.trace_comm import compiled_hlo_text

        return compiled_hlo_text(
            self._mark.lower(self._example).compile()
        )


class StreamingLoader:
    """Producer-thread pipeline over any ``fetch(i)`` batch source
    (module doc).

    ``fetch(i)`` — host batch for in-epoch index ``i`` (the model-data
    contract's ``train_batch``); must be a pure indexed read (the
    starvation fallback may call it from the consumer thread while a
    stalled producer still holds a reference — true of every in-repo
    data object, whose batches are permutation-indexed views).
    ``stage(batch)`` — host batch → device-resident batch (a
    :class:`HostStager`-backed callable).  ``n_batches`` — int or
    callable giving the epoch length; the producer never reads past
    it, so a fresh permutation installed by ``shuffle(epoch)`` before
    the epoch's first ``next(0)`` is the one it fetches from.

    Restarts/jumps need no bookkeeping by the caller: ``next(i)`` for
    an out-of-sequence ``i`` resyncs the producer (generation bump;
    queued stale batches drop), which is how epoch boundaries,
    mid-epoch resumes, and post-starvation realignment all work.
    """

    def __init__(self, fetch, stage, *, n_batches, depth=2,
                 timeout_s=2.0, global_batch=None, sample_ids=None,
                 journal_meta=None):
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        self._fetch = fetch
        self._stage = stage
        self._n_batches = (
            n_batches if callable(n_batches) else (lambda: n_batches)
        )
        self.depth = int(depth)
        self.timeout_s = float(timeout_s)
        self.global_batch = (
            int(global_batch) if global_batch is not None else None
        )
        self._sample_ids = sample_ids
        self._journal_meta = journal_meta
        self._journal_path = os.environ.get("TM_LOADER_JOURNAL")

        self._cv = threading.Condition()
        # guarded-by: _cv
        self._ring: deque = deque()
        self._gen = 0
        self._next_prod = 0
        self._next_cons: int | None = None
        self._stop = False
        # telemetry (written under _cv, read-only from summaries)
        self.starved = 0       # consumer timeouts -> synchronous fetch
        self.staged = 0        # batches delivered from the ring
        self._thread: threading.Thread | None = None

    # -- producer ---------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._produce, name="tm-loader", daemon=True
            )
            self._thread.start()

    def _produce(self) -> None:
        from theanompi_tpu.utils import faults

        while True:
            with self._cv:
                while not self._stop and (
                    len(self._ring) >= self.depth
                    or self._next_prod >= self._n_batches()
                ):
                    self._cv.wait(0.25)
                if self._stop:
                    return
                gen, i = self._gen, self._next_prod
            if faults.consume_loader_stall():
                # the stall drill: the producer stops staging for this
                # iteration — sleep past the consumer's timeout so the
                # degrade path (synchronous fetch + starved counter)
                # takes over instead of a deadlock
                time.sleep(self.timeout_s)
                continue
            batch = self._fetch(i)
            staged = self._stage(batch)
            with self._cv:
                if gen == self._gen and i == self._next_prod:
                    self._ring.append((gen, i, staged))
                    self._next_prod = i + 1
                else:
                    # resynced mid-stage (epoch restart / starvation
                    # realignment): the batch is stale — drop it; the
                    # permutation, not the transport, defines order
                    staged = None
                self._cv.notify_all()

    # -- consumer (the worker loops' drop-in) -----------------------------

    def _resync(self, i: int) -> None:
        """Point the producer at ``i`` (caller holds ``_cv``)."""
        self._gen += 1
        self._ring.clear()
        self._next_prod = i
        self._cv.notify_all()

    def next(self, i: int):
        """Device-resident batch for in-epoch index ``i`` — the
        drop-in for the inline fetch+put.  Sequential calls ride the
        ring; a timeout degrades to a synchronous fetch (recorded in
        ``starved``), never a deadlock."""
        self._ensure_thread()
        fallback = False
        with self._cv:
            if self._next_cons != i:
                self._resync(i)
            deadline = time.monotonic() + self.timeout_s
            staged = None
            while staged is None:
                while self._ring and (
                    self._ring[0][0] != self._gen
                    or self._ring[0][1] < i
                ):
                    self._ring.popleft()   # stale generation / index
                if self._ring and self._ring[0][1] == i:
                    staged = self._ring.popleft()[2]
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    fallback = True
                    break
                self._cv.wait(remaining)
            if fallback:
                self.starved += 1
                # realign the producer PAST i: we will fetch i
                # ourselves, and its late-staged copy must drop
                self._resync(i + 1)
            else:
                self.staged += 1
                self._cv.notify_all()
            self._next_cons = i + 1
        if fallback:
            staged = self._stage(self._fetch(i))
        self._journal(i)
        return staged

    # -- cursor / accounting ----------------------------------------------

    def cursor(self) -> dict:
        """The stream cursor stamped into checkpoints: the next
        in-epoch batch index and its SAMPLE offset (sample units
        survive an elastic global-batch regrid), plus delivery
        counters.  The permutation itself is derived state —
        ``shuffle(epoch)`` reseeds it deterministically, so epoch +
        offset identify the position exactly."""
        with self._cv:
            nxt = self._next_cons or 0
            return {
                "next_iter": nxt,
                "next_sample": (
                    nxt * self.global_batch
                    if self.global_batch is not None else None
                ),
                "global_batch": self.global_batch,
                "staged": self.staged,
                "starved": self.starved,
            }

    def _journal(self, i: int) -> None:
        """Sample-id accounting (``TM_LOADER_JOURNAL`` env): one JSON
        line per delivered batch — the elastic drills' zero-lost/
        zero-duplicated proof reads this across kills and resumes.
        Flushed per line so a preemption-style ``os._exit`` cannot
        lose delivered entries."""
        if not self._journal_path or self._sample_ids is None:
            return
        entry = {"iter": i}
        if self._journal_meta is not None:
            entry.update(self._journal_meta())
        ids = self._sample_ids(i)
        entry["ids"] = [int(s) for s in ids]
        with open(self._journal_path, "a") as f:
            f.write(json.dumps(entry) + "\n")
            f.flush()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.timeout_s + 1.0)
            self._thread = None
