"""The exchange plan: which path a training step's gradients take
and everything that follows from the choice.

``strategies.py`` names the wires and resolves the knobs,
``exchange.py`` holds the collectives; this module is the one place
that puts them together for a step, so the model families
(``models/base.py``, ``models/llama.py``) and the workers state none
of it themselves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.parallel.exchange import (
    compressed_allreduce_mean,
    exchange_bucket_count,
    flat_layout,
    scatter_update_gather,
)
from theanompi_tpu.parallel.mesh import EXPERT_AXIS
from theanompi_tpu.parallel.strategies import (
    ExchangeStrategy,
    get_strategy,
    resolve_bucket_mb,
    resolve_compression,
)


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Strategy x compression x error feedback x bucketing, decided
    once, in two stages.  ``from_config`` validates the four knobs:
    what a worker needs before the model build (a typo fails in
    milliseconds) and reports in its summary.  ``bind`` fixes the
    step's layout; a bound plan knows the flat layout, the zero1
    optimizer-state and EF-residual shapes with their specs, the
    checkpoint stamps, what a restore before the compile may keep, and
    ``apply`` IS the exchange + optimizer update of the step body.
    Callers hand it data (axes, sizes, a mask), never their name.
    """

    strategy: ExchangeStrategy
    bucket_mb: float
    compression: Optional[str]      # None | "int8" | "fp8"
    error_feedback: bool            # the compressed wire carries residuals
    # -- the step's layout (``bind``) --
    optimizer: Any = None
    replica_axes: tuple = ()
    exchange_replicas: Optional[int] = None   # size of the replica group
    flat_axes: tuple = ()           # mesh axes the flat buffers vary over
    flat_devices: int = 1           # ... and the product of their sizes
    per_leaf: bool = False
    n_elems: int = 0                # per-device parameter-pack size
    padded: int = 0                 # flat_layout(n_elems, replicas, bucket)
    bucket_len: int = 0

    @classmethod
    def from_config(cls, config: dict | None,
                    exch_strategy: str | None = None) -> "ExchangePlan":
        c = config or {}
        strategy = get_strategy(
            exch_strategy or c.get("exch_strategy", "ici32")
        )
        bucket_mb = resolve_bucket_mb(c)
        comp, use_ef = resolve_compression(c)
        return cls(strategy, bucket_mb, comp, bool(comp) and use_ef)

    def bind(self, axis_sizes: Mapping[str, int], *, n_elems: int,
             replica_axes: tuple, flat_axes: tuple, optimizer,
             per_leaf: bool = False) -> "ExchangePlan":
        """``axis_sizes``: ``mesh.shape``.  ``per_leaf``: leaves reduce
        over DIFFERENT axis sets (expert-sharded ones over the replica
        axes less ``expert``), so no flat buffer can span them — such
        a step neither buckets, shards its optimizer nor quantizes."""
        if per_leaf and self.zero1:
            raise NotImplementedError(
                "exch_strategy='zero1' does not yet compose with "
                "MoE expert sharding (n_experts > 0): expert "
                "leaves exchange over data alone while dense "
                "leaves exchange over (expert, data) — two "
                "separate shard groups"
            )
        if per_leaf and self.compression:
            raise NotImplementedError(
                "exch_compression does not yet compose with MoE "
                "expert sharding (n_experts > 0): expert and dense "
                "leaves exchange over different shard groups, so "
                "there is no single flat buffer to quantize (same "
                "split that keeps MoE+zero1 NotImplementedError)"
            )
        n = math.prod(axis_sizes[a] for a in replica_axes)
        padded, bucket_len = flat_layout(n_elems, n, self.bucket_elems)
        return dataclasses.replace(
            self, optimizer=optimizer, replica_axes=tuple(replica_axes),
            exchange_replicas=n, flat_axes=tuple(flat_axes),
            flat_devices=math.prod(axis_sizes[a] for a in flat_axes),
            per_leaf=per_leaf, n_elems=n_elems, padded=padded,
            bucket_len=bucket_len,
        )

    # -- from the configuration alone -------------------------------------

    @property
    def zero1(self) -> bool:
        return self.strategy.zero1

    @property
    def bucket_elems(self) -> int:
        return self.strategy.bucket_elems(self.bucket_mb)

    @property
    def wire(self):
        """The async rules' TCP wire: the codec name under
        compression, else the strategy's dtype (None = native)."""
        return self.compression or self.strategy.wire_dtype

    # -- of the bound layout ----------------------------------------------

    @property
    def exchange_buckets(self) -> int:
        """``exchange_b*`` bodies the step traces (run-summary key)."""
        return exchange_bucket_count(
            self.n_elems, self.exchange_replicas,
            0 if self.per_leaf else self.bucket_elems,
            flat=bool(self.zero1 or self.compression),
        )

    @property
    def bucketed(self) -> bool:
        """The layout ACTUALLY has buckets (tiny models degrade to
        monolithic, a per-leaf exchange never buckets): the input of
        the ``overlap`` compiler preset."""
        return bool(self.bucket_len) and not self.per_leaf

    @property
    def zero1_layout(self) -> tuple | None:
        """Checkpoint stamp: a zero1 optimizer shard's INTERNAL order
        is bucket-major, so it is only valid under this layout."""
        return (self.padded, self.bucket_len) if self.zero1 else None

    @property
    def ef_layout(self) -> tuple | None:
        """Checkpoint stamp of the EF residual's flat order."""
        if not self.error_feedback:
            return None
        return (self.compression, self.padded, self.bucket_len)

    @property
    def shard_len(self) -> int:
        return self.padded // self.exchange_replicas

    def _shard_state_shapes(self):
        """Shapes only: nothing ``[shard_len]`` is allocated."""
        return jax.eval_shape(
            lambda: self.optimizer.shard_state(self.shard_len)
        )

    @property
    def opt_state_specs(self):
        """zero1 state is a FLAT 1/N shard per device of the (already
        model-sharded) local parameter pack: it varies over every flat
        axis; scalars (adam's t) stay replicated."""
        return jax.tree.map(
            lambda x: P(self.flat_axes) if x.ndim else P(),
            self._shard_state_shapes(),
        )

    def init_opt_state(self):
        """Fresh zero1 state as GLOBAL arrays; call under jit with
        sharded ``out_shardings`` where the whole must not
        materialize."""
        n = self.shard_len * self.flat_devices
        return jax.tree.map(
            lambda x: jnp.zeros((n,), x.dtype) if jnp.ndim(x) else x,
            self.optimizer.shard_state(self.shard_len),
        )

    @property
    def ef_proto(self) -> dict:
        """EF residuals as global shapes, varying over every flat axis
        (packed local grads differ across tp/pp shards AND replicas):
        r1 is each device's own ``[padded]`` residual of the local-grad
        compression; r2 the shard owner's ``[shard_len]`` residual of
        the reduced-mean compression — absent under zero1, whose param
        gather is uncompressed.  Empty without error feedback."""
        if not self.error_feedback:
            return {}
        lens = {"r1": self.padded}
        if not self.zero1:
            lens["r2"] = self.shard_len
        return {
            k: jax.ShapeDtypeStruct((v * self.flat_devices,), jnp.float32)
            for k, v in lens.items()
        }

    @property
    def ef_specs(self) -> dict:
        return {k: P(self.flat_axes) for k in self.ef_proto}

    def init_ef(self, mesh) -> dict:
        proto = self.ef_proto
        if not proto:
            return {}
        return jax.jit(
            lambda: {k: jnp.zeros(v.shape, v.dtype)
                     for k, v in proto.items()},
            out_shardings={k: NamedSharding(mesh, s)
                           for k, s in self.ef_specs.items()},
        )()

    # -- a checkpoint restored BEFORE the compile -------------------------
    # (``restored``: TMModel._finish_load's record of what it attached)

    def check_restored_opt_state(self, opt_state, restored: Mapping) -> None:
        """A zero1 compile after a restore keeps same-layout state (a
        zero1 checkpoint: flat ``[padded]`` buffers); anything else it
        would have to zero, so it refuses."""
        saved = restored.get("zero1_layout")
        saved = (
            tuple(saved) if saved is not None
            else (self.padded, 0)        # pre-bucketing: monolithic
        )
        if not (
            jax.tree.structure(opt_state)
            == jax.tree.structure(self._shard_state_shapes())
            and all(
                jnp.shape(l) == (self.padded,)
                for l in jax.tree.leaves(opt_state) if jnp.ndim(l)
            )
            and saved == self.zero1_layout
        ):
            raise ValueError(
                "compile_iter_fns(exch_strategy='zero1') "
                "after a checkpoint restore would silently "
                "discard the restored optimizer state (the "
                "zero1 layout is a flat 1/N shard, not the "
                "restored tree) — compile first, then "
                "load(); cross-strategy resume is not "
                "supported"
            )

    def keeps_restored_ef(self, ef_state, restored: Mapping) -> bool:
        """True: ``ef_state`` is a restored residual in this layout
        and stays; False: install ``init_ef``.  A residual in the wrong
        flat order would re-inject rows against the wrong parameters,
        a fresh one in place of a restored one breaks interrupted ==
        uninterrupted: both refuse."""
        proto = self.ef_proto
        if not proto:
            return False
        if restored.get("ef_orphaned"):
            raise ValueError(
                "a checkpoint restored BEFORE this compile carried an "
                "EF residual (ef_layout stamped) that load() could "
                "not attach — the model had no compressed exchange "
                "yet.  Compiling now would silently zero the "
                "residual; compile_iter_fns first, then load()"
            )
        if not restored.get("ef_state"):
            return False
        saved = restored.get("ef_layout")
        if not (
            saved is not None
            and tuple(saved) == self.ef_layout
            and isinstance(ef_state, dict)
            and set(ef_state) == set(proto)
            and all(
                tuple(jnp.shape(ef_state[k])) == v.shape
                for k, v in proto.items()
            )
        ):
            raise ValueError(
                "compile_iter_fns with exch_compression after a "
                "checkpoint restore found an EF residual that "
                "does not match the compiled exchange layout "
                "(compression, padded, bucket_len) — compile "
                "first, then load(); cross-layout resume is not "
                "supported"
            )
        return True

    # -- inside the step --------------------------------------------------

    def apply(self, params, grads, opt_state, ef, lr, *,
              expert_mask=None, ep: int = 1):
        """THE exchange (reference: ``BSP_Exchanger.exchange`` between
        train iters, here folded into the step) and the optimizer
        update: ``(params, opt_state, ef)`` after them.  ``grads`` are
        per-replica local gradients; ``expert_mask``/``ep``: the
        per-leaf exchange's expert-sharded leaves and the size of the
        expert group."""
        optimizer, strat = self.optimizer, self.strategy
        comp, axes = self.compression, self.replica_axes
        over = axes if len(axes) > 1 else axes[0]
        if self.zero1:
            # reduce-scatter grads, update the optimizer on this
            # device's flat 1/N shard (opt_state IS that shard),
            # all-gather the updated params: the two-phase allreduce's
            # wire bytes, optimizer HBM /N.  Compressed, the grad
            # reduce-scatter ships 1-byte chunks + scales; the param
            # gather stays master-width (quantized params would corrupt
            # the replicated masters).
            def opt_upd(p_shard, g_shard, state):
                return optimizer.update(p_shard, g_shard, state, lr)

            if comp:
                params, opt_state, r1 = scatter_update_gather(
                    params, grads, opt_upd, over, opt_state=opt_state,
                    bucket_elems=self.bucket_elems,
                    compression=comp, r1=ef.get("r1"),
                )
                if "r1" in ef:
                    ef = {"r1": r1}
            else:
                params, opt_state = scatter_update_gather(
                    params, grads, opt_upd, over,
                    wire_dtype=strat.wire_dtype, opt_state=opt_state,
                    bucket_elems=self.bucket_elems,
                )
            return params, opt_state, ef
        if self.per_leaf:
            # expert-sharded grads: the all_to_all transpose already
            # summed the ep group's token cotangents at each owner, so
            # the global mean over the replicas is (mean over the
            # others) / ep; every other leaf averages over the whole
            # replica group
            rest = tuple(a for a in axes if a != EXPERT_AXIS)
            rest = rest if len(rest) > 1 else rest[0]

            def exch(g, is_exp):
                if is_exp:
                    g = strat(g, rest)
                    return (g / ep).astype(g.dtype) if ep > 1 else g
                return strat(g, over)

            grads = jax.tree.map(exch, grads, expert_mask)
        elif comp:
            grads, r1, r2 = compressed_allreduce_mean(
                grads, over, compression=comp,
                r1=ef.get("r1"), r2=ef.get("r2"),
                bucket_elems=self.bucket_elems,
            )
            if "r1" in ef:
                ef = {"r1": r1, "r2": r2}
        else:
            grads = strat(grads, over, self.bucket_elems)
        # profiler scope (analysis/registry.py): the optimizer update
        # is its own step-phase leg
        with jax.named_scope("opt_update"):
            params, opt_state = optimizer.update(
                params, grads, opt_state, lr
            )
        return params, opt_state, ef
