"""Exchange-strategy registry.

The reference selects an allreduce implementation by config string
(reference: ``theanompi/lib/exchanger_strategy.py`` — ``Exch_allreduce``
host-staged MPI, ``Exch_asa32``/``Exch_asa16`` GPU-direct CUDA-MPI ring
reduce-scatter+allgather, ``Exch_nccl32``/``Exch_nccl16`` pygpu NCCL).
On TPU every strategy lowers to XLA ICI collectives; what survives is
the *strategy surface*: the same config names map to
(wire dtype × collective shape):

=========  ==========  ===========  =====================================
name       wire dtype  lowering     reference analogue
=========  ==========  ===========  =====================================
ar         fp32        psum         host-staged MPI.Allreduce
asa32      fp32        rs+ag        CUDA-aware MPI ring (two-phase)
asa16      bf16        rs+ag        fp16-wire CUDA-aware MPI ring
nccl32     fp32        psum         pygpu GpuComm.all_reduce
nccl16     bf16        psum         fp16-wire NCCL
=========  ==========  ===========  =====================================

(bf16 replaces fp16 on the wire: same 2x byte saving, TPU-native
number format, no loss-scaling needed for gradient exchange.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

from theanompi_tpu.parallel.exchange import allreduce_mean


@dataclasses.dataclass(frozen=True)
class ExchangeStrategy:
    """A named allreduce flavor: wire dtype + collective shape.

    ``zero1=True`` marks the ZeRO-1 strategies: ``ExchangePlan.apply``
    swaps the allreduce-then-replicated-update step body for
    ``exchange.scatter_update_gather`` (reduce-scatter grads → update
    the optimizer on the 1/N shard → all-gather updated params) over
    SHARD-shaped optimizer state.  Calling a zero1 strategy
    directly still allreduce-means (the two-phase wire it shares) —
    auxiliary exchanges like BN-stat sync route through it unchanged.

    ``bucket_elems`` (call-time, from the ``exchange_bucket_mb``
    config knob) buckets the exchange buffer so per-bucket collectives
    overlap with compute — see ``exchange.allreduce_mean`` /
    ``scatter_update_gather``; 0 keeps the monolithic exchange.

    A replica group of one exchanges nothing (``allreduce_mean``
    returns its input), so there all non-zero1 strategies are the
    same step.
    """

    name: str
    wire_dtype: Optional[Any]       # None = native dtype on the wire
    two_phase: bool                  # reduce_scatter+all_gather vs psum
    zero1: bool = False              # sharded-optimizer step body

    def __call__(self, tree, axis_name: str | tuple[str, ...],
                 bucket_elems: int = 0):
        return allreduce_mean(
            tree,
            axis_name,
            wire_dtype=self.wire_dtype,
            two_phase=self.two_phase,
            bucket_elems=bucket_elems,
        )

    def bucket_elems(self, bucket_mb: float, dtype_bytes: int = 4) -> int:
        """``exchange_bucket_mb`` → elements of the fp32 master-width
        exchange buffer per bucket (0 stays 0 = monolithic)."""
        if not bucket_mb:
            return 0
        return max(1, int(float(bucket_mb) * 2**20 / dtype_bytes))


STRATEGIES: dict[str, ExchangeStrategy] = {
    s.name: s
    for s in (
        ExchangeStrategy("ar", None, False),
        ExchangeStrategy("asa32", None, True),
        ExchangeStrategy("asa16", jnp.bfloat16, True),
        ExchangeStrategy("nccl32", None, False),
        ExchangeStrategy("nccl16", jnp.bfloat16, False),
        # TPU-native aliases (preferred spelling in new configs):
        ExchangeStrategy("ici32", None, False),
        ExchangeStrategy("ici16", jnp.bfloat16, False),
        # ZeRO-1: the asa* two-phase wire, optimizer state sharded 1/N
        # over the data axis (zero1_16 = bf16 gradient wire analogue)
        ExchangeStrategy("zero1", None, True, zero1=True),
        ExchangeStrategy("zero1_16", jnp.bfloat16, True, zero1=True),
    )
}


# exchange_bucket_mb default: DDP-style ~4 MiB buckets (Li et al.
# 2020's knee between per-collective launch overhead and overlap
# granularity); 0 = monolithic.  ONE resolver, read through
# ``plan.ExchangePlan`` by the workers and the models' compiles.
DEFAULT_BUCKET_MB = 4.0


def resolve_bucket_mb(config: dict | None) -> float:
    """The ``exchange_bucket_mb`` config knob, validated: None/0 →
    0.0 (monolithic), unset → ``DEFAULT_BUCKET_MB``."""
    mb = float((config or {}).get(
        "exchange_bucket_mb", DEFAULT_BUCKET_MB) or 0)
    if mb < 0:
        raise ValueError(
            f"exchange_bucket_mb must be >= 0 (0 = monolithic "
            f"exchange), got {mb}"
        )
    return mb


# exch_compression: quantized 1-byte wire for the gradient exchange
# (parallel/exchange quantize/dequantize + all_to_all reduce-scatter),
# with an error-feedback residual carried in worker state so the
# quantization error is re-injected next step (error_feedback=True,
# the default; False drops it — plain QSGD, for A/B only).  ONE
# resolver (the resolve_bucket_mb pattern).
COMPRESSION_CHOICES = ("none", "int8", "fp8")


def resolve_compression(config: dict | None) -> tuple[str | None, bool]:
    """The ``exch_compression`` + ``error_feedback`` config knobs,
    validated: returns ``(compression, error_feedback)`` where
    ``compression`` is ``None`` (no compression; unset/"none") or
    ``"int8"``/``"fp8"``."""
    c = config or {}
    comp = c.get("exch_compression", "none") or "none"
    if comp not in COMPRESSION_CHOICES:
        raise ValueError(
            f"unknown exch_compression {comp!r}; known: "
            f"{COMPRESSION_CHOICES}"
        )
    ef = bool(c.get("error_feedback", True))
    return (None if comp == "none" else comp), ef


def get_strategy(name: str) -> ExchangeStrategy:
    try:
        return STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown exch_strategy {name!r}; known: {sorted(STRATEGIES)}"
        ) from None
