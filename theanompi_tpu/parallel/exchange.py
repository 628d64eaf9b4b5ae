"""Exchange rules as pure, jittable functions.

TPU-native rebuild of the reference's parameter-exchange layer
(reference: ``theanompi/lib/exchanger.py`` — ``BSP_Exchanger``,
``EASGD_Exchanger``, ``GOSGD_Exchanger``).  Three design shifts:

1. The reference exchanges *parameters* after each optimizer step and
   rescales by 1/N; here BSP exchanges *gradients* inside the jitted
   train step (mathematically equivalent given identical init, and it
   lets XLA overlap the allreduce with backprop).
2. Exchanges are pure functions over pytrees, called inside
   ``shard_map`` with a named axis — XLA lowers them to ICI
   collectives.  There is no buffer management; ``bufint``-style raw
   pointer plumbing (reference: ``theanompi/lib/helper_funcs.py``) has
   no TPU equivalent and is deliberately absent.
3. Wire-format compression (the reference's fp16 ``asa16``/``nccl16``
   strategies) becomes a cast to ``bfloat16`` around the collective.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

# The all-gather whose result is typed INVARIANT over the gathered
# axes.  Under a vma-checked shard_map (the Llama step) a plain
# ``lax.all_gather`` types its result as varying, so values every
# shard holds identically — exchanged grads, gathered params — could
# not leave through a replicated out_spec.  jax 0.9.0 does not export
# it from ``jax.lax``.
from jax._src.lax.parallel import all_gather_invariant as _all_gather

PyTree = Any


def _cast(tree: PyTree, dtype) -> PyTree:
    if dtype is None:
        return tree
    return jax.tree.map(lambda x: x.astype(dtype), tree)


# ---------------------------------------------------------------------------
# BSP: synchronous allreduce-mean (reference: BSP_Exchanger.exchange —
# NCCL allreduce / CUDA-MPI ring on param buffers, then scale by 1/N).
# ---------------------------------------------------------------------------

def allreduce_mean(
    tree: PyTree,
    axis_name: str | tuple[str, ...],
    *,
    wire_dtype=None,
    two_phase: bool = False,
    bucket_elems: int = 0,
) -> PyTree:
    """Mean-allreduce a pytree over ``axis_name``.

    ``wire_dtype`` casts values onto the "wire" before the collective
    (bf16 halves exchange bytes, like the reference's ``*16``
    strategies) and casts back to the original dtype after.

    ``two_phase=True`` lowers to reduce_scatter + all_gather (the
    reference's ``asa*`` ring strategies were explicitly two-phase);
    with ``False`` a single psum is emitted (the ``nccl*`` analogue).
    XLA usually picks the best algorithm either way — the knob exists
    to preserve the reference's strategy surface and for A/B profiling.

    ``axis_name`` may be a tuple of mesh axes — the reduction then
    spans their product (the MoE case: non-expert grads average over
    ``(expert, data)`` while expert-sharded grads average over
    ``data`` alone).

    ``bucket_elems > 0`` packs the tree into one flat buffer and
    exchanges it as fixed-size BUCKETS (DDP-style, Li et al. 2020):
    each bucket's collective depends only on the leaves it covers, so
    XLA's latency-hiding scheduler can dispatch bucket *i*'s wire time
    under bucket *i±1*'s (and the producing backward's) compute instead
    of serializing one monolithic tail.  Small leaves coalesce (fewer
    per-collective launches), large buffers split (earlier first
    dispatch).  When the tree fits in a single bucket the per-leaf
    monolithic path below runs unchanged.

    A replica group of ONE exchanges nothing: the mean over one
    member is the identity and a wire dtype describes bytes on a wire
    that is not there, so no flat buffer, bucket, cast or unpack is
    traced — every leaf comes back bitwise as it went in, whatever
    ``wire_dtype``, ``two_phase`` and ``bucket_elems`` say.
    """
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = 1
    for a in axes:
        n *= lax.axis_size(a)

    if n == 1:
        # the psum over the one-member group only re-types the leaf
        # from varying to invariant for a vma-checked shard_map (the
        # Llama step); XLA deletes it
        return jax.tree.map(lambda x: lax.psum(x, axes), tree)

    if bucket_elems:
        spec = flat_spec(tree, n, bucket_elems=bucket_elems)
        if spec.n_buckets > 1:
            parts = []
            for i in range(spec.n_buckets):
                # per-bucket profiler scope (obs/profiler.py leg
                # attribution); label prefix registered as a
                # PROFILE_SCOPE_PREFIX in analysis/registry.py
                with jax.named_scope(f"exchange_b{i}"):
                    b = flat_pack_bucket(tree, spec, i)
                    w = b if wire_dtype is None else b.astype(wire_dtype)
                    if two_phase:
                        part = lax.psum_scatter(
                            w, axes, scatter_dimension=0, tiled=True
                        )
                        w = _all_gather(part, axes, axis=0, tiled=True)
                    else:
                        w = lax.psum(w, axes)
                    parts.append((w / n).astype(spec.dtype))
            return flat_unpack(jnp.concatenate(parts), spec, tree)

    def one(x):
        orig = x.dtype
        # the monolithic exchange is "bucket 0" to the profiler
        with jax.named_scope("exchange_b0"):
            w = x if wire_dtype is None else x.astype(wire_dtype)
            if two_phase and w.shape and w.shape[0] % n == 0:
                # reduce_scatter over leading dim, then all_gather back.
                part = lax.psum_scatter(
                    w, axes, scatter_dimension=0, tiled=True
                )
                w = _all_gather(part, axes, axis=0, tiled=True)
            else:
                w = lax.psum(w, axes)
            return (w / n).astype(orig)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# ZeRO-1: sharded optimizer states over the data axis (Rajbhandari et
# al. 2020).  The reference's asa* strategies were already two-phase
# reduce-scatter + all-gather (the exact ZeRO wire shape) — but then
# kept full replicated optimizer state on every chip.  ZeRO-1 finishes
# the move: update the optimizer on the 1/N gradient shard only and
# all-gather the UPDATED PARAMS instead of the reduced grads, cutting
# per-chip optimizer HBM by ~1/N for the same bytes on the wire.
#
# Pytree leaves are uneven, so the exchange runs over ONE contiguous
# flat buffer: pad-and-concat every leaf (FlatSpec below), shard the
# buffer evenly, unpack after the gather.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FlatSpec:
    """Static layout of a pytree packed into one padded flat buffer.

    Built once at trace time (`flat_spec`); `flat_pack`/`flat_unpack`
    are pure jittable functions over it.  ``padded`` is ``size``
    rounded up so the buffer shards evenly over ``n_shards`` devices.

    ``bucket_len > 0`` additionally tiles the buffer into equal
    buckets of that many elements (each a multiple of ``n_shards``,
    so every bucket reduce-scatters evenly); ``padded`` is then
    rounded up to a whole bucket count.  ``bucket_len == 0`` is the
    monolithic layout.
    """

    treedef: Any = field(repr=False)
    shapes: tuple
    dtypes: tuple
    dtype: Any            # buffer dtype (the optimizer's master width)
    size: int             # live elements
    padded: int           # size rounded up to n_shards (and buckets)
    n_shards: int
    bucket_len: int = 0   # elements per bucket; 0 = monolithic

    @property
    def shard_len(self) -> int:
        return self.padded // self.n_shards

    @property
    def n_buckets(self) -> int:
        return self.padded // self.bucket_len if self.bucket_len else 1

    @property
    def bucket_shard_len(self) -> int:
        """Per-device elements of ONE bucket's reduce-scatter shard."""
        return (self.bucket_len if self.bucket_len else
                self.padded) // self.n_shards


# flat_spec memo: the spec is pure static layout, so rebuilding it per
# trace (the zero1 plain-step, device-cache, and scan paths each
# retrace the step body) is wasted flatten/shape work — and, worse,
# per-compile treedef churn.  Keyed on everything that shapes the
# layout; distinct shard counts / dtypes / bucket sizes miss.
_FLAT_SPEC_CACHE: dict = {}
_FLAT_SPEC_STATS = {"hits": 0, "misses": 0}


def flat_spec_cache_info() -> dict:
    """(hits, misses, size) of the ``flat_spec`` memo — test surface."""
    return dict(_FLAT_SPEC_STATS, size=len(_FLAT_SPEC_CACHE))


def flat_spec_cache_clear() -> None:
    _FLAT_SPEC_CACHE.clear()
    _FLAT_SPEC_STATS.update(hits=0, misses=0)


# HLO-size guard: the bucketed pipeline is an UNROLLED loop (each
# bucket must be its own HLO chain, depending only on its own leaves —
# a lax.scan body would have to dynamic-slice the FULL packed buffer,
# making every iteration depend on every gradient and killing the
# backward overlap that is the point).  Unrolling is linear in bucket
# count, so the count is capped: past the cap the bucket size grows
# instead.  64 buckets is pipeline-depth plenty; it bounds trace and
# compile cost at flagship scale (a 4 GB gradient pack at the 4 MiB
# default would otherwise unroll ~1000 bodies).
MAX_EXCHANGE_BUCKETS = 64


def flat_layout(size: int, n_shards: int,
                bucket_elems: int = 0) -> tuple[int, int]:
    """``(padded, bucket_len)`` of a ``size``-element buffer sharded
    ``n_shards`` ways with target ``bucket_elems`` per bucket — THE
    layout rule, shared by ``flat_spec`` and the models' shard-shaped
    optimizer-state sizing so both always agree.  ``bucket_len == 0``
    means monolithic (requested bucket 0, or one bucket would cover
    the buffer).  The bucket count is capped at
    ``MAX_EXCHANGE_BUCKETS`` by growing the bucket size."""
    padded = -(-size // n_shards) * n_shards
    if bucket_elems <= 0 or not size:
        return padded, 0
    min_elems = -(-size // MAX_EXCHANGE_BUCKETS)
    bucket_len = -(-max(int(bucket_elems), min_elems) // n_shards) * n_shards
    if bucket_len >= padded:
        return padded, 0              # one bucket = the monolithic path
    return -(-size // bucket_len) * bucket_len, bucket_len


def exchange_bucket_count(size: int, n_replicas: int,
                          bucket_elems: int = 0, *,
                          flat: bool = False) -> int:
    """How many ``exchange_b*`` bodies the gradient exchange of a
    ``size``-element tree over ``n_replicas`` traces — the run
    summary's ``exchange_buckets``: 0 when ``allreduce_mean`` meets a
    group of one (nothing is exchanged), 1 for its per-leaf path, the
    bucket count when bucketed.  ``flat``: the zero1 and compressed
    exchanges, which pack at any group size."""
    if n_replicas == 1 and not flat:
        return 0
    padded, bucket_len = flat_layout(size, n_replicas, bucket_elems)
    return padded // bucket_len if bucket_len else 1


def flat_spec(tree: PyTree, n_shards: int, dtype=None,
              *, bucket_elems: int = 0) -> FlatSpec:
    """Layout for packing ``tree`` into one buffer sharded ``n`` ways.

    ``dtype``: buffer dtype; default is the common leaf dtype (fp32
    when leaves disagree — the optimizer master width).

    ``bucket_elems``: target bucket size in ELEMENTS (callers convert
    from ``exchange_bucket_mb``); rounded up to a multiple of
    ``n_shards``.  When one bucket would cover the whole buffer the
    spec degrades to the monolithic layout (``bucket_len == 0``), so
    tiny models never pay bucketing overhead.

    Memoized on (treedef, shapes, dtypes, n_shards, dtype,
    bucket_elems) — see ``flat_spec_cache_info``.
    """
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(jnp.shape(x)) for x in leaves)
    dtypes = tuple(jnp.asarray(x).dtype if not hasattr(x, "dtype")
                   else x.dtype for x in leaves)
    key = (treedef, shapes, dtypes, int(n_shards),
           None if dtype is None else jnp.dtype(dtype),
           int(bucket_elems))
    hit = _FLAT_SPEC_CACHE.get(key)
    if hit is not None:
        _FLAT_SPEC_STATS["hits"] += 1
        return hit
    _FLAT_SPEC_STATS["misses"] += 1
    if dtype is None:
        dtype = dtypes[0] if len(set(dtypes)) == 1 else jnp.float32
    size = sum(math.prod(s) for s in shapes)
    padded, bucket_len = flat_layout(size, n_shards, bucket_elems)
    spec = FlatSpec(
        treedef=treedef, shapes=shapes, dtypes=dtypes,
        dtype=jnp.dtype(dtype), size=size, padded=padded,
        n_shards=n_shards, bucket_len=bucket_len,
    )
    _FLAT_SPEC_CACHE[key] = spec
    return spec


def flat_pack(tree: PyTree, spec: FlatSpec) -> jnp.ndarray:
    """Concat every raveled leaf (+ zero pad) into ``[spec.padded]``."""
    leaves = jax.tree.leaves(tree)
    parts = [jnp.ravel(x).astype(spec.dtype) for x in leaves]
    if spec.padded > spec.size:
        parts.append(jnp.zeros((spec.padded - spec.size,), spec.dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def flat_pack_bucket(tree: PyTree, spec: FlatSpec, i: int) -> jnp.ndarray:
    """Bucket ``i`` of the packed buffer (``[spec.bucket_len]``),
    built ONLY from the leaves overlapping it — so in the lowered HLO
    a bucket's collective depends on just those leaves' producers, and
    the scheduler can dispatch it while later leaves' gradients are
    still being computed (the DDP-bucketing dependence structure)."""
    if spec.bucket_len == 0:
        assert i == 0
        return flat_pack(tree, spec)
    leaves = jax.tree.leaves(tree)
    lo, hi = i * spec.bucket_len, (i + 1) * spec.bucket_len
    parts, off, live = [], 0, 0
    for x, shape in zip(leaves, spec.shapes):
        n = math.prod(shape)
        s, e = max(lo, off), min(hi, off + n)
        if e > s:
            flat = jnp.ravel(x).astype(spec.dtype)
            parts.append(flat if (s == off and e == off + n)
                         else lax.slice_in_dim(flat, s - off, e - off))
            live += e - s
        off += n
    if live < spec.bucket_len:                 # tail bucket: zero pad
        parts.append(jnp.zeros((spec.bucket_len - live,), spec.dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _narrow_vma(x: jnp.ndarray, like) -> jnp.ndarray:
    """Re-type ``x`` to ``like``'s varying axes under a vma-checked
    shard_map.  A leaf that is replicated over some mesh axis (a norm
    weight over ``model``) comes out of the flat buffer typed as
    varying over every axis ANY packed leaf varies over, and could
    not leave the step through its replicated out_spec.  ``pmax`` over
    the surplus axes is the identity on values that are equal across
    them (exact at any axis size, unlike a mean) and types the result
    invariant; over a size-1 axis XLA drops it.  An unchecked
    shard_map carries no vma, so nothing is inserted there."""
    extra = jax.typeof(x).vma - jax.typeof(like).vma
    return lax.pmax(x, tuple(sorted(extra))) if extra else x


def flat_unpack(buf: jnp.ndarray, spec: FlatSpec,
                like: PyTree | None = None) -> PyTree:
    """Inverse of ``flat_pack`` (pad dropped, leaf dtypes restored).
    ``like`` — the tree that was packed — gives every leaf its own
    vma type back (``_narrow_vma``)."""
    out, off = [], 0
    for shape, dt in zip(spec.shapes, spec.dtypes):
        n = math.prod(shape)
        out.append(lax.slice_in_dim(buf, off, off + n).reshape(shape)
                   .astype(dt))
        off += n
    if like is not None:
        out = [
            _narrow_vma(x, l) for x, l in zip(out, jax.tree.leaves(like))
        ]
    return jax.tree_util.tree_unflatten(spec.treedef, out)


# ---------------------------------------------------------------------------
# Low-bit quantized wire with error feedback (QSGD, Alistarh et al.
# 2017; EF-SGD / 1-bit Adam, Karimireddy et al. 2019).  The reference's
# fp16 wire (``asa16``/``nccl16``) halved exchange bytes by a cast;
# int8/fp8 quarters them, but a plain psum of 8-bit values would
# overflow (int8) or drown in rounding (fp8).  So the compressed
# reduce-scatter is an ``all_to_all`` of quantized CHUNKS: each device
# quantizes the chunk destined for each peer with ONE symmetric scale
# per (bucket x shard) chunk, ships 1-byte lanes + a tiny f32 scale
# vector, and the receiver dequantizes and accumulates in f32 — the
# sum is exact over the decoded values, and only 1-byte lanes cross
# the wire.  The quantization error itself is carried as an
# error-feedback residual in worker state and re-injected into the
# NEXT step's gradient instead of being lost, which is what keeps the
# trajectory at fp32-wire quality (the EF-SGD convergence result).
# ---------------------------------------------------------------------------

#: wire codecs: name -> (wire jnp dtype, symmetric qmax the per-chunk
#: scale maps amax onto).  fp8 uses e4m3 (TPU/ml_dtypes native): the
#: per-chunk rescale puts the chunk's amax at 448, so the format's
#: dynamic range is spent on the chunk's actual spread.
WIRE_COMPRESSIONS: dict = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}


def quantize_chunks(chunks: jnp.ndarray, compression: str):
    """Symmetric per-chunk quantization: ``chunks`` ``[C, L]`` float →
    ``(wire [C, L] 1-byte, scales [C] f32)`` with ``scale = amax/qmax``
    per chunk (all-zero chunks get scale 1 so the wire stays 0)."""
    wire_dtype, qmax = WIRE_COMPRESSIONS[compression]
    with jax.named_scope("quantize_wire"):
        c32 = chunks.astype(jnp.float32)
        amax = jnp.max(jnp.abs(c32), axis=1)
        scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
        y = c32 / scale[:, None]
        if compression == "int8":
            wire = jnp.clip(jnp.round(y), -qmax, qmax).astype(wire_dtype)
        else:
            wire = y.astype(wire_dtype)
    return wire, scale


def dequantize_chunks(wire: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_chunks`` → f32 ``[C, L]`` (every receiver
    decodes a chunk to the SAME values the sender's local decode sees —
    the identity the error-feedback residual depends on)."""
    with jax.named_scope("dequantize_wire"):
        return wire.astype(jnp.float32) * scales[:, None]


def _compressed_reduce_scatter(
    buf: jnp.ndarray, axes: tuple, n: int, compression: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized reduce-scatter of ``buf`` ``[len]`` (len % n == 0)
    over ``axes``: returns ``(sum_shard [len//n] f32, decoded [len]
    f32)`` where ``decoded`` is this device's own contribution as every
    receiver decodes it (the EF residual is ``buf - decoded``).

    Wire shape: one ``all_to_all`` of 1-byte chunks (each device sends
    chunk *d* to device *d* — the same (n-1)/n · len bytes a tiled
    ``psum_scatter`` moves, at 1/4 the width) plus an ``all_to_all`` of
    the ``[n]`` f32 scales; the receiver dequantizes each sender's
    chunk with that sender's scale and accumulates in f32, so the
    reduction itself is exact over the decoded values."""
    chunks = buf.astype(jnp.float32).reshape(n, -1)
    wire, scales = quantize_chunks(chunks, compression)
    decoded = dequantize_chunks(wire, scales).reshape(-1)
    wr = lax.all_to_all(wire, axes, split_axis=0, concat_axis=0)
    sr = lax.all_to_all(scales, axes, split_axis=0, concat_axis=0)
    shard = jnp.sum(dequantize_chunks(wr, sr), axis=0)
    return shard, decoded


def _compressed_all_gather(
    shard: jnp.ndarray, axes: tuple, n: int, compression: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized all-gather of ``shard`` ``[L]`` over ``axes``:
    returns ``(full [n*L] f32, decoded [L] f32)``.  ``full`` is built
    from the gathered 1-byte lanes + per-shard scales, so every device
    decodes the IDENTICAL buffer (replica consistency holds bit-for-
    bit); ``decoded`` is this device's own slice for the shard-owner
    EF residual."""
    wire, scales = quantize_chunks(shard[None, :], compression)
    decoded = dequantize_chunks(wire, scales)[0]
    # gathered params/grads are identical on every shard — they
    # re-enter the step invariant (the same rule
    # scatter_update_gather uses for its master-dtype gather)
    wg = _all_gather(wire[0], axes, axis=0, tiled=True)
    sg = _all_gather(scales, axes, axis=0, tiled=True)
    full = dequantize_chunks(wg.reshape(n, -1), sg).reshape(-1)
    return full, decoded


def compressed_allreduce_mean(
    tree: PyTree,
    axis_name: str | tuple[str, ...],
    *,
    compression: str,
    r1: jnp.ndarray | None = None,
    r2: jnp.ndarray | None = None,
    bucket_elems: int = 0,
) -> tuple[PyTree, jnp.ndarray | None, jnp.ndarray | None]:
    """Mean-allreduce with a quantized wire: both phases of the
    two-phase exchange (reduce-scatter of grads, all-gather of the
    reduced shard) ship 1-byte lanes + per-chunk f32 scales — ~4x
    fewer bytes than the fp32 wire, ~2x fewer than bf16.

    ``r1`` — error-feedback residual of the LOCAL gradient compression
    (``[spec.padded]`` f32, per device): added to the packed grads
    before quantization; the new residual (input - decoded) is
    returned.  ``r2`` — shard-owner residual of the reduced-mean
    compression (``[spec.shard_len]`` f32, bucket-major when
    bucketed).  Pass ``None`` to drop errors instead (plain QSGD —
    measurably worse convergence; the knob exists for A/B).

    Composes with ``FlatSpec`` bucketing: with ``bucket_elems`` the
    quantize → all_to_all → decode pipeline runs per bucket, each
    bucket's wire depending only on its own leaves (the same overlap
    dependence structure as the uncompressed bucketed exchange).

    Returns ``(mean_tree, r1_new, r2_new)`` (residuals ``None`` when
    not carried)."""
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    spec = flat_spec(tree, n, bucket_elems=bucket_elems)
    nb = spec.n_buckets
    bl = spec.bucket_len if spec.bucket_len else spec.padded
    bs = spec.bucket_shard_len
    parts, r1_parts, r2_parts = [], [], []
    for i in range(nb):
        # per-bucket profiler scope (obs/profiler.py leg attribution);
        # the nested quantize_wire/dequantize_wire scopes take
        # priority in the profiler's first-match-wins assignment
        with jax.named_scope(f"exchange_b{i}"):
            g = flat_pack_bucket(tree, spec, i).astype(jnp.float32)
            if r1 is not None:
                g = g + lax.slice_in_dim(r1, i * bl, (i + 1) * bl)
            shard_sum, dec1 = _compressed_reduce_scatter(
                g, axes, n, compression
            )
            if r1 is not None:
                r1_parts.append(g - dec1)
            m = shard_sum / n
            if r2 is not None:
                m = m + lax.slice_in_dim(r2, i * bs, (i + 1) * bs)
            full, dec2 = _compressed_all_gather(m, axes, n, compression)
            if r2 is not None:
                r2_parts.append(m - dec2)
            parts.append(full.astype(spec.dtype))
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return (
        flat_unpack(buf, spec, tree),
        jnp.concatenate(r1_parts) if len(r1_parts) > 1 else (
            r1_parts[0] if r1_parts else None),
        jnp.concatenate(r2_parts) if len(r2_parts) > 1 else (
            r2_parts[0] if r2_parts else None),
    )


def _flat_axis_index(axes: tuple) -> jnp.ndarray:
    """This device's flattened index over ``axes`` (first axis major —
    the order `psum_scatter`/`all_gather` tile shards in)."""
    idx = None
    for a in axes:
        i = lax.axis_index(a)
        idx = i if idx is None else idx * lax.axis_size(a) + i
    return idx


def _pvary(x, axes: tuple):
    """Idempotent invariant→varying cast over ``axes``: under a
    vma-checked shard_map the param pack enters dp-INVARIANT and the
    varying-index slice below would be rejected; outside checked mode
    this is an identity."""
    vma = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in vma)
    return lax.pcast(x, need, to="varying") if need else x


def _slice_shard_state(opt_state: Any, spec: FlatSpec, i: int) -> Any:
    """Bucket ``i``'s rows of a shard-shaped optimizer state: flat
    ``[shard_len]`` leaves slice to ``[bucket_shard_len]``; scalar
    leaves (adam's step counter) pass through whole."""
    bs = spec.bucket_shard_len

    def one(x):
        if jnp.ndim(x) and jnp.shape(x)[0] == spec.shard_len:
            return lax.slice_in_dim(x, i * bs, (i + 1) * bs)
        return x

    return jax.tree.map(one, opt_state)


def _concat_shard_state(opt_state: Any, parts: list, spec: FlatSpec) -> Any:
    """Inverse of ``_slice_shard_state``: reassemble per-bucket aux
    states into the full shard layout.  Scalar leaves are identical
    across buckets by construction (each bucket's update computed
    them from the same replicated input) — the first is kept."""
    def one(orig, *xs):
        if jnp.ndim(orig) and jnp.shape(orig)[0] == spec.shard_len:
            return jnp.concatenate(xs)
        return xs[0]

    return jax.tree.map(one, opt_state, *parts)


def scatter_update_gather(
    params: PyTree,
    grads: PyTree,
    opt_update,
    axis_name: str | tuple[str, ...],
    *,
    wire_dtype=None,
    spec: FlatSpec | None = None,
    opt_state: Any = None,
    bucket_elems: int = 0,
    compression: str | None = None,
    r1: jnp.ndarray | None = None,
) -> tuple[PyTree, Any] | tuple[PyTree, Any, jnp.ndarray | None]:
    """ZeRO-1 exchange + update, inside ``shard_map``.

    1. pack ``grads`` into one flat buffer and ``psum_scatter`` it over
       ``axis_name`` — each device ends holding the MEAN of its 1/N
       gradient shard (the reduce-scatter half of the reference's
       ``asa*`` ring);
    2. ``opt_update(param_shard, grad_shard) -> (new_param_shard,
       aux)`` applies the optimizer on that shard only — ``aux``
       (the updated shard-shaped optimizer state) stays sharded;
    3. ``all_gather`` the UPDATED param shards back to the full flat
       buffer (the all-gather half), unpack to the original pytree.

    ``wire_dtype`` casts the gradient buffer for the reduce-scatter
    (the ``*16`` strategies' half-width wire); the param gather rides
    in the master dtype — a bf16 gather would truncate the master
    weights and break equivalence with the allreduce path.

    **Bucketed overlap schedule** (``spec.n_buckets > 1``, built via
    ``flat_spec(..., bucket_elems=...)`` or the ``bucket_elems``
    kwarg): the three phases run as a software pipeline over fixed
    buckets instead of one monolithic tail.  Each bucket's
    reduce-scatter depends only on the leaves it covers (see
    ``flat_pack_bucket``), its optimizer update only on its own
    grad/param/state rows, and its all-gather only on its own updated
    shard — so with async collectives + the latency-hiding scheduler
    (``utils.xla_options.overlap_preset``) bucket *i*'s wire time
    dispatches under bucket *i±1*'s pack/update compute and under the
    tail of the producing backward, instead of serializing after it.
    The math is elementwise-identical to the monolithic path (bucket
    order only permutes the INTERNAL flat layout of the optimizer
    shard; unpacked params are bit-equal).

    ``opt_state``: the (shard-shaped) optimizer state pytree.  When
    given, ``opt_update`` is called as ``opt_update(p_shard, g_shard,
    state)`` and the bucketed path slices the state per bucket — the
    per-bucket update then touches only its rows.  Without it (the
    legacy 2-arg closure), the bucketed path still pipelines both
    collective phases but runs ONE full-shard update between them.

    ``compression`` (``"int8"``/``"fp8"``): the gradient
    reduce-scatter ships quantized 1-byte chunks + per-chunk f32
    scales instead of ``wire_dtype``-cast values (which it then
    supersedes) — see ``compressed_allreduce_mean``.  ``r1`` is the
    per-device error-feedback residual ``[spec.padded]`` (``None``
    drops quantization error).  The param all-gather stays in the
    MASTER dtype: quantizing the updated params would corrupt the
    replicated master weights with no residual to catch it.  With
    compression the return gains the new residual:
    ``(new_params, aux, r1_new)``.

    Returns ``(new_params, aux)`` (plus ``r1_new`` under compression).
    """
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    if spec is None:
        spec = flat_spec(params, n, bucket_elems=bucket_elems)
    assert spec.n_shards == n, (spec.n_shards, n)

    r1_new = None
    if spec.n_buckets == 1:
        # profiler scopes (obs/profiler.py): the collective legs are
        # "bucket 0" of the exchange; the optimizer update is its own
        # leg — both labels registered in analysis/registry.py
        with jax.named_scope("exchange_b0"):
            g_flat = flat_pack(grads, spec)
            if compression is not None:
                g32 = g_flat.astype(jnp.float32)
                if r1 is not None:
                    g32 = g32 + r1
                g_sum, dec = _compressed_reduce_scatter(
                    g32, axes, n, compression
                )
                if r1 is not None:
                    r1_new = g32 - dec
                g_shard = (g_sum / n).astype(spec.dtype)
            else:
                w = (g_flat if wire_dtype is None
                     else g_flat.astype(wire_dtype))
                g_shard = lax.psum_scatter(
                    w, axes, scatter_dimension=0, tiled=True
                )
                g_shard = g_shard.astype(spec.dtype) / n

            p_flat = _pvary(flat_pack(params, spec), axes)
            p_shard = lax.dynamic_slice_in_dim(
                p_flat, _flat_axis_index(axes) * spec.shard_len,
                spec.shard_len,
            )
        with jax.named_scope("opt_update"):
            if opt_state is None:
                new_p_shard, aux = opt_update(p_shard, g_shard)
            else:
                new_p_shard, aux = opt_update(p_shard, g_shard, opt_state)
        with jax.named_scope("exchange_b0"):
            p_new = _all_gather(
                new_p_shard.astype(spec.dtype), axes, axis=0, tiled=True
            )
        if compression is not None:
            return flat_unpack(p_new, spec, params), aux, r1_new
        return flat_unpack(p_new, spec, params), aux

    # -- bucketed pipeline ------------------------------------------------
    nb, bs = spec.n_buckets, spec.bucket_shard_len
    me = _flat_axis_index(axes)

    # phase 1: per-bucket reduce-scatter (each depends only on its own
    # leaves' grads — the scheduler starts bucket 0's wire while the
    # backward still computes later buckets' gradients).  Compressed:
    # the same dependence structure, with a per-bucket quantize →
    # all_to_all → decode in place of the psum_scatter (and the
    # residual sliced per bucket — buckets tile the pack order, so
    # r1's [i*bl:(i+1)*bl] rows ARE bucket i's).
    g_shards, r1_parts = [], []
    bl = spec.bucket_len
    for i in range(nb):
        # per-bucket profiler scope (obs/profiler.py leg attribution)
        with jax.named_scope(f"exchange_b{i}"):
            gb = flat_pack_bucket(grads, spec, i)
            if compression is not None:
                g32 = gb.astype(jnp.float32)
                if r1 is not None:
                    g32 = g32 + lax.slice_in_dim(
                        r1, i * bl, (i + 1) * bl
                    )
                g_sum, dec = _compressed_reduce_scatter(
                    g32, axes, n, compression
                )
                if r1 is not None:
                    r1_parts.append(g32 - dec)
                g_shards.append((g_sum / n).astype(spec.dtype))
            else:
                w = gb if wire_dtype is None else gb.astype(wire_dtype)
                gs = lax.psum_scatter(
                    w, axes, scatter_dimension=0, tiled=True
                )
                g_shards.append(gs.astype(spec.dtype) / n)
    if r1_parts:
        r1_new = jnp.concatenate(r1_parts)

    # phase 2: per-bucket param-shard slice + optimizer update.  The
    # optimizer-shard flat layout becomes bucket-major (bucket i's 1/N
    # rows at [i*bs:(i+1)*bs]) — internal only; unpack restores the
    # original leaf order exactly.
    p_buckets = [
        lax.dynamic_slice_in_dim(
            _pvary(flat_pack_bucket(params, spec, i), axes), me * bs, bs
        )
        for i in range(nb)
    ]
    if opt_state is None:
        # legacy closure: one full-shard update between the pipelined
        # collective phases
        with jax.named_scope("opt_update"):
            new_p, aux = opt_update(
                jnp.concatenate(p_buckets), jnp.concatenate(g_shards)
            )
        new_p_buckets = [
            lax.slice_in_dim(new_p, i * bs, (i + 1) * bs)
            for i in range(nb)
        ]
    else:
        new_p_buckets, aux_parts = [], []
        for i in range(nb):
            with jax.named_scope("opt_update"):
                np_i, aux_i = opt_update(
                    p_buckets[i], g_shards[i],
                    _slice_shard_state(opt_state, spec, i),
                )
            new_p_buckets.append(np_i)
            aux_parts.append(aux_i)
        aux = _concat_shard_state(opt_state, aux_parts, spec)

    # phase 3: per-bucket all-gather of the updated params — bucket
    # i's gather dispatches as soon as ITS update lands, under bucket
    # i+1's update compute
    parts = []
    for i, np_i in enumerate(new_p_buckets):
        with jax.named_scope(f"exchange_b{i}"):
            parts.append(
                _all_gather(np_i.astype(spec.dtype), axes, axis=0, tiled=True)
            )
    if compression is not None:
        return flat_unpack(jnp.concatenate(parts), spec, params), aux, r1_new
    return flat_unpack(jnp.concatenate(parts), spec, params), aux


# ---------------------------------------------------------------------------
# EASGD: elastic averaging (Zhang et al. 2015).  Reference:
# EASGD_Exchanger — server applies w_c += alpha*(w_i - w_c), worker
# applies w_i += alpha*(w_c - w_i), via MPI Sendrecv of param buffers.
# Here both sides of the elastic pair update are one pure function.
# ---------------------------------------------------------------------------

def _tree_pair_map(pair, local: PyTree, center: PyTree) -> tuple[PyTree, PyTree]:
    """Apply ``pair(w, c) -> (w', c')`` leafwise; returns two pytrees."""
    flat_l, treedef = jax.tree.flatten(local)
    flat_c = treedef.flatten_up_to(center)
    out = [pair(a, b) for a, b in zip(flat_l, flat_c)]
    return (
        treedef.unflatten([o[0] for o in out]),
        treedef.unflatten([o[1] for o in out]),
    )


def elastic_pair_update(
    local: PyTree, center: PyTree, alpha: float
) -> tuple[PyTree, PyTree]:
    """One elastic exchange: returns ``(new_local, new_center)``.

    new_local  = w_i - alpha*(w_i - w_c)
    new_center = w_c + alpha*(w_i - w_c)
    """

    def pair(w_i, w_c):
        diff = alpha * (w_i - w_c)
        return w_i - diff, w_c + diff

    return _tree_pair_map(pair, local, center)


def elastic_center_merge(
    locals_stacked: PyTree, center: PyTree, alpha: float
) -> tuple[PyTree, PyTree]:
    """Vectorised EASGD round over a stacked leading worker axis.

    The reference's server serialises exchanges (one Sendrecv per
    worker request); the SPMD adaptation applies each worker's elastic
    pull against the *same* center snapshot, then the center absorbs
    the summed elastic pushes — equivalent to the reference's loop when
    requests land within one cadence window.
    """

    def pair(w, c):
        diff = alpha * (w - c)                      # [workers, ...]
        return w - diff, c + jnp.sum(diff, axis=0)

    return _tree_pair_map(pair, locals_stacked, center)


def elastic_center_merge_masked(
    locals_stacked: PyTree,
    center: PyTree,
    alpha: float,
    mask: jnp.ndarray,
) -> tuple[PyTree, PyTree]:
    """EASGD round where only ``mask``-ed workers exchange.

    ``mask`` — ``[W]`` {0,1} runtime array (no recompile per draw);
    1 = this worker's elastic pair update happens this round, 0 = the
    worker keeps training against a stale center.  This is the
    out-of-step shape of the reference (each worker exchanges when ITS
    OWN local step counter hits tau — workers at different speeds hit
    it at different times; the server serializes whoever shows up,
    which the summed masked pushes reproduce for same-round arrivals).
    """

    def pair(w, c):
        m = mask.astype(jnp.float32).reshape(
            (-1,) + (1,) * (w.ndim - 1)
        ).astype(w.dtype)
        diff = alpha * (w - c) * m
        return w - diff, c + jnp.sum(diff, axis=0)

    return _tree_pair_map(pair, locals_stacked, center)


# ---------------------------------------------------------------------------
# GoSGD: gossip SGD (Blot et al. 2016).  Reference: GOSGD_Worker —
# with prob p, isend (params, score/2) to a random peer and halve own
# score; receiver merges params weighted by scores and adds scores.
# TPU-native: the whole gossip round is one ppermute over the data
# axis, driven by a host-sampled permutation + Bernoulli mask.
# ---------------------------------------------------------------------------

def gossip_push(
    params: PyTree,
    score: jnp.ndarray,
    *,
    axis_name: str,
    perm: list[tuple[int, int]],
    pushing: jnp.ndarray,
) -> tuple[PyTree, jnp.ndarray]:
    """One gossip round inside ``shard_map``.

    ``perm`` is a (src, dst) permutation sampled on host; ``pushing``
    is a per-device {0,1} mask (1 = this device pushes this round).
    A pushing device halves its score and its (params, score/2) travel
    to its ``perm`` destination; the receiver does the score-weighted
    merge.  Non-pushing sources send score 0, making their contribution
    vanish in the merge — so a single ppermute implements the sparse
    randomized push of the reference.
    """
    idx = lax.axis_index(axis_name)
    my_push = pushing[idx].astype(score.dtype)
    sent_score = my_push * score * 0.5              # what travels
    new_score = score - sent_score                   # halved iff pushing

    recv_score = lax.ppermute(sent_score, axis_name, perm)
    recv_params = jax.tree.map(
        lambda x: lax.ppermute(x, axis_name, perm), params
    )

    total = new_score + recv_score

    def merge(mine, theirs):
        w = (new_score * mine + recv_score * theirs) / total
        return w.astype(mine.dtype)

    merged = jax.tree.map(merge, params, recv_params)
    return merged, total


def gossip_matrix_round(
    stacked_params: PyTree,
    scores: jnp.ndarray,
    route: jnp.ndarray,
    push_mask: jnp.ndarray,
) -> tuple[PyTree, jnp.ndarray]:
    """One gossip round over a stacked leading worker axis, with
    *dynamic* peer routing (no recompile per random draw).

    The reference samples a fresh random peer every pushing iteration;
    a ``ppermute`` permutation is a static jit argument, so expressing
    the round that way would recompile per draw.  Instead the push is a
    score-weighted routing matrix ``R[s, d] = onehot(route)[s, d] *
    sent_score[s]`` and delivery is a tiny ``[W, W] x [W, ...]``
    contraction — XLA lowers it to a cross-device reduce over the
    sharded worker axis, and ``route``/``push_mask`` stay runtime
    arrays.

    ``stacked_params`` — pytree with leading axis W (one slot per
    worker); ``scores`` — ``[W]``; ``route`` — ``[W]`` int destination
    worker for each source; ``push_mask`` — ``[W]`` {0,1}, 1 = this
    worker pushes this round.

    Simultaneous deliveries merge in one step: the score-weighted merge
    is linear, so absorbing k senders at once equals the reference's
    sequential queue drain of the same k messages.
    """
    w = scores.shape[0]
    sent = push_mask.astype(scores.dtype) * scores * 0.5
    kept = scores - sent                            # halved iff pushing
    routing = jax.nn.one_hot(route, w, dtype=scores.dtype) * sent[:, None]
    recv_score = jnp.sum(routing, axis=0)           # [W] per destination
    new_scores = kept + recv_score

    def merge(p):
        if not jnp.issubdtype(p.dtype, jnp.floating):
            # integer leaves (e.g. optimizer step counters) can't be
            # weight-averaged; workers advance them in lockstep, so
            # keeping the local value is exact
            return p
        f32 = p.astype(jnp.float32)
        recv = jnp.tensordot(routing, f32, axes=[[0], [0]])  # [W, ...]
        own = kept.reshape((w,) + (1,) * (f32.ndim - 1)) * f32
        tot = new_scores.reshape((w,) + (1,) * (f32.ndim - 1))
        return ((own + recv) / tot).astype(p.dtype)

    return jax.tree.map(merge, stacked_params), new_scores


def gossip_send(
    scores: jnp.ndarray,
    route: jnp.ndarray,
    push_mask: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Send side of a (possibly delayed) gossip round: pushing workers
    halve their score NOW (reference: sender halves at isend time);
    returns ``(new_scores, routing)`` where ``routing[s, d]`` carries
    the in-flight score mass from s to d."""
    w = scores.shape[0]
    sent = push_mask.astype(scores.dtype) * scores * 0.5
    routing = jax.nn.one_hot(route, w, dtype=scores.dtype) * sent[:, None]
    return scores - sent, routing


def gossip_deliver(
    stacked_params: PyTree,
    scores: jnp.ndarray,
    stale_params: PyTree,
    routing: jnp.ndarray,
) -> tuple[PyTree, jnp.ndarray]:
    """Receive side: merge in-flight payloads into the CURRENT replicas.

    ``stale_params`` is the sender-side snapshot taken when ``routing``
    was built (``gossip_send``) — with a staleness delay the payload a
    worker merges is D rounds old, exactly like the reference's
    messages sitting in MPI buffers while both peers kept training.
    """
    w = scores.shape[0]
    recv_score = jnp.sum(routing, axis=0)
    new_scores = scores + recv_score

    def merge(cur, stale):
        if not jnp.issubdtype(cur.dtype, jnp.floating):
            return cur
        f32 = cur.astype(jnp.float32)
        st = stale.astype(jnp.float32)
        recv = jnp.tensordot(routing, st, axes=[[0], [0]])
        own = scores.reshape((w,) + (1,) * (f32.ndim - 1)) * f32
        tot = new_scores.reshape((w,) + (1,) * (f32.ndim - 1))
        return ((own + recv) / tot).astype(cur.dtype)

    return jax.tree.map(merge, stacked_params, stale_params), new_scores


def gossip_merge(
    params_a: PyTree, score_a, params_b: PyTree, score_b
) -> tuple[PyTree, jnp.ndarray]:
    """Score-weighted merge of two models (the receive-side math alone):
    w = (s_a*w_a + s_b*w_b)/(s_a+s_b); s = s_a + s_b."""
    total = score_a + score_b
    merged = jax.tree.map(
        lambda a, b: ((score_a * a + score_b * b) / total).astype(a.dtype),
        params_a,
        params_b,
    )
    return merged, total


# ---------------------------------------------------------------------------
# Debug-mode cross-replica consistency check (new; the reference had no
# race detection — SURVEY §5.2).  Cheap psum-of-norm assert.
# ---------------------------------------------------------------------------

def replica_consistency_delta(tree: PyTree, axis_name: str) -> jnp.ndarray:
    """Max |local - mean| over the tree; 0 everywhere iff replicas agree."""
    mean = allreduce_mean(tree, axis_name)
    deltas = jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))),
        tree,
        mean,
    )
    return jax.tree.reduce(jnp.maximum, deltas, jnp.float32(0))
