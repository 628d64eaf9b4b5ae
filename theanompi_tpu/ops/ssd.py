"""The Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"): a
state-space layer whose recurrence runs as a CHUNKED scan (SSD).

Per head ``h`` (``P`` channels, a state ``[P, N]``), per token::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

``ssd_scan`` cuts the sequence into chunks of ``chunk`` positions and
turns the recurrence into batched products on the matrix unit:

- inside a chunk, ``y_l += sum_{s <= l} (C_l . B_s) exp(a_s+1 + .. +
  a_l) dt_s x_s`` (``a_t = dt_t A <= 0``): ``C B^T`` once a group of
  heads, under a head's DECAY MASK ``exp(cum_l - cum_s)`` (lower
  triangle; a difference of cumulative sums that is never positive,
  so no exponential can overflow), times ``dt x``;
- a chunk's own contribution to the state at its end, ``B^T (dt x
  exp(cum_end - cum_s))``: ``[N, chunk] x [chunk, P]`` a head;
- between chunks the linear recurrence ``S_in[z] = exp(total_{z-1})
  S_in[z-1] + S[z-1]`` over the chunk totals, written as ONE product
  with the ``[chunks, chunks]`` matrix of those decays
  (``_carried_states``), float32 at ``highest`` precision;
- the carried state's part of the output, ``C S_in`` times the decay
  from the chunk's start: ``[chunk, N] x [N, P]`` a head.

Decays, cumulative sums and states are float32; the products'
operands are in the input's dtype and accumulate in float32; inside
the scan every array is head-major, so no product relays an operand.  The
backward is autodiff's of this form (under the layer's remat in
``models/llama.py``, so one layer's decay mask ``[B, chunks, H, chunk,
chunk]`` and its cotangent are alive at a time).  ON A TPU, where the
shapes tile (``scan_kernel_tiles``), the same scan runs in
``ops/ssd_kernel.py``'s two Pallas kernels, which build the mask in
VMEM and carry the state between chunks there, with a backward of
their own; this form stays the path everywhere else and the form
``tests/test_ssd.py`` holds to the recurrence.  ``ssd_reference`` is
the recurrence itself, a ``lax.scan`` over tokens, as
``ops.attention.mha_reference`` stands beside the flash kernels.

``causal_conv_silu`` (the depthwise convolution before the scan),
``gated_rms_norm`` (the norm after it, the gate INSIDE) and
``mamba_mixer`` (the whole mixer between the block's norm and its
residual add, under the scopes ``ssm_proj``, ``ssm_conv``,
``ssd_scan``, ``ssm_gate_norm``) complete the layer;
``mamba_init`` draws its leaves as Mamba-2's own code does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import ssd_kernel
from theanompi_tpu.ops.grouped_matmul import same_vma

F32 = jnp.float32


def causal_conv_silu(x, w, b):
    """``silu(conv1d_causal(x))``, depthwise: ``x [B, T, C]``, ``w [K,
    C]`` (tap ``k`` multiplies position ``t - (K - 1) + k``: the last
    tap is the token itself), ``b [C]``; positions before the
    sequence's start read zero.  Float32 sums, ``x``'s dtype out."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = b.astype(F32)
    for j in range(k):
        y = y + xp[:, j:j + t].astype(F32) * w[j].astype(F32)
    return jax.nn.silu(y).astype(x.dtype)


def gated_rms_norm(y, z, w, eps=1e-5, n_groups=1):
    """``rmsnorm(y * silu(z)) * w`` over the last dimension in
    ``n_groups`` groups (``MambaRMSNormGated`` of the published port:
    the gate goes in BEFORE the statistic), float32 inside."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = g.reshape(*g.shape[:-1], n_groups, -1)
    ms = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    g = (grouped * lax.rsqrt(ms + eps)).reshape(g.shape)
    return g.astype(y.dtype) * w.astype(y.dtype)


def _carried_states(states, total):
    """The state each chunk STARTS from: ``states [..., Z, P, N]`` is
    what each chunk adds by its own end, ``total [..., Z]`` each
    chunk's summed log-decay; ``S_in[z] = sum_{k < z} exp(total_{k+1}
    + .. + total_{z-1}) states[k]``, zero for the first chunk."""
    z = total.shape[-1]
    cum = jnp.cumsum(total, axis=-1)
    before = cum - total                            # up to chunk z - 1
    earlier = jnp.tril(jnp.ones((z, z), bool), -1)
    w = jnp.exp(jnp.where(
        earlier, before[..., :, None] - cum[..., None, :], -jnp.inf
    ))                                              # [..., Z, K]
    return jnp.einsum(
        "...zk,...kpn->...zpn", w, states, precision=lax.Precision.HIGHEST
    )


def scan_kernel_tiles(t, chunk, p, n, heads_a_group, n_heads):
    """The tiles ``ssd_scan`` hands its Pallas kernels for a shape
    (``ssd_kernel.scan_tiles``), or ``None`` where XLA's form runs:
    off the TPU (``ops.attention._on_tpu``: read off a device), or a
    shape the kernels do not take."""
    from theanompi_tpu.ops.attention import _on_tpu

    tiles = ssd_kernel.scan_tiles(t, chunk, p, n, heads_a_group, n_heads)
    return tiles if tiles is not None and _on_tpu() else None


def _scan_on_kernels(x, dt, A, B, C, D, tiles, *, with_stats=False,
                     interpret=False):
    """``ssd_scan`` through ``ssd_kernel.ssd_chunks``, which wants the
    positions on the lanes: ``x`` as ``[B, H P, T]``, ``B`` / ``C`` as
    ``[B, G N, T]``, ``dt`` as ``[B, H, T]`` (where XLA keeps a batch
    of one sequence with the positions minor, these transposes are
    bitcasts).  The cumulative sums a chunk (small, float32) stay
    XLA's."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    ln = tiles.chunk
    dt_rows = dt.astype(F32).transpose(0, 2, 1)             # [B, H, T]
    cum = jnp.cumsum(
        (dt_rows * A.astype(F32)[:, None]).reshape(b, h, t // ln, ln), axis=-1
    )                                                       # [B, H, Z, L]
    y, s_in = ssd_kernel.ssd_chunks(*same_vma(
        x.reshape(b, t, h * p).transpose(0, 2, 1), dt_rows,
        cum.reshape(b, h, t), D.astype(F32),
        B.reshape(b, t, g * n).transpose(0, 2, 1),
        C.reshape(b, t, g * n).transpose(0, 2, 1),
    ), n, tiles, interpret)
    y = y.transpose(0, 2, 1).reshape(x.shape)
    if not with_stats:
        return y
    stats = lax.stop_gradient(jnp.stack([
        jnp.min(cum[..., -1]),
        jnp.sqrt(jnp.mean(jnp.square(s_in[:, -1]))),
    ]))
    return y, stats


def ssd_scan(x, dt, A, B, C, D, chunk, *, with_stats=False):
    """The chunked state-space scan.  ``x [B, T, H, P]``, ``dt [B, T,
    H]`` (positive: after its softplus), ``A [H]`` (negative), ``B``
    and ``C`` ``[B, T, G, N]`` (``G`` groups of ``H / G`` heads share
    them), ``D [H]``; ``chunk`` positions a chunk (a length that is no
    multiple is padded with steps that neither decay nor add).
    Returns ``y [B, T, H, P]`` in ``x``'s dtype; ``with_stats`` also
    ``float32[2]``: the most negative cumulative ``dt A`` inside one
    chunk, and the RMS of the state the LAST chunk starts from (0
    where there is one chunk).

    Inside, every array is HEAD-MAJOR (``[B, G, H / G, chunks, chunk,
    ..]``): the products' batch dimensions lead, so each reads its
    operands and writes its result where they lie, and the one
    transpose in and the one out are of ``x`` and ``y`` in their own
    dtype (with the tokens leading, XLA relaid float32 ``[T, H P]``
    arrays around every product: some thirty 0.65 ms copies a layer
    at the benchmark's sizes; PERF.md section 6, PR 47).

    Which form runs is read off the device and the shapes
    (``scan_kernel_tiles``): the Pallas kernels on a TPU where chunk,
    state and heads tile, the products below everywhere else."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = h // g
    tiles = scan_kernel_tiles(t, chunk, p, n, r, h)
    if tiles is not None:
        return _scan_on_kernels(
            x, dt, A, B, C, D, tiles, with_stats=with_stats)
    cd = x.dtype
    ln = min(int(chunk), t)
    pad = -t % ln
    xs, dts, Bs, Cs = x, dt.astype(F32), B, C
    if pad:
        xs, dts, Bs, Cs = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (xs, dts, Bs, Cs)
        )
    z = (t + pad) // ln
    xh = xs.transpose(0, 2, 1, 3).reshape(b, g, r, z, ln, p)
    dth = dts.transpose(0, 2, 1).reshape(b, g, r, z, ln)
    Bh = Bs.transpose(0, 2, 1, 3).reshape(b, g, z, ln, n)
    Ch = Cs.transpose(0, 2, 1, 3).reshape(b, g, z, ln, n)
    a = dth * A.astype(F32).reshape(g, r, 1, 1)
    cum = jnp.cumsum(a, axis=-1)                    # [B,G,R,Z,L], <= 0
    total = cum[..., -1]                            # [B,G,R,Z]
    xdt = xh.astype(F32) * dth[..., None]           # [B,G,R,Z,L,P]

    # inside a chunk: C B^T under the decay mask, times dt x
    scores = jnp.einsum(
        "bgzln,bgzsn->bgzls", Ch, Bh, preferred_element_type=F32
    )
    seen = jnp.tril(jnp.ones((ln, ln), bool))
    decay = jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], -jnp.inf
    ))                                              # [B,G,R,Z,L,S]
    y = jnp.einsum(
        "bgrzls,bgrzsp->bgrzlp", (scores[:, :, None] * decay).astype(cd),
        xdt.astype(cd), preferred_element_type=F32,
    )

    # what each chunk adds to the state by its end, and the carry
    to_end = jnp.exp(total[..., None] - cum)        # [B,G,R,Z,L]
    states = jnp.einsum(
        "bgzsn,bgrzsp->bgrzpn", Bh, (xdt * to_end[..., None]).astype(cd),
        preferred_element_type=F32,
    )
    s_in = _carried_states(states, total)           # [B,G,R,Z,P,N]
    y = y + jnp.einsum(
        "bgzln,bgrzpn->bgrzlp", Ch, s_in.astype(cd),
        preferred_element_type=F32,
    ) * jnp.exp(cum)[..., None]

    y = y + D.astype(F32).reshape(g, r, 1, 1, 1) * xh.astype(F32)
    y = y.astype(cd).reshape(b, h, z * ln, p).transpose(0, 2, 1, 3)[:, :t]
    if not with_stats:
        return y
    stats = lax.stop_gradient(jnp.stack([
        jnp.min(total),
        jnp.sqrt(jnp.mean(jnp.square(s_in[..., -1, :, :]))),
    ]))
    return y, stats


def ssd_reference(x, dt, A, B, C, D):
    """The recurrence itself, a token at a time in float32 (a
    ``lax.scan`` over ``T``): what ``ssd_scan`` has to equal."""
    b, t, h, p = x.shape
    g = B.shape[2]
    rep = h // g
    Bh = jnp.repeat(B.astype(F32), rep, axis=2)     # [B, T, H, N]
    Ch = jnp.repeat(C.astype(F32), rep, axis=2)
    xf, dtf = x.astype(F32), dt.astype(F32)

    def step(s, inp):
        xt, dtt, bt, ct = inp                       # [B,H,P] [B,H] [B,H,N]
        s = (
            jnp.exp(dtt * A.astype(F32))[..., None, None] * s
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        )
        return s, jnp.einsum(
            "bhpn,bhn->bhp", s, ct, precision=lax.Precision.HIGHEST
        )

    s0 = jnp.zeros((b, h, p, B.shape[3]), F32)
    _, ys = lax.scan(step, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (xf, dtf, Bh, Ch)
    ))
    y = jnp.moveaxis(ys, 0, 1) + D.astype(F32)[:, None] * xf
    return y.astype(x.dtype)


def mamba_sizes(*, n_heads, head_dim, d_state, n_groups):
    """``(inner, conv, proj)`` widths of a mixer: the scan's channels,
    what the convolution sees (``x | B | C``) and the input
    projection's columns (``z | xBC | dt``)."""
    inner = n_heads * head_dim
    conv = inner + 2 * n_groups * d_state
    return inner, conv, inner + conv + n_heads


def mamba_init(key, dim, *, d_conv, dense, **sizes):
    """A mixer's leaves (``sizes``: ``mamba_sizes``' arguments).  ``ssm_in`` / ``ssm_out`` as ``dense`` draws
    every matrix; the convolution as a ``Conv1d`` of fan-in ``d_conv``
    (uniform in ``+-1 / sqrt(d_conv)``, weight and bias); ``A ~ U[1,
    16]``, ``dt`` log-uniform in ``[1e-3, 1e-1]`` with ``dt_bias`` its
    inverse softplus, ``D = 1``, the gated norm 1: Mamba-2's own
    initial values, so that the decays of a fresh model neither vanish
    within a token nor stay at one."""
    inner, conv, proj = mamba_sizes(**sizes)
    n_heads = sizes["n_heads"]
    k_in, k_out, k_w, k_b, k_a, k_dt = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(d_conv)
    dt = jnp.exp(
        jax.random.uniform(k_dt, (n_heads,), F32)
        * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    return {
        "ssm_in": dense(k_in, (dim, proj)),
        "ssm_conv_w": jax.random.uniform(
            k_w, (d_conv, conv), F32, -bound, bound),
        "ssm_conv_b": jax.random.uniform(k_b, (conv,), F32, -bound, bound),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_a_log": jnp.log(jax.random.uniform(
            k_a, (n_heads,), F32, 1.0, 16.0)),
        "ssm_d": jnp.ones((n_heads,), F32),
        "ssm_norm": jnp.ones((inner,), F32),
        "ssm_out": dense(k_out, (inner, dim)),
    }


def mamba_mixer(p, xn, *, n_heads, head_dim, d_state, n_groups, chunk,
                eps=1e-5):
    """The mixer on a block's normed input ``xn [B, T, D]``: ``[z |
    xBC | dt] = xn W_in`` (three products over column cuts of the ONE
    weight, so no activation is sliced; ``dt``'s leaves in float32),
    the causal convolution with SiLU over ``xBC``, ``[x | B | C]``
    from it, ``dt = softplus(dt + dt_bias)``, the scan, the gated norm
    and ``W_out``.  Returns ``(out [B, T, D], float32[2])``: the
    scan's two counters (``ssd_scan``)."""
    inner, conv, _ = mamba_sizes(
        n_heads=n_heads, head_dim=head_dim, d_state=d_state,
        n_groups=n_groups)
    b, t, _ = xn.shape
    w_in = p["ssm_in"]
    with jax.named_scope("ssm_proj"):
        z = xn @ w_in[:, :inner].astype(xn.dtype)
        xbc = xn @ w_in[:, inner:inner + conv].astype(xn.dtype)
        dt = jnp.matmul(
            xn, w_in[:, inner + conv:].astype(xn.dtype),
            preferred_element_type=F32,
        )
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv_silu(xbc, p["ssm_conv_w"], p["ssm_conv_b"])
    with jax.named_scope("ssd_scan"):
        dt = jax.nn.softplus(dt + p["ssm_dt_bias"].astype(F32))
        gn = n_groups * d_state
        y, stats = ssd_scan(
            xbc[..., :inner].reshape(b, t, n_heads, head_dim), dt,
            -jnp.exp(p["ssm_a_log"].astype(F32)),
            xbc[..., inner:inner + gn].reshape(b, t, n_groups, d_state),
            xbc[..., inner + gn:].reshape(b, t, n_groups, d_state),
            p["ssm_d"], chunk, with_stats=True,
        )
    with jax.named_scope("ssm_gate_norm"):
        y = gated_rms_norm(
            y.reshape(b, t, inner), z, p["ssm_norm"], eps, n_groups
        )
    with jax.named_scope("ssm_proj"):
        return y @ p["ssm_out"].astype(y.dtype), stats
