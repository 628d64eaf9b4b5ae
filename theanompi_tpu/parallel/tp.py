"""Tensor-parallel building blocks over the ``model`` mesh axis
(new-framework scope — SURVEY §2.2 row "Tensor parallel": absent
upstream, required for the Llama-class configs).

Megatron-style decomposition expressed as pure functions inside
``shard_map``: column-parallel matmuls need no communication (the
activation picks up a sharded feature dim), row-parallel matmuls end
in one ``psum`` over the model axis — which XLA lowers onto ICI.  The
vocab dimension (embedding table + LM head + softmax loss) is sharded
the same way, with the masked-gather / global-logsumexp tricks that
keep the full [B, T, V] logits from ever materializing on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

PyTree = jax.typing.ArrayLike | dict | list | tuple


# -- sharded matmuls --------------------------------------------------------

def col_parallel(x, w, axis_name: str = MODEL_AXIS):
    """[..., D] x [D, F/tp] -> [..., F/tp]; no comm (output sharded)."""
    del axis_name
    return x @ w.astype(x.dtype)


def col_parallel_heads(x, w, n_heads: int):
    """``col_parallel`` for an attention operand: ``x [B, T, D]`` times
    the ``n_heads`` local heads' columns ``w [D, n_heads * hd]`` (or
    ``[D, n_heads, hd]``) -> ``[B, n_heads, T, hd]``, the layout the
    attention kernels take, WITHOUT a head transpose in either
    direction.  Forward: exactly ``(x @ w).reshape(B, T, n_heads,
    hd).transpose(0, 2, 1, 3)``, which XLA:TPU lowers to one product
    that writes ``[B, n_heads, T, hd]``.  Backward: autodiff's two
    products of that expression, the same values in the same dtype,
    written as einsums over ``dy [B, n_heads, T, hd]``; autodiff's own
    form (``dy`` transposed and flattened to ``[B, T, n_heads * hd]``
    first) makes XLA relay every ``dy`` through a copy: 0.4 ms for
    each of q, k and v a block at ``[2, 20, 8192, 256]`` (PERF.md §6,
    PR 38)."""
    return _heads_product(
        x, w.reshape(w.shape[0], n_heads, -1).astype(x.dtype)
    )


@jax.custom_vjp
def _heads_product(x, w):
    b, t, _ = x.shape
    r, h, d = w.shape
    y = (x @ w.reshape(r, h * d)).reshape(b, t, h, d)
    return y.transpose(0, 2, 1, 3)


def _heads_product_fwd(x, w):
    return _heads_product(x, w), (x, w)


def _heads_product_bwd(res, dy):
    x, w = res
    dx = jnp.einsum("bhtd,rhd->btr", dy, w)
    dw = jnp.einsum("btr,bhtd->rhd", x, dy)
    return _reduce_ct_to_primal(dx, x), _reduce_ct_to_primal(dw, w)


_heads_product.defvjp(_heads_product_fwd, _heads_product_bwd)


def row_parallel(x, w, axis_name: str = MODEL_AXIS):
    """[..., F/tp] x [F/tp, D] -> [..., D] via partial matmul + psum."""
    return lax.psum(x @ w.astype(x.dtype), axis_name)


# -- vocab-sharded embedding ------------------------------------------------

def vocab_shard_info(vocab: int, axis_name: str = MODEL_AXIS):
    tp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    v_loc = vocab // tp
    return v_loc, idx * v_loc


def embed_lookup(ids, table, vocab: int, axis_name: str = MODEL_AXIS):
    """Row-sharded embedding: each shard owns ids [off, off+V/tp);
    misses contribute zeros and one psum assembles full vectors."""
    v_loc, off = vocab_shard_info(vocab, axis_name)
    local = ids - off
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    vecs = table[safe] * hit[..., None].astype(table.dtype)
    return lax.psum(vecs, axis_name)


# -- vocab-sharded softmax cross-entropy ------------------------------------

def sharded_softmax_xent(
    logits_loc, labels, vocab: int, axis_name: str = MODEL_AXIS
):
    """Mean CE over tokens with the vocab dim sharded.

    logits_loc: [..., V/tp] local shard (f32 recommended);
    labels: [...] int32 global ids.  Never materializes full logits:
    global logsumexp = max-psum + sum-psum, target logit = masked
    gather + psum.
    """
    v_loc, off = vocab_shard_info(vocab, axis_name)
    x = logits_loc.astype(jnp.float32)

    # stability shift only — constant wrt the gradient (pmax has no
    # JVP rule, so it must see a zero-tangent operand); d(lse)/dx is
    # still the softmax
    m = lax.pmax(lax.stop_gradient(jnp.max(x, axis=-1)), axis_name)
    lse = m + jnp.log(
        lax.psum(jnp.sum(jnp.exp(x - m[..., None]), axis=-1), axis_name)
    )

    local = labels - off
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    tgt = jnp.take_along_axis(x, safe[..., None], axis=-1)[..., 0]
    tgt = lax.psum(jnp.where(hit, tgt, 0.0), axis_name)
    return jnp.mean(lse - tgt)


def sharded_argmax(score_loc, vocab: int, axis_name: str = MODEL_AXIS):
    """Global argmax over a vocab-sharded score row [..., V/tp] via
    the (value, id) max-reduction trick.  Ties break to the LOWEST
    global id — both across shards (pmin over tying shards) and
    within a shard (jnp.argmax returns the first maximum) — so the
    result is deterministic and layout-invariant: tp=1 and tp=8 pick
    the same token for the same global score row."""
    v_loc, off = vocab_shard_info(vocab, axis_name)
    loc_max = jnp.max(score_loc, axis=-1)
    loc_arg = jnp.argmax(score_loc, axis=-1).astype(jnp.int32) + off
    gmax = lax.pmax(loc_max, axis_name)
    cand = jnp.where(loc_max >= gmax, loc_arg, vocab)
    return lax.pmin(cand, axis_name)


def sharded_sample(logits_loc, vocab: int, keys, temperature,
                   axis_name: str = MODEL_AXIS):
    """One token id per row from vocab-sharded logits [N, V/tp].

    ``temperature <= 0`` rows decode greedily (pure argmax, lowest-id
    tie-break); positive rows sample via the Gumbel-max trick:
    ``argmax(logits/T + g)`` with ``g ~ Gumbel(0,1)`` is an exact
    draw from ``softmax(logits/T)``.  The Gumbel noise is drawn for
    the FULL vocab from each row's key and sliced to the local
    columns, so the perturbed scores — and therefore the sampled
    ids — are bitwise layout-invariant across tp meshes (the
    serving determinism contract; tests/test_serving.py).

    ``keys``: [N, 2] uint32 PRNG keys, one per row (already folded
    with the row's position — the caller owns the fold policy).
    Returns [N] int32 global token ids.

    Higher-rank inputs ([..., V/tp] logits with [..., 2] keys and
    [...] temperatures) flatten to rows, sample, and reshape back:
    every row is sampled exactly as in a flat batch.  The decoder's
    speculative verify step pre-flattens its [S, k] rows itself
    (``_verify_body``) — this branch keeps the PUBLIC sampler
    contract honest for multi-row callers that don't, with the
    flat-vs-shaped bitwise equality under test.
    """
    lead = logits_loc.shape[:-1]
    if len(lead) > 1:
        flat = sharded_sample(
            logits_loc.reshape(-1, logits_loc.shape[-1]), vocab,
            keys.reshape(-1, keys.shape[-1]),
            temperature.reshape(-1), axis_name,
        )
        return flat.reshape(lead)
    v_loc, off = vocab_shard_info(vocab, axis_name)
    x = logits_loc.astype(jnp.float32)
    g = jax.vmap(
        lambda k: jax.random.gumbel(k, (vocab,), jnp.float32)
    )(keys)
    g_loc = lax.dynamic_slice(g, (0, off), (g.shape[0], v_loc))
    t = jnp.maximum(temperature, 1e-6)[:, None]
    score = jnp.where(temperature[:, None] > 0.0, x / t + g_loc, x)
    return sharded_argmax(score, vocab, axis_name)


def sharded_top1_err(logits_loc, labels, vocab: int,
                     axis_name: str = MODEL_AXIS):
    """Top-1 error with sharded vocab: global argmax via
    ``sharded_argmax``."""
    # metrics carry no gradient; keeps pmax/pmin off the JVP path
    x = lax.stop_gradient(logits_loc).astype(jnp.float32)
    pred = sharded_argmax(x, vocab, axis_name)
    return jnp.mean((pred != labels).astype(jnp.float32))


def sharded_topk_err(logits_loc, labels, vocab: int, k: int = 5,
                     axis_name: str = MODEL_AXIS):
    """Top-k error with sharded vocab: local top-k candidates,
    all_gather the (tp*k_loc) candidates, global top-k among them.

    Exact even when a shard holds fewer than ``k`` entries: any global
    top-k element is in its own shard's local top-min(k, v_loc), so the
    gathered candidate set always contains the true top-k.
    """
    v_loc, off = vocab_shard_info(vocab, axis_name)
    k_loc = min(k, v_loc)
    x = lax.stop_gradient(logits_loc).astype(jnp.float32)
    vals, ids = lax.top_k(x, k_loc)                               # [..., k_loc]
    ids = ids + off
    all_vals = lax.all_gather(vals, axis_name, axis=-1, tiled=True)
    all_ids = lax.all_gather(ids, axis_name, axis=-1, tiled=True)
    k_eff = min(k, all_vals.shape[-1])
    _, sel = lax.top_k(all_vals, k_eff)
    top_ids = jnp.take_along_axis(all_ids, sel, axis=-1)
    hit = jnp.any(top_ids == labels[..., None], axis=-1)
    return jnp.mean(1.0 - hit.astype(jnp.float32))


# -- chunked (logits-free) unembed + cross-entropy --------------------------

def pick_xent_chunks(v_loc: int, target: int = 4096) -> int:
    """Largest chunk count with ~``target``-wide chunks that divides
    the local vocab; 1 = chunking off (small vocab)."""
    if v_loc <= 2 * target:
        return 1
    for c in range(v_loc // target, 1, -1):
        if v_loc % c == 0:
            return c
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def chunked_unembed_xent(x2, w, labels, vocab, n_chunks, axis_name):
    """Fused LM head + softmax cross-entropy that NEVER materializes
    the [N, V] logits (profiled on v5e, 8L/1024d proxy: the dense
    head wrote ~1.5 GB/step of fp32+bf16 logits copies — ~8% of the
    step — and autodiff's dW ran as an fp32 MXU matmul at 1/8 rate).

    Streams vocab CHUNKS through an online-softmax recurrence (the
    flash-attention trick applied to the classifier head):
    per chunk, logits = x2 @ w[:, c] live only at [N, V_c]; the carry
    holds running (max, sumexp, target-logit, argmax).  The manual
    backward recomputes each chunk's logits and feeds the dW matmul
    bf16 operands (fp32 accumulate), like every other grad matmul in
    the model.

    x2: [N, D] tokens (compute dtype), w: [D, V_loc] (fp32 master),
    labels: [N] GLOBAL int ids.  Works under tensor parallelism: w
    holds this shard's V/tp columns and the global combine is one
    pmax+psum over ``axis_name`` (no-ops at tp=1).
    Returns (loss_vec [N] fp32 = lse - target, pred [N] int32).
    """
    out, _ = _chunked_head_fwd_impl(
        x2, w, labels, vocab, n_chunks, axis_name
    )
    return out


def _carry_vma(*refs):
    """Union of the refs' varying-manual-axes: scan carries must
    enter with the SAME vma the body produces (check_vma=True rejects
    an invariant init whose output is data/seq-varying)."""
    axes = set()
    for r in refs:
        axes |= set(getattr(jax.typeof(r), "vma", ()) or ())
    return tuple(sorted(axes))


def _vary(a, axes):
    return lax.pcast(a, axes, to="varying") if axes else a


def _chunk_logits(x2, w, c, n_chunks):
    d, v_loc = w.shape
    vc = v_loc // n_chunks
    wc = lax.dynamic_slice(w, (0, c * vc), (d, vc))
    return (x2 @ wc.astype(x2.dtype)).astype(jnp.float32), wc, vc


def _chunked_head_fwd_impl(x2, w, labels, vocab, n_chunks, axis_name):
    n = x2.shape[0]
    v_loc = w.shape[1]
    off = vocab_shard_info(vocab, axis_name)[1] if axis_name else 0

    def body(carry, c):
        m, s, tgt, bv, bi = carry
        lg, _, vc = _chunk_logits(x2, w, c, n_chunks)
        mc = jnp.max(lg, axis=-1)
        m_new = jnp.maximum(m, mc)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lg - m_new[:, None]), axis=-1
        )
        local = labels - (off + c * vc)
        hit = (local >= 0) & (local < vc)
        safe = jnp.clip(local, 0, vc - 1)
        t = jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0]
        tgt = tgt + jnp.where(hit, t, 0.0)
        # running argmax: strict > keeps the EARLIEST max, matching
        # argmax over the full row
        cb = jnp.argmax(lg, axis=-1) + (off + c * vc)
        better = mc > bv
        bv = jnp.where(better, mc, bv)
        bi = jnp.where(better, cb, bi)
        return (m_new, s, tgt, bv, bi), None

    vma = _carry_vma(x2, w, labels)
    init = (
        _vary(jnp.full((n,), -jnp.inf, jnp.float32), vma),
        _vary(jnp.zeros((n,), jnp.float32), vma),
        _vary(jnp.zeros((n,), jnp.float32), vma),
        _vary(jnp.full((n,), -jnp.inf, jnp.float32), vma),
        _vary(jnp.full((n,), vocab, jnp.int32), vma),
    )
    (m, s, tgt, bv, bi), _ = lax.scan(
        body, init, jnp.arange(n_chunks), unroll=False
    )
    if axis_name:
        gm = lax.pmax(m, axis_name)
        s = lax.psum(s * jnp.exp(m - gm), axis_name)
        lse = gm + jnp.log(jnp.maximum(s, 1e-30))
        tgt = lax.psum(tgt, axis_name)
        gbv = lax.pmax(bv, axis_name)
        pred = lax.pmin(jnp.where(bv >= gbv, bi, vocab), axis_name)
    else:
        lse = m + jnp.log(jnp.maximum(s, 1e-30))
        pred = bi
    loss_vec = lse - tgt
    return (loss_vec, pred), (x2, w, labels, lse)


def _chunked_head_fwd(x2, w, labels, vocab, n_chunks, axis_name):
    return _chunked_head_fwd_impl(x2, w, labels, vocab, n_chunks, axis_name)


def _chunked_head_bwd(vocab, n_chunks, axis_name, res, cts):
    g, _ = cts                       # dpred: int output, no gradient
    x2, w, labels, lse = res
    off = vocab_shard_info(vocab, axis_name)[1] if axis_name else 0
    d = w.shape[0]
    n = x2.shape[0]
    gf = g.astype(jnp.float32)

    def body(carry, c):
        dx, dw = carry
        lg, wc, vc = _chunk_logits(x2, w, c, n_chunks)
        p = jnp.exp(lg - lse[:, None])
        local = labels - (off + c * vc)
        hit = (local >= 0) & (local < vc)
        safe = jnp.clip(local, 0, vc - 1)
        onehot = (
            (jnp.arange(vc)[None, :] == safe[:, None]) & hit[:, None]
        )
        dlg = (p - onehot.astype(jnp.float32)) * gf[:, None]
        # bf16 operands, fp32 accumulate — the same wire every other
        # grad matmul in the model uses (autodiff's fp32 logits made
        # this dW an fp32 MXU matmul: 1/8 rate, profiled)
        dlgc = dlg.astype(x2.dtype)
        dwc = lax.dot_general(
            x2, dlgc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [D, Vc]
        dx = dx + lax.dot_general(
            dlgc, wc.astype(x2.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [N, D]
        dw = lax.dynamic_update_slice(dw, dwc, (0, c * (w.shape[1] // n_chunks)))
        return (dx, dw), None

    vma = _carry_vma(x2, w, labels, g)
    (dx, dw), _ = lax.scan(
        body,
        (_vary(jnp.zeros((n, d), jnp.float32), vma),
         _vary(jnp.zeros(w.shape, jnp.float32), vma)),
        jnp.arange(n_chunks),
    )
    dx = _reduce_ct_to_primal(dx, x2)
    dw = _reduce_ct_to_primal(dw, w)
    return dx.astype(x2.dtype), dw.astype(w.dtype), None


chunked_unembed_xent.defvjp(_chunked_head_fwd, _chunked_head_bwd)


# -- dense unembed + xent with bf16 grad matmuls ----------------------------

def _reduce_ct_to_primal(ct, primal):
    """psum a cotangent down to its primal's vma — the reductions
    autodiff's broadcast-transposes would insert (a cotangent computed
    from axis-varying operands is a per-shard PARTIAL wherever the
    primal is invariant)."""
    have = set(getattr(jax.typeof(ct), "vma", ()) or ())
    want = set(getattr(jax.typeof(primal), "vma", ()) or ())
    extra = tuple(sorted(have - want))
    return lax.psum(ct, extra) if extra else ct


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def dense_unembed_xent(x2, w, labels, vocab, axis_name):
    """Fused LM head + softmax cross-entropy, logits MATERIALIZED ONCE
    in compute dtype and saved for the backward.

    Why not plain autodiff: the xent reductions upcast logits to fp32,
    so autodiff hands the two big backward matmuls (dW = x2^T dlogits,
    dx = dlogits w^T) an fp32 operand — profiled on v5e as the lm_head
    dW running at ~52% of the MXU (fused with the Adam update,
    ``divide_subtract_fusion``).  The manual backward computes the
    softmax from the SAVED bf16 logits (no recompute — the chunked
    variant's extra head matmul is what made it lose) and casts
    dlogits to compute dtype before both matmuls, fp32 accumulation,
    like every other grad matmul in the model.

    Same signature/returns/sharding semantics as
    ``chunked_unembed_xent`` (which remains the MEMORY-bound variant
    for >=64k local vocab, where saving [N, V] logits is the problem).
    """
    out, _ = _dense_head_fwd_impl(x2, w, labels, vocab, axis_name)
    return out


def _dense_head_fwd_impl(x2, w, labels, vocab, axis_name):
    v_loc = w.shape[1]
    off = vocab_shard_info(vocab, axis_name)[1] if axis_name else 0
    lg = x2 @ w.astype(x2.dtype)                    # [N, V_loc], bf16
    lgf = lg.astype(jnp.float32)
    m = jnp.max(lgf, axis=-1)
    local = labels - off
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    tgt = jnp.take_along_axis(lgf, safe[:, None], axis=-1)[:, 0]
    tgt = jnp.where(hit, tgt, 0.0)
    bi = jnp.argmax(lgf, axis=-1) + off
    if axis_name:
        gm = lax.pmax(m, axis_name)
        s = lax.psum(
            jnp.sum(jnp.exp(lgf - gm[:, None]), axis=-1), axis_name
        )
        lse = gm + jnp.log(jnp.maximum(s, 1e-30))
        tgt = lax.psum(tgt, axis_name)
        # gm doubles as the global best for the argmax tie-break
        pred = lax.pmin(jnp.where(m >= gm, bi, vocab), axis_name)
    else:
        s = jnp.sum(jnp.exp(lgf - m[:, None]), axis=-1)
        lse = m + jnp.log(jnp.maximum(s, 1e-30))
        pred = bi
    loss_vec = lse - tgt
    return (loss_vec, pred), (x2, w, labels, lg, lse)


def _dense_head_fwd(x2, w, labels, vocab, axis_name):
    return _dense_head_fwd_impl(x2, w, labels, vocab, axis_name)


def _dense_head_bwd(vocab, axis_name, res, cts):
    g, _ = cts                       # dpred: int output, no gradient
    x2, w, labels, lg, lse = res
    v_loc = w.shape[1]
    off = vocab_shard_info(vocab, axis_name)[1] if axis_name else 0
    p = jnp.exp(lg.astype(jnp.float32) - lse[:, None])
    local = labels - off
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    onehot = (jnp.arange(v_loc)[None, :] == safe[:, None]) & hit[:, None]
    dlg = (p - onehot.astype(jnp.float32)) * g.astype(jnp.float32)[:, None]
    dlgc = dlg.astype(x2.dtype)                     # bf16 wire
    dw = lax.dot_general(
        x2, dlgc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # [D, V_loc]
    dx = lax.dot_general(
        dlgc, w.astype(x2.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # [N, D]
    dx = _reduce_ct_to_primal(dx, x2)
    dw = _reduce_ct_to_primal(dw, w)
    return dx.astype(x2.dtype), dw.astype(w.dtype), None


dense_unembed_xent.defvjp(_dense_head_fwd, _dense_head_bwd)


# -- the R exits of a looped decoder through one dense head ----------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def exits_unembed_xent(xs, w, labels, row_w, vocab, axis_name):
    """The dense head over ALL R exits of a looped decoder, its
    gradients computed in its FORWARD pass.

    ``dense_unembed_xent`` keeps its [N, V] logits for the backward;
    R exits through it keep R sets, or replay each exit's logits under
    a remat (a fourth head product an exit; PERF.md, PR 34).  The exit
    weights are known before any head runs, so a head that is GIVEN
    its row weights forms ``dlogits = (softmax - onehot) * row_w``
    while an exit's logits are alive and runs both gradient products
    there and then: three products an exit, one exit's [N, V] logits
    and ``dlogits`` alive at a time (one loop body), none between
    forward and backward.  What crosses to the backward is ``dx``
    [R, N, D], one fp32 ``dW`` summed over the exits and ``xent``;
    the backward scales them by the scalar cotangent.

    xs: [R, N, D] exits (compute dtype), w: [D, V_loc] (fp32 master,
    cast to compute dtype once a call), labels: [N] GLOBAL int ids
    that every exit is held to, or [R, N], a row of them an exit (a
    multi-token-prediction module's exit is held to the token after
    next), row_w: [R, N] fp32.  Statistics, operands, dtypes, accumulation
    and sharding semantics of ``dense_unembed_xent``, ``row_w[t]``
    where its loss vector's cotangent stood.
    Returns (total = sum(row_w * xent), xent [R, N] fp32, pred [R, N]
    int32).  Only ``total`` carries a gradient (to xs, w and row_w):
    ``xent`` and ``pred`` are outputs WITHOUT one — read them under
    ``stop_gradient``.  Undifferentiated, no gradient product runs.
    """
    xent, pred, _, _ = _exits_head_loop(
        xs, w, labels, row_w, vocab, axis_name, with_grads=False
    )
    return jnp.sum(row_w * xent), xent, pred


def _exits_head_loop(xs, w, labels, row_w, vocab, axis_name, with_grads):
    """(xent [R, N], pred [R, N], dx, dW): the gradients of
    ``sum(row_w * xent)`` — dx [R, N, D] in xs' dtype, reduced to xs'
    vma; dW [D, V_loc] fp32, this shard's partial — or None, None."""
    v_loc = w.shape[1]
    off = vocab_shard_info(vocab, axis_name)[1] if axis_name else 0
    wc = w.astype(xs.dtype)                 # R (3 R) products read it
    local = labels - off
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    own_labels = labels.ndim == 2       # [R, N]: a row of them an exit
    exit_labels = (hit, safe)

    def body(dw, exit_):
        if own_labels:
            x2, rw, hit, safe = exit_
        else:
            x2, rw = exit_
            hit, safe = exit_labels
        lg = x2 @ wc                                # [N, V_loc], bf16
        # _dense_head_fwd_impl's statistics and values, each read
        # from the compute-dtype logits themselves: its fp32 copy of
        # them (1.6 GB at [8192, 49152], written for the target's
        # gather alone) is here only ever a reduction's operand
        m = jnp.max(lg, axis=-1).astype(jnp.float32)
        tgt = jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0]
        tgt = jnp.where(hit, tgt.astype(jnp.float32), 0.0)
        pred = jnp.argmax(lg, axis=-1) + off
        if axis_name:
            gm = lax.pmax(m, axis_name)
            tgt = lax.psum(tgt, axis_name)
            pred = lax.pmin(jnp.where(m >= gm, pred, vocab), axis_name)
        else:
            gm = m
        s = jnp.sum(jnp.exp(lg.astype(jnp.float32) - gm[:, None]), axis=-1)
        if axis_name:
            s = lax.psum(s, axis_name)
        lse = gm + jnp.log(jnp.maximum(s, 1e-30))
        xent = lse - tgt
        if not with_grads:
            return None, (xent, pred, None)
        # _dense_head_bwd's expression, the row weight for a cotangent
        p = jnp.exp(lg.astype(jnp.float32) - lse[:, None])
        onehot = (jnp.arange(v_loc)[None, :] == safe[:, None]) & hit[:, None]
        dlgc = (
            (p - onehot.astype(jnp.float32)) * rw[:, None]
        ).astype(x2.dtype)                  # bf16 wire
        # written once and read by both products, not re-evaluated as
        # a producer inside each one's operand: on the chip at
        # [8192, 49152] the pass costs 2.4 ms an exit and the products
        # gain 2.3 (dx) + 0.7 (dW) (PERF.md, PR 34)
        dlgc = lax.optimization_barrier(dlgc)
        dw = dw + lax.dot_general(
            x2, dlgc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                   # [D, V_loc]
        dx = lax.dot_general(
            dlgc, wc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                   # [N, D]
        dx = _reduce_ct_to_primal(dx, x2).astype(x2.dtype)
        return dw, (xent, pred, dx)

    dw0 = None
    if with_grads:
        # (``local``: the shard's offset varies over the model axis
        # even where ``w`` is replicated over it)
        vma = _carry_vma(xs, w, local, row_w)
        dw0 = _vary(jnp.zeros(w.shape, jnp.float32), vma)
    dw, (xent, pred, dx) = lax.scan(
        body, dw0, (xs, row_w, *exit_labels) if own_labels else (xs, row_w)
    )
    return xent, pred, dx, dw


def _exits_head_fwd(xs, w, labels, row_w, vocab, axis_name):
    xent, pred, dx, dw = _exits_head_loop(
        xs, w, labels, row_w, vocab, axis_name, with_grads=True
    )
    return (jnp.sum(row_w * xent), xent, pred), (dx, dw, xent, w, row_w)


def _exits_head_bwd(vocab, axis_name, res, cts):
    g = cts[0].astype(jnp.float32)   # of total; xent, pred: no gradient
    dx, dw, xent, w, row_w = res
    # each shard's partial dW is scaled by ITS cotangent, then summed
    dw = _reduce_ct_to_primal(g * dw, w)
    d_row_w = _reduce_ct_to_primal(g * xent, row_w)
    return (g * dx).astype(dx.dtype), dw.astype(w.dtype), None, d_row_w


exits_unembed_xent.defvjp(_exits_head_fwd, _exits_head_bwd)


# -- spec-aware gradient reduction ------------------------------------------

def grad_sync(grads: PyTree, specs: PyTree,
              mesh_axes=(DATA_AXIS, MODEL_AXIS, SEQ_AXIS)) -> PyTree:
    """Mean-reduce each grad leaf over every mesh axis its param is
    REPLICATED on (the axes absent from its PartitionSpec).

    ONLY for explicitly-constructed per-shard grads (manual backward,
    or pure-DP forwards with no collectives, under ``check_vma=False``)
    — the generalized BSP exchanger.  Do NOT apply it to autodiff grads
    from a vma-checked (``check_vma=True``) shard_map: there the
    psum↔pvary transposes already deliver exact grads for every layout
    and a further psum would double-count (see models/llama.py).
    """

    def one(g, spec):
        used = set()
        for entry in tuple(spec):
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                used.update(entry)
            else:
                used.add(entry)
        reduce_over = tuple(a for a in mesh_axes if a not in used)
        if not reduce_over:
            return g
        n = 1
        for a in reduce_over:
            n *= lax.axis_size(a)
        return (lax.psum(g.astype(jnp.float32), reduce_over) / n).astype(
            g.dtype
        )

    return jax.tree.map(one, grads, specs)
