"""Plain reference of the OLMoE decoder (OLMoE-1B-7B: QK-norm, 64
experts, 8 picks a token, every pick computed): forward, loss and —
through ``jax.grad`` of ``loss`` — gradients, in float32
``jax.numpy``, no kernels, no sort, no capacity, no cache, and no
import from ``theanompi_tpu``.

Per layer, on ``x [T, 2048]``::

    h = rmsnorm(x; attn_norm)
    q = rmsnorm(h Wq; q_norm)   k = rmsnorm(h Wk; k_norm)   v = h Wv
                      the norm is over all 2048 channels, eps 1e-5,
                      before the split into 16 heads of 128
    q, k = rope(q), rope(k)                         theta 10000
    x = x + softmax(q k^T / sqrt(128) + causal) v  Wo
    h = rmsnorm(x; mlp_norm)
    p = softmax(h W_router)                         64 experts, float32
    (g_j, e_j), j = 1..8 = top-8 of p               NOT renormalised
    x = x + sum_j g_j * Wd[e_j] ( silu(Wg[e_j] h) * (Wu[e_j] h) )
    logits = rmsnorm(x; final_norm) W_head          untied head
    loss = mean CE + aux_coef * LB + z_coef * Z
    LB = mean over layers of  E * sum_e f_e P_e     f_e: share of the
                      batch's T*8 picks that went to expert e (no
                      gradient), P_e: mean of p_e over the batch
    Z  = mean over layers and tokens of logsumexp(h W_router)^2

The expert sum is computed as the definition reads: a dense ``[T, E]``
gate matrix, zero outside a token's top-8, times the outputs of ALL
experts, a block of tokens at a time (``[E, block, D]`` floats; the
blocks are independent, so their size changes nothing but the memory).

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]) where the Hugging Face
  port rotates (x[i], x[i + 64]): the same function under a fixed
  permutation of the columns of Wq and Wk inside each head.  QK-norm
  does not break that: its RMS statistic is invariant under any
  permutation of the 2048 channels, and its weight vector permutes
  with the columns.  With weights from a seed nothing distinguishes
  the layouts; the program under test uses the adjacent one.
- ``LB`` here is the Switch form over all 8 picks (1.0 at balance);
  the Hugging Face port's term is 8 times this.  The coefficients
  (``aux_coef`` 0.01, ``z_coef`` 0.001) are arguments.
- ``clip_qkv`` is null in the published config: nothing to clip.

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq, wk, wv, wo,
q_norm, k_norm, mlp_norm, router [D, E], we_gate [E, D, F], we_up
[E, D, F], we_down [E, F, D]}``, ``final_norm``, ``lm_head [D, V]``.
A float32 product on a TPU runs in reduced precision unless asked
otherwise, so every entry point sets ``highest``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5
THETA = 10000.0
TOKEN_BLOCK = 256


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def _rope(x, pos):
    """x [T, H, hd], pos [T]: rotate adjacent pairs by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (THETA ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def _attention(x, lp, n_heads: int, n_kv_heads: int):
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rmsnorm(x, _f32(lp["attn_norm"]))
    q = _rmsnorm(h @ _f32(lp["wq"]), _f32(lp["q_norm"]))
    k = _rmsnorm(h @ _f32(lp["wk"]), _f32(lp["k_norm"]))
    hd = q.shape[-1] // n_heads
    q = _rope(q.reshape(t, n_heads, hd), pos)
    k = _rope(k.reshape(t, n_kv_heads, hd), pos)
    v = (h @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return a.reshape(t, n_heads * hd) @ _f32(lp["wo"])


def route(h, router, top_k: int):
    """``h [T, D]`` -> (gate matrix ``[T, E]``, zero outside a token's
    ``top_k`` largest probabilities and NOT renormalised inside;
    picked expert ids ``[T, top_k]``; probabilities; logits)."""
    logits = h @ _f32(router)
    p = jax.nn.softmax(logits, -1)
    vals, idx = jax.lax.top_k(p, top_k)
    picked = jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype)   # [T, k, E]
    return jnp.sum(picked * vals[..., None], axis=1), idx, p, logits


def _experts(h, gate, lp):
    """sum_e gate[:, e] * expert_e(h): all experts on every token, a
    block of tokens at a time."""
    wg, wu, wd = _f32(lp["we_gate"]), _f32(lp["we_up"]), _f32(lp["we_down"])
    t, d = h.shape
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    @jax.checkpoint
    def one(args):
        hb, gb = args
        a = jnp.einsum("td,edf->etf", hb, wg)
        u = jnp.einsum("td,edf->etf", hb, wu)
        o = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * u, wd)
        return jnp.einsum("te,etd->td", gb, o)

    y = jax.lax.map(one, (h.reshape(t // block, block, d),
                          gate.reshape(t // block, block, -1)))
    return y.reshape(t, d)


def _sequence(params, ids, *, n_heads: int, n_kv_heads: int, top_k: int):
    """ids [T] -> final-norm hidden states [T, D] and, per layer, the
    pick counts [E], the summed router probabilities [E], the summed
    squared logsumexp of the router logits, and the picks [T, k]."""
    x = _f32(params["embed"])[ids]
    counts, psums, zsums, picks = [], [], [], []
    for lp in params["layers"]:
        x = x + _attention(x, lp, n_heads, n_kv_heads)
        h = _rmsnorm(x, _f32(lp["mlp_norm"]))
        gate, idx, p, logits = route(h, lp["router"], top_k)
        x = x + _experts(h, gate, lp)
        counts.append(jnp.sum(jax.nn.one_hot(idx, p.shape[-1]), axis=(0, 1)))
        psums.append(jnp.sum(p, axis=0))
        zsums.append(jnp.sum(jax.scipy.special.logsumexp(logits, -1) ** 2))
        picks.append(idx)
    stats = (jnp.stack(counts), jnp.stack(psums), jnp.stack(zsums))
    return _rmsnorm(x, _f32(params["final_norm"])), stats, jnp.stack(picks)


def hidden_states(params, ids, *, n_heads: int, n_kv_heads: int,
                  top_k: int, **_):
    """ids [T] -> final-norm hidden states [T, D], float32."""
    return _sequence(params, ids, n_heads=n_heads, n_kv_heads=n_kv_heads,
                     top_k=top_k)[0]


def logits_at(params, ids, rows, *, n_heads: int, n_kv_heads: int,
              top_k: int, **_):
    """``(logits [len(rows), V], picks [L, T, top_k])`` of one sequence
    ``ids [T]``: only the positions ``rows`` meet the head."""
    with jax.default_matmul_precision("highest"):
        h, _, picks = _sequence(params, ids, n_heads=n_heads,
                                n_kv_heads=n_kv_heads, top_k=top_k)
        return h[rows] @ _f32(params["lm_head"]), picks


def loss(params, inputs, targets, *, n_heads: int, n_kv_heads: int,
         top_k: int, aux_coef: float, z_coef: float):
    """Mean next-token cross-entropy over ``inputs/targets [B, T]``
    plus the two router terms (module docstring), one sequence at a
    time; the terms' moments are pooled over the batch first."""
    with jax.default_matmul_precision("highest"):
        head = _f32(params["lm_head"])

        @jax.checkpoint
        def one(args):
            ids, tgt = args
            h, stats, _ = _sequence(params, ids, n_heads=n_heads,
                                    n_kv_heads=n_kv_heads, top_k=top_k)
            logp = jax.nn.log_softmax(h @ head, -1)
            ce = -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], -1))
            return ce, stats

        ce, (counts, psums, zsums) = jax.lax.map(one, (inputs, targets))
        n_tokens = inputs.shape[0] * inputs.shape[1]
        n_experts = counts.shape[-1]
        f = jax.lax.stop_gradient(counts.sum(0)) / (n_tokens * top_k)  # [L, E]
        p = psums.sum(0) / n_tokens
        lb = jnp.mean(n_experts * jnp.sum(f * p, axis=-1))
        z = jnp.mean(zsums.sum(0) / n_tokens)
        return jnp.mean(ce) + aux_coef * lb + z_coef * z
