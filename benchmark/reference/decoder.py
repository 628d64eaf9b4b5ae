"""Plain reference of the dense decoder (the Mistral-7B equations at
sequences the sliding window never binds): forward and loss in
float32 ``jax.numpy``, no kernels, no cache, no batching tricks, and
no import from ``theanompi_tpu``.

Per layer, on ``x [T, D]``::

    h  = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv   (32 / 8 / 8 heads of 128)
    q, k = rope(q), rope(k)                         theta 10000
    a  = softmax(q k^T / sqrt(128) + causal) v      each kv head serves 4 q heads
    x  = x + a Wo
    x  = x + (silu(rmsnorm(x) Wg) * (rmsnorm(x) Wu)) Wd
    logits = rmsnorm(x) W_head                      untied head, eps 1e-5

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]), the layout of the
  original Meta code, where the Hugging Face port rotates (x[i],
  x[i + 64]).  The two are the same function under a fixed
  permutation of the columns of Wq and Wk; with weights from a seed
  nothing distinguishes them, and the program under test uses the
  adjacent layout.
- No sliding window: with at most 4096 positions the published window
  of 4096 masks nothing.

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq, wk, wv, wo,
mlp_norm, w_gate, w_up, w_down}``, ``final_norm``, ``lm_head [D, V]``.
A float32 product on a TPU runs in reduced precision unless asked
otherwise, so every entry point sets ``highest``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5
THETA = 10000.0


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


def hidden_states(params, ids, *, n_heads: int, n_kv_heads: int):
    """ids [T] -> final-norm hidden states [T, D], float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)     # noqa: E731
    t = ids.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    x = f32(params["embed"])[ids]
    for lp in params["layers"]:
        h = _rmsnorm(x, f32(lp["attn_norm"]))
        hd = lp["wq"].shape[1] // n_heads
        q = _rope((h @ f32(lp["wq"])).reshape(t, n_heads, hd), pos)
        k = _rope((h @ f32(lp["wk"])).reshape(t, n_kv_heads, hd), pos)
        v = (h @ f32(lp["wv"])).reshape(t, n_kv_heads, hd)
        rep = n_heads // n_kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(t, n_heads * hd) @ f32(lp["wo"])
        h = _rmsnorm(x, f32(lp["mlp_norm"]))
        x = x + (
            jax.nn.silu(h @ f32(lp["w_gate"])) * (h @ f32(lp["w_up"]))
        ) @ f32(lp["w_down"])
    return _rmsnorm(x, f32(params["final_norm"]))


def logits_at(params, ids, rows, *, n_heads: int, n_kv_heads: int):
    """Logits [len(rows), V] at the positions ``rows`` of one sequence
    ``ids [T]`` (only those rows meet the head: a whole [T, V] table
    is a gigabyte at these widths)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, n_heads=n_heads,
                          n_kv_heads=n_kv_heads)
        return h[rows] @ jnp.asarray(params["lm_head"], jnp.float32)


def loss(params, inputs, targets, *, n_heads: int, n_kv_heads: int):
    """Mean next-token cross-entropy over ``inputs/targets [B, T]``,
    one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        head = jnp.asarray(params["lm_head"], jnp.float32)

        def one(args):
            ids, tgt = args
            h = hidden_states(params, ids, n_heads=n_heads,
                              n_kv_heads=n_kv_heads)
            logp = jax.nn.log_softmax(h @ head, -1)
            return -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], -1))

        return jnp.mean(jax.lax.map(one, (inputs, targets)))
