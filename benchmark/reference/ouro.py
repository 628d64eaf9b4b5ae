"""Plain reference of the looped decoder (Ouro-2.6B's equations):
forward, exit distribution and Stage-I loss in float32 ``jax.numpy``,
no kernels, no scan, no cache, and no import from ``theanompi_tpu``.

One stack of L layers is run R times over the same weights.  On
``h [T, D]``, with N1..N4 the four RMSNorms of a sandwich block and
Nf the final norm::

    h = E[ids]
    for t in 1..R:
        for l in 1..L:
            a = h + N2_l( Attn_l( N1_l(h) ) )      causal, RoPE on q and k
            h = a + N4_l( SwiGLU_l( N3_l(a) ) )    w_down( silu(w_gate x) * w_up x )
        h = Nf(h)                                  the NORMED h enters pass t+1
        z_t = h                                    exit t
        logits_t = z_t W_head                      one head for all exits
        lam_t = sigmoid(z_t w_g + b_g)             t < R
    q_1 = lam_1;  q_t = lam_t prod_{j<t}(1 - lam_j);  q_R = prod_{j<R}(1 - lam_j)
    loss = mean over tokens of [ sum_t q_t xent(logits_t, y) - beta H(q) ]

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]) where the model's own
  file rotates (x[i], x[i + 64]): the same function under a fixed
  permutation of the columns of Wq and Wk; with weights from a seed
  nothing distinguishes them, and the program under test uses the
  adjacent layout.
- The loss is the paper's first-stage objective (arXiv:2510.25741):
  the exits' cross-entropies under the learned exit distribution less
  ``beta`` times its entropy, gates trained with the model.  The
  config's ``early_exit_threshold`` is an inference setting.

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq, wk, wv, wo,
attn_out_norm, mlp_norm, w_gate, w_up, w_down, mlp_out_norm}``,
``final_norm``, ``lm_head [D, V]``, ``exit_gate_w [D, 1]``,
``exit_gate_b [1]``.  A float32 product on a TPU runs in reduced
precision unless asked otherwise, so every entry point sets
``highest``.  One sequence at a time (``lax.map``): float32 logits
are 0.8 GB a sequence and exit at 4096 x 49152, the scores 1.07 GB a
sequence and layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _rope(x, pos, theta):
    """x [T, H, hd], pos [T]: rotate adjacent pairs by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def layer(lp, h, *, n_heads: int, n_kv_heads: int, rope_theta: float,
          eps: float):
    """One sandwich block on ``h [T, D]``."""
    t = h.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    x = _rmsnorm(h, lp["attn_norm"], eps)
    hd = lp["wq"].shape[1] // n_heads
    q = _rope((x @ _f32(lp["wq"])).reshape(t, n_heads, hd), pos, rope_theta)
    k = _rope((x @ _f32(lp["wk"])).reshape(t, n_kv_heads, hd), pos, rope_theta)
    v = (x @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    a = h + _rmsnorm(
        o.reshape(t, n_heads * hd) @ _f32(lp["wo"]), lp["attn_out_norm"], eps
    )
    x = _rmsnorm(a, lp["mlp_norm"], eps)
    m = (jax.nn.silu(x @ _f32(lp["w_gate"])) * (x @ _f32(lp["w_up"]))) @ _f32(
        lp["w_down"]
    )
    return a + _rmsnorm(m, lp["mlp_out_norm"], eps)


def _plain(f):
    return f


def exits(params, ids, *, ut_steps: int, stacks=None, block=_plain, **kw):
    """ids [T] -> the R exits' hidden states, a list of [T, D].  Pass
    t runs ``stacks[t]``: the model's one stack ``params["layers"]``
    every time, unless a test hands in R untied copies to see each
    pass's share of a shared weight's gradient.  ``block`` goes around
    every layer call (see :func:`loss`)."""
    stacks = stacks or [params["layers"]] * ut_steps
    one = block(lambda lp, h: layer(lp, h, **kw))
    h = _f32(params["embed"])[ids]
    out = []
    for stack in stacks:
        for lp in stack:
            h = one(lp, h)
        h = _rmsnorm(h, params["final_norm"], kw["eps"])
        out.append(h)
    return out


def exit_distribution(params, zs):
    """``q [R, T]`` from the exits ``zs`` (a list of R ``[T, D]``)."""
    w, b = _f32(params["exit_gate_w"]), _f32(params["exit_gate_b"])
    lam = [jax.nn.sigmoid((z @ w)[:, 0] + b[0]) for z in zs[:-1]]
    q, rest = [], jnp.ones_like(lam[0])
    for l in lam:
        q.append(l * rest)
        rest = rest * (1.0 - l)
    return jnp.stack(q + [rest])


def _xent(z, head, tgt):
    logp = jax.nn.log_softmax(z @ head, -1)
    return -jnp.take_along_axis(logp, tgt[:, None], -1)[:, 0]


def sequence_terms(params, ids, tgt, *, block=_plain, **kw):
    """Of one sequence: ``(q [R, T], xent [R, T])``."""
    head = _f32(params["lm_head"])
    zs = exits(params, ids, block=block, **kw)
    xent = jnp.stack([block(_xent)(z, head, tgt) for z in zs])
    return exit_distribution(params, zs), xent


def loss(params, inputs, targets, *, n_heads: int, n_kv_heads: int,
         ut_steps: int, beta: float, rope_theta: float, eps: float,
         stacks=None, block=_plain):
    """The Stage-I loss over ``inputs/targets [B, T]``, one sequence
    at a time.  ``block`` is put around every layer call and every
    exit's cross-entropy and changes no value: the check of the
    gradients at the published widths (``tools/ouro_check.py``) hands
    in ``jax.checkpoint``, so that the backward holds one layer's
    scores, or one exit's logits, at a time."""
    kw = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, ut_steps=ut_steps,
              rope_theta=rope_theta, eps=eps, stacks=stacks, block=block)
    with jax.default_matmul_precision("highest"):

        def one(args):
            q, xent = sequence_terms(params, *args, **kw)
            entropy = -jnp.sum(q * jnp.log(q), 0)
            return jnp.mean(jnp.sum(q * xent, 0) - beta * entropy)

        return jnp.mean(jax.lax.map(one, (inputs, targets)))
