"""Plain reference of the ``granitemoehybrid`` decoder without experts
(granite-4.0-h-micro: nine Mamba-2 state-space layers to every
attention layer, no rotary position at all, four scalar multipliers,
one dense SwiGLU in every layer, a tied head): forward, loss and,
through ``jax.grad`` of ``loss``, gradients, in float32 ``jax.numpy``,
the recurrence a ``lax.scan`` over TOKENS — no chunks, no kernels, no
cache, and no import from ``theanompi_tpu``.

``h_0 = embedding_multiplier * E[ids]`` on ``[T, D]``.  Layer ``l`` of
kind ``layer_types[l]`` (``H`` state heads of ``P`` channels, ``G``
groups of ``N`` state channels, ``I = H P``)::

    a = rmsnorm(h; attn_norm)                         eps 1e-5
                                (the published ``input_layernorm``)
    "mamba":
      [z | xBC | dt] = a W_in        widths I | I + 2 G N | H, no bias
      xBC = silu(conv1d_causal(xBC; w [4, I + 2 G N], b))
                      depthwise, taps t-3..t: the LAST tap is the token
      [x | B | C] = xBC              widths I (H heads of P) | G N | G N
      dt = softplus(dt + dt_bias)    [T, H];   A = -exp(A_log)  [H]
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
                      a [P, N] state a head, S_{-1} = 0; a head reads
                      the B and C of its group
      y_t = S_t C_t + D x_t
      m = rmsnorm(y * silu(z); ssm_norm [I]) W_out
                      the gate INSIDE the norm, the statistic over I / G
    "attention" (32 query heads, 8 key/value heads of 64):
      q = a Wq, k = a Wk, v = a Wv   NO rotation, no bias
      m = softmax(attention_multiplier * q k^T + causal) v Wo
                      not 1 / sqrt(64); a key/value head serves H / H_kv
    h = h + residual_multiplier * m
    n = rmsnorm(h; mlp_norm)    (the published ``post_attention_layernorm``)
    h = h + residual_multiplier * Wd( silu(Wg n) * (Wu n) )
    logits = rmsnorm(h_L; final_norm) E^T / logits_scaling      TIED
    loss = mean cross-entropy over the rows the tree holds

The vocabulary may be a slice: ``embed`` is what the tree holds, and
ids, logits and loss run over its rows.  The stack may be cut in
depth: ``layer_types`` names the layers the tree holds.

What is computed in blocks, none of which changes a value: the
recurrence runs ``TOKEN_BLOCK`` tokens at a time under
``jax.checkpoint`` (a backward pass then keeps the state at the
blocks' starts and one block's steps, not all ``T`` states);
attention one head at a time; with ``block=jax.checkpoint`` every
layer call is replayed in the backward.

Departures from the published model, each noted:

- The convolution's weight is held tap-major, ``[d_conv, channels]``
  (tap ``k`` multiplies position ``t - 3 + k``), where the published
  ``Conv1d`` holds ``[channels, 1, d_conv]``: the same numbers
  transposed, the channels on the vector unit's lanes.
- ``time_step_limit`` is ``(0, inf)``: ``dt`` is not clipped (the
  published port's default).
- The published ``intermediate_size`` (8192) is the unused twin of
  ``shared_intermediate_size`` (8192): with ``num_local_experts`` 0
  the one SwiGLU of a layer is the shared one.
- No dropout, no biases but the convolution's (``mamba_conv_bias``
  true, ``mamba_proj_bias`` and ``attention_bias`` false).

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``final_norm``, ``layers[i]{attn_norm,
mlp_norm, w_gate, w_up [D, F], w_down [F, D]}`` and, a mamba layer,
``{ssm_in [D, 2 I + 2 G N + H], ssm_conv_w [4, I + 2 G N],
ssm_conv_b, ssm_dt_bias [H], ssm_a_log [H], ssm_d [H], ssm_norm [I],
ssm_out [I, D]}``, an attention layer ``{wq [D, H hd], wk, wv [D, H_kv
hd], wo [H hd, D]}``.  A float32 product on a TPU runs in reduced
precision unless asked otherwise, so every entry point sets
``highest``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 128


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def recurrence(x, dt, a, b, c):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T`` from ``S_{-1} = 0``: ``x [T, H, P]``, ``dt [T, H]``, ``a
    [H]``, ``b`` and ``c`` ``[T, H, N]`` -> ``[T, H, P]``, a token at
    a time."""
    t, h, p = x.shape

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    n = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    blocks = tuple(
        z.reshape(t // n, n, *z.shape[1:]) for z in (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, b.shape[-1])), blocks)
    return y.reshape(t, h, p)


def conv_silu(x, w, b):
    """``silu`` of the causal depthwise convolution: ``x [T, C]``, ``w
    [K, C]``, tap ``k`` on position ``t - (K - 1) + k``."""
    k, t = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return jax.nn.silu(sum(xp[j:j + t] * w[j] for j in range(k)) + b)


def mamba(h, lp, *, mamba_n_heads: int, mamba_d_head: int,
          mamba_d_state: int, mamba_n_groups: int, eps: float):
    """The mixer branch of a mamba layer, ``h [T, D] -> [T, D]`` (the
    residual not added)."""
    t = h.shape[0]
    nh, p, n, g = mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups
    inner, gn = nh * p, g * n
    if lp["ssm_in"].shape[1] != 2 * inner + 2 * gn + nh:
        raise ValueError(
            f"ssm_in holds {lp['ssm_in'].shape[1]} columns: not [z | x B "
            f"C | dt] of {nh} heads of {p}, {g} groups of {n}")
    proj = _rmsnorm(h, lp["attn_norm"], eps) @ _f32(lp["ssm_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    xbc = conv_silu(xbc, _f32(lp["ssm_conv_w"]), _f32(lp["ssm_conv_b"]))
    x = xbc[:, :inner].reshape(t, nh, p)
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(t, g, n), nh // g, 1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(t, g, n), nh // g, 1)
    dt = jax.nn.softplus(dt + _f32(lp["ssm_dt_bias"]))
    y = recurrence(x, dt, -jnp.exp(_f32(lp["ssm_a_log"])), b, c)
    y = y + _f32(lp["ssm_d"])[:, None] * x
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    normed = _rmsnorm(gated, 1.0, eps).reshape(t, inner)
    return (normed * _f32(lp["ssm_norm"])) @ _f32(lp["ssm_out"])


def attention(h, lp, *, n_heads: int, n_kv_heads: int, head_dim: int,
              attention_multiplier: float, eps: float):
    """The mixer branch of an attention layer, ``h [T, D] -> [T, D]``:
    no rotation; the scores times ``attention_multiplier``."""
    t, hd = h.shape[0], head_dim
    if lp["wq"].shape != (h.shape[-1], n_heads * hd) or (
            lp["wk"].shape != (h.shape[-1], n_kv_heads * hd)):
        raise ValueError(
            f"the weights hold wq {lp['wq'].shape}, wk {lp['wk'].shape}: "
            f"not {n_heads} query and {n_kv_heads} key/value heads of {hd}")
    a = _rmsnorm(h, lp["attn_norm"], eps)
    q = (a @ _f32(lp["wq"])).reshape(t, n_heads, hd)
    k = (a @ _f32(lp["wk"])).reshape(t, n_kv_heads, hd)
    v = (a @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                   # [T, hd]
        s = attention_multiplier * (qh @ kh.T)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1) @ vh

    o = jax.lax.map(head, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(t, n_heads * hd) @ _f32(lp["wo"])


def layer(h, lp, kind: str, *, residual_multiplier: float,
          eps: float = 1e-5, n_heads: int, n_kv_heads: int, head_dim: int,
          attention_multiplier: float, **mamba_kw):
    """One whole block of ``kind``, ``h [T, D] -> [T, D]``."""
    if kind == "mamba":
        m = mamba(h, lp, eps=eps, **mamba_kw)
    else:
        assert kind == "attention", kind
        m = attention(
            h, lp, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, attention_multiplier=attention_multiplier,
            eps=eps)
    h = h + residual_multiplier * m
    n = _rmsnorm(h, lp["mlp_norm"], eps)
    return h + residual_multiplier * (
        (jax.nn.silu(n @ _f32(lp["w_gate"])) * (n @ _f32(lp["w_up"])))
        @ _f32(lp["w_down"]))


def sequence_logits(params, ids, kw):
    """One sequence ``ids [T]`` -> logits ``[T, V]`` over the rows the
    tree holds.  ``kw["block"]`` (``jax.checkpoint``) wraps every
    layer call."""
    kw = dict(kw)
    wrap = kw.pop("block", None) or (lambda f: f)
    kinds = kw.pop("layer_types")
    emb, scaling = kw.pop("embedding_multiplier"), kw.pop("logits_scaling")
    if len(kinds) != len(params["layers"]):
        raise ValueError(
            f"layer_types names {len(kinds)} layers, the tree holds "
            f"{len(params['layers'])}")
    table = _f32(params["embed"])
    h = emb * table[ids]
    for lp, kind in zip(params["layers"], kinds):
        h = wrap(lambda h, lp, kind=kind: layer(h, lp, kind, **kw))(h, lp)
    return _rmsnorm(
        h, params["final_norm"], kw.get("eps", 1e-5)) @ table.T / scaling


def _sequence(params, ids, targets, kw):
    """The sum of one sequence's cross-entropies."""
    wrap = kw.get("block") or (lambda f: f)

    @wrap
    def ce(logits):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    return ce(sequence_logits(params, ids, kw))


def logits(params, ids, **kw):
    """Logits ``[T, V]`` of one sequence ``ids [T]``."""
    with jax.default_matmul_precision("highest"):
        return sequence_logits(params, ids, kw)


def loss(params, inputs, targets, **kw):
    """Mean next-token cross-entropy over ``inputs/targets [B, T]``,
    one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        one = jax.checkpoint(lambda args: _sequence(params, *args, kw))
        return jnp.sum(jax.lax.map(one, (inputs, targets))) / inputs.size
