"""Plain reference of the ``mellum`` decoder (Mellum2-12B-A2.5B: window
and full attention layers mixed three to one, a rotary table per layer
kind with YaRN on the full ones, every FFN 64 softmax-routed experts
with 8 renormalised picks): forward, loss and, through ``jax.grad`` of
``loss``, gradients, in float32 ``jax.numpy``, no kernels, no sort, no
cache, and no import from ``theanompi_tpu``.

Layer ``l`` of kind ``t_l = layer_types[l]`` on ``x [T, D]``, positions
``p = 0..T-1``, ``H`` query heads and ``H_kv`` key/value heads of
``hd`` (the published head dim: ``H * hd`` need not be ``D``)::

    a = rmsnorm(x; attn_norm)                       eps 1e-6
    q = a Wq -> [H, hd]   k = a Wk, v = a Wv -> [H_kv, hd]
                                                    no bias, no QK-norm
    the rotary table of the layer's kind, i = 0..hd/2-1,
    f_i = theta^(-2i/hd):
      "default" (the window layers):  w_i = f_i,  c = 1
      "yarn" (the full layers):
          d(n)  = hd ln(original / (2 pi n)) / (2 ln theta)
          lo    = floor(d(beta_fast)),  hi = ceil(d(beta_slow))
          r_i   = clip((i - lo) / (hi - lo), 0, 1)
          w_i   = (f_i / factor) r_i + f_i (1 - r_i)
          c     = attention_factor              (0.1 ln(factor) + 1)
    cos = c cos(p w_i), sin = c sin(p w_i); q and k rotated with them
                      (static: the same table at every length)
    s = q k^T / sqrt(hd), a key/value head serving H / H_kv query heads
    key j is visible to query i  where  j <= i  and, on a
    "sliding_attention" layer,  i - j < sliding_window
                      (a query sees itself and the window - 1 before it)
    x = x + softmax(s + mask) v  Wo                 softmax in float32
    m = rmsnorm(x; mlp_norm)
    g = softmax_E(m W_router)                       float32, E experts
    (e_j), j = 1..k = the k largest g;  g_j = g[e_j] / sum_j g[e_j]
    x = x + sum_{j : e_j held} g_j Wd[e_j]( silu(Wg[e_j] m) * (Wu[e_j] m) )
    logits = rmsnorm(x; final_norm) W_head          untied head
    loss = mean CE + aux_coef * LB
    LB = mean over layers of  E * sum_e f_e P_e     f_e: share of the
                      batch's T*k picks that went to expert e over ALL
                      E (no gradient), P_e: mean of g_e over the batch

**One rank's share.**  The weights may hold only experts ``[0,
held)`` of the ``E`` the router scores (``we_* [held, ...]``): the
router, its top-k, the renormalisation over all ``k`` picks and the
balance loss stay over all ``E``; the sum over the picks runs over the
held experts alone.  With ``held < E`` the gates are read without a
gradient (a share by itself holds its router's part of the task loss,
as ``reference/glm_moe_lite.py`` says and why); the balance loss
reads the scores of all ``E`` experts and is whole on any rank, so it
is then the routers' only gradient.  The vocabulary may be a slice as
well: ``embed`` and ``lm_head`` are what the tree holds.

The routed sum is computed as the definition reads: a dense ``[T,
held]`` gate matrix, zero outside a token's picks, times the outputs
of ALL held experts, a block of tokens at a time; attention one head
at a time (an explicit ``[T, T]`` mask a layer kind).  Neither
blocking changes a value.

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]) where the Hugging Face
  port rotates (x[i], x[i + hd/2]): the same function under a fixed
  permutation of the columns of Wq and Wk inside each head.  With
  weights from a seed nothing distinguishes the layouts; the program
  under test uses the adjacent one.
- ``aux_coef`` is not in the published ``config.json``; it is an
  argument (the configuration's file says which value and why).
- ``LB`` is the Switch form over all ``k`` picks (1.0 at balance).
- No QK-norm and no multi-token-prediction module: no key of the
  published config names either.

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq [D, H hd], wk,
wv [D, H_kv hd], wo [H hd, D], mlp_norm, router [D, E], we_gate
[held, D, F], we_up, we_down [held, F, D]}``, ``final_norm``,
``lm_head [D, V]``.  A float32 product on a TPU runs in reduced
precision unless asked otherwise, so every entry point sets
``highest``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_BLOCK = 256


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def rotary_table(spec: dict, hd: int):
    """``(w [hd/2] float32, c)`` of one kind's ``rope_parameters``
    entry (module docstring)."""
    theta = float(spec["rope_theta"])
    i = np.arange(hd // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / hd)
    if spec.get("rope_type", "default") == "default":
        return f.astype(np.float32), 1.0
    assert spec["rope_type"] == "yarn", spec["rope_type"]
    factor = float(spec["factor"])
    original = float(spec["original_max_position_embeddings"])

    def d(n):
        return hd * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    lo = max(math.floor(d(float(spec["beta_fast"]))), 0)
    hi = min(math.ceil(d(float(spec["beta_slow"]))), hd - 1)
    r = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    c = spec.get("attention_factor")
    c = 0.1 * math.log(factor) + 1.0 if c is None else float(c)
    return ((f / factor) * r + f * (1 - r)).astype(np.float32), c


def _rope(x, pos, w, c):
    """x [T, H, hd], pos [T]: rotate adjacent pairs by ``pos * w_i``,
    cos and sin times ``c``."""
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(w)
    cos, sin = c * jnp.cos(ang), c * jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def visible(t: int, window: int | None):
    """The layer kind's ``[T, T]`` mask: key ``j`` for query ``i``."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    return mask if window is None else mask & (i - j < window)


def attention(x, lp, kind: str, *, n_heads: int, n_kv_heads: int,
              head_dim: int, sliding_window: int, rope_parameters: dict,
              eps: float):
    """The attention branch of one layer of ``kind``, ``x [T, D] ->
    [T, D]`` (the residual not added).  ``head_dim`` is the PUBLISHED
    one: weights cut for other heads (``D / H`` where that differs)
    are refused, not reinterpreted."""
    t = x.shape[0]
    pos = jnp.arange(t)
    hd = head_dim
    if lp["wq"].shape != (x.shape[-1], n_heads * hd) or (
            lp["wk"].shape != (x.shape[-1], n_kv_heads * hd)):
        raise ValueError(
            f"the weights hold wq {lp['wq'].shape}, wk {lp['wk'].shape}: "
            f"not {n_heads} query and {n_kv_heads} key/value heads of "
            f"the published head_dim {hd}"
        )
    a = _rmsnorm(x, lp["attn_norm"], eps)
    q = a @ _f32(lp["wq"])
    w, c = rotary_table(rope_parameters[kind], hd)
    q = _rope(q.reshape(t, n_heads, hd), pos, w, c)
    k = _rope((a @ _f32(lp["wk"])).reshape(t, n_kv_heads, hd), pos, w, c)
    v = (a @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    mask = visible(t, sliding_window if kind == "sliding_attention" else None)

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                   # [T, hd]
        s = qh @ kh.T / jnp.sqrt(jnp.float32(hd))
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ vh

    o = jax.lax.map(head, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(t, n_heads * hd) @ _f32(lp["wo"])


def route(m, router, top_k: int):
    """``m [T, D]`` -> (gate matrix ``[T, E]``: a token's ``top_k``
    largest softmax scores divided by their sum, zero elsewhere; the
    picks ``[T, top_k]``; the scores ``[T, E]``)."""
    g = jax.nn.softmax(m @ _f32(router), -1)
    vals, idx = jax.lax.top_k(g, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    picked = jax.nn.one_hot(idx, g.shape[-1], dtype=g.dtype)   # [T, k, E]
    return jnp.sum(picked * vals[..., None], axis=1), idx, g


def routed(m, gate, lp):
    """sum over the HELD experts e of ``gate[:, e] * expert_e(m)``:
    all of them on every token, a block of tokens at a time."""
    wg, wu, wd = _f32(lp["we_gate"]), _f32(lp["we_up"]), _f32(lp["we_down"])
    t, d = m.shape
    gate = gate[:, :wg.shape[0]]
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    @jax.checkpoint
    def one(args):
        mb, gb = args
        a = jnp.einsum("td,edf->etf", mb, wg)
        u = jnp.einsum("td,edf->etf", mb, wu)
        o = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * u, wd)
        return jnp.einsum("te,etd->td", gb, o)

    y = jax.lax.map(one, (m.reshape(t // block, block, d),
                          gate.reshape(t // block, block, -1)))
    return y.reshape(t, d)


def ffn(x, lp, *, top_k: int, eps: float):
    """The expert branch of one layer, ``x [T, D]`` -> ``(branch [T,
    D], pick counts [E] over all experts, summed scores [E])``."""
    m = _rmsnorm(x, lp["mlp_norm"], eps)
    gate, idx, g = route(m, lp["router"], top_k)
    if lp["we_gate"].shape[0] < g.shape[-1]:
        # a share by itself: the gates carry no gradient to the router
        gate = jax.lax.stop_gradient(gate)
    counts = jnp.sum(jax.nn.one_hot(idx, g.shape[-1]), axis=(0, 1))
    return routed(m, gate, lp), counts, jnp.sum(g, axis=0)


def layer(x, lp, kind: str, *, n_heads: int, n_kv_heads: int,
          head_dim: int, top_k: int, sliding_window: int,
          rope_parameters: dict, eps: float = 1e-6, **_):
    """One whole block of ``kind``, ``x [T, D] -> (x [T, D], pick
    counts [E], summed scores [E])``."""
    x = x + attention(
        x, lp, kind, n_heads=n_heads, n_kv_heads=n_kv_heads,
        head_dim=head_dim, sliding_window=sliding_window,
        rope_parameters=rope_parameters, eps=eps)
    y, counts, gsum = ffn(x, lp, top_k=top_k, eps=eps)
    return x + y, counts, gsum


def _sequence(params, ids, targets, kw):
    """One sequence ``ids, targets [T]`` -> (sum of the CE, pick counts
    ``[L, E]``, summed scores ``[L, E]``).  ``kw["block"]``
    (``jax.checkpoint``) wraps every layer call: a backward pass then
    holds one layer's intermediates at a time."""
    kw = dict(kw)
    wrap = kw.pop("block", None) or (lambda f: f)
    kinds = kw.pop("layer_types")
    eps = kw.get("eps", 1e-6)
    x = _f32(params["embed"])[ids]
    counts, gsums = [], []
    for lp, kind in zip(params["layers"], kinds):
        x, c, g = wrap(
            lambda x, lp, kind=kind: layer(x, lp, kind, **kw))(x, lp)
        counts.append(c)
        gsums.append(g)

    @wrap
    def ce(hidden):
        logp = jax.nn.log_softmax(
            _rmsnorm(hidden, params["final_norm"], eps)
            @ _f32(params["lm_head"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    return ce(x), jnp.stack(counts), jnp.stack(gsums)


def loss_and_counts(params, inputs, targets, *, aux_coef: float = 0.0,
                    **kw):
    """``(loss, pick counts [L, E] of the whole batch)`` over
    ``inputs/targets [B, T]``, one sequence at a time; the balance
    loss's moments are pooled over the batch first."""
    with jax.default_matmul_precision("highest"):
        one = jax.checkpoint(lambda args: _sequence(params, *args, kw))
        ce, counts, gsums = jax.lax.map(one, (inputs, targets))
        n = inputs.shape[0] * inputs.shape[1]
        n_experts = counts.shape[-1]
        counts = jax.lax.stop_gradient(counts.sum(0))           # [L, E]
        f = counts / (n * kw["top_k"])
        p = gsums.sum(0) / n
        lb = jnp.mean(n_experts * jnp.sum(f * p, axis=-1))
        return jnp.sum(ce) / n + aux_coef * lb, counts


def loss(params, inputs, targets, **kw):
    """The training loss (module docstring) over ``inputs/targets [B,
    T]``."""
    return loss_and_counts(params, inputs, targets, **kw)[0]
