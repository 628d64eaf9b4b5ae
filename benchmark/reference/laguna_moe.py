"""Plain reference of the ``laguna`` decoder (Laguna-S-2.1: window
layers of 72 query heads to full layers of 48 over the same 8
key/value heads, a sigmoid gate a head on attention's output, half a
head's channels rotated on the full layers under YaRN, a dense first
layer, then 256 softmax-routed experts with 10 renormalised picks
times a routed scaling factor beside one shared expert): forward, loss
and, through ``jax.grad`` of ``loss``, gradients, in float32
``jax.numpy``, no kernels, no sort, no cache, and no import from
``theanompi_tpu``.

Layer ``l`` of kind ``t_l = layer_types[l]`` with ``H_l =
heads_per_layer[l]`` query heads on ``x [T, D]``, positions ``p =
0..T-1``, ``H_kv`` key/value heads, ``hd`` channels a head::

    a = rmsnorm(x; attn_norm)                       eps 1e-6
    q = a Wq -> [H_l, hd]   k = a Wk, v = a Wv -> [H_kv, hd]
    g = sigmoid(a Wg) -> [H_l]                      one gate a head
                                                    no bias, no QK-norm
    the kind's entry of rope_parameters rotates r = hd *
    partial_rotary_factor channels of a head, the other hd - r pass as
    they are; its table is that of a head of r channels,
    i = 0..r/2-1, f_i = theta^(-2i/r):
      "default" (the window layers, r = hd):  w_i = f_i,  c = 1
      "yarn" (the full layers, r = hd / 2):
          d(n)  = r ln(original / (2 pi n)) / (2 ln theta)
          lo    = floor(d(beta_fast)),  hi = ceil(d(beta_slow))
          r_i   = clip((i - lo) / (hi - lo), 0, 1)
          w_i   = (f_i / factor) r_i + f_i (1 - r_i)
          c     = attention_factor
    cos = c cos(p w_i), sin = c sin(p w_i)   (static, at every length)
    s = q k^T / sqrt(hd), a key/value head serving H_l / H_kv heads
    key j is visible to query i  where  j <= i  and, on a
    "sliding_attention" layer,  i - j < sliding_window
    o_h = g_h * softmax(s_h + mask) v               softmax in float32
    x = x + concat_h(o_h) Wo                        Wo [H_l hd, D]
    m = rmsnorm(x; mlp_norm)
    a layer with dense weights (the first):
        x = x + Wd( silu(Wg m) * (Wu m) )
    a layer with a router:
        s = softmax_E(m W_router)                   float32, E experts
        (e_j), j = 1..k = the k largest s
        w_j = route_scale * s[e_j] / sum_j s[e_j]
        x = x + sum_{j : e_j held} w_j expert_{e_j}(m) + shared(m)
                      (each a SwiGLU; the shared one has no gate)
    logits = rmsnorm(x; final_norm) W_head          untied head
    loss = mean CE + aux_coef * LB
    LB = mean over the ROUTED layers of  E * sum_e f_e P_e
                      f_e: share of the batch's T*k picks that went to
                      expert e over ALL E (no gradient), P_e: mean of
                      s_e over the batch

**One rank's share.**  The weights may hold only experts ``[0,
held)`` of the ``E`` the router scores (``we_* [held, ...]``): the
router, its top-k, the renormalisation over all ``k`` picks, the
scaling factor and the balance loss stay over all ``E``; the sum over
the picks runs over the held experts alone; the shared expert, like
attention and the dense layer, is whole on every rank.  With ``held <
E`` the gates are read without a gradient (a share by itself holds its
router's part of the task loss, as ``reference/glm_moe_lite.py`` says
and why); the balance loss is whole on any rank and is then the
routers' only gradient.  The vocabulary may be a slice as well.

The routed sum is computed as the definition reads: a dense ``[T,
held]`` gate matrix, zero outside a token's picks, times the outputs
of ALL held experts, a block of tokens at a time; attention one head
at a time (an explicit ``[T, T]`` mask a layer kind).  Neither
blocking changes a value.

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]) of the LAST ``r``
  channels of a head, where the Hugging Face half-split layout rotates
  (x[i], x[i + r/2]) of the FIRST ``r``: the same function under a
  fixed permutation of the columns of Wq and Wk inside each head.
  With weights from a seed nothing distinguishes the layouts; the
  program under test uses this one.
- The gate's form is the headwise gate G1 of Qiu et al.,
  arXiv:2505.06708 (from the block's normed input, a sigmoid, on the
  attention output before ``Wo``): ``config.json`` names ``gating:
  "per-head"`` and no more.
- The shared expert is summed ungated (Qwen2-MoE's modelling file
  gates its own by a ``[D, 1]`` sigmoid; no key of this config names
  such a gate).
- ``aux_coef`` is not in the published ``config.json``; it is an
  argument (the configuration's file says which value and why).
- ``LB`` is the Switch form over all ``k`` picks (1.0 at balance).

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq [D, H_l hd],
wk, wv [D, H_kv hd], w_attn_gate [D, H_l], wo [H_l hd, D], mlp_norm}``
with ``{w_gate, w_up [D, F_dense], w_down}`` or ``{router [D, E],
we_gate [held, D, F], we_up, we_down [held, F, D], ws_gate [D, F],
ws_up, ws_down [F, D]}``, ``final_norm``, ``lm_head [D, V]``.  A
float32 product on a TPU runs in reduced precision unless asked
otherwise, so every entry point sets ``highest``.
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
    """``(w [r/2] float32, c)`` of one kind's ``rope_parameters``
    entry, ``r = hd * partial_rotary_factor`` (module docstring)."""
    r = int(hd * float(spec.get("partial_rotary_factor", 1.0)))
    theta = float(spec["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)
    if spec.get("rope_type", "default") == "default":
        return f.astype(np.float32), 1.0
    assert spec["rope_type"] == "yarn", spec["rope_type"]
    factor = float(spec["factor"])
    original = float(spec["original_max_position_embeddings"])

    def d(n):
        return r * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    lo = max(math.floor(d(float(spec["beta_fast"]))), 0)
    hi = min(math.ceil(d(float(spec["beta_slow"]))), r - 1)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    c = spec.get("attention_factor")
    c = 0.1 * math.log(factor) + 1.0 if c is None else float(c)
    return ((f / factor) * ramp + f * (1 - ramp)).astype(np.float32), c


def _rope(x, pos, w, c):
    """x [T, H, hd], pos [T]: the LAST ``2 len(w)`` channels of a head
    rotated in adjacent pairs by ``pos * w_i``, cos and sin times
    ``c``; the channels before them as they are."""
    r = 2 * len(w)
    keep, turn = x[..., :x.shape[-1] - r], x[..., x.shape[-1] - r:]
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(w)
    cos, sin = c * jnp.cos(ang), c * jnp.sin(ang)
    x1, x2 = turn[..., 0::2], turn[..., 1::2]
    turned = jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(turn.shape)
    return jnp.concatenate([keep, turned], -1)


def visible(t: int, window: int | None):
    """The layer kind's ``[T, T]`` mask: key ``j`` for query ``i``."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    return mask if window is None else mask & (i - j < window)


def attention(x, lp, kind: str, n_heads: int, *, n_kv_heads: int,
              head_dim: int, sliding_window: int, rope_parameters: dict,
              eps: float):
    """The attention branch of one layer of ``kind`` with ``n_heads``
    query heads, ``x [T, D] -> [T, D]`` (the residual not added).
    Weights cut for another head count or head dim are refused, not
    reinterpreted."""
    t, d = x.shape
    pos = jnp.arange(t)
    hd = head_dim
    want = {"wq": (d, n_heads * hd), "wk": (d, n_kv_heads * hd),
            "w_attn_gate": (d, n_heads), "wo": (n_heads * hd, d)}
    got = {k: tuple(lp[k].shape) for k in want}
    if got != want:
        raise ValueError(
            f"the weights hold {got}: not {n_heads} query and "
            f"{n_kv_heads} key/value heads of the published head_dim "
            f"{hd} with a gate a head ({want})"
        )
    a = _rmsnorm(x, lp["attn_norm"], eps)
    w, c = rotary_table(rope_parameters[kind], hd)
    q = _rope((a @ _f32(lp["wq"])).reshape(t, n_heads, hd), pos, w, c)
    k = _rope((a @ _f32(lp["wk"])).reshape(t, n_kv_heads, hd), pos, w, c)
    v = (a @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    gate = jax.nn.sigmoid(a @ _f32(lp["w_attn_gate"]))          # [T, H]
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    mask = visible(t, sliding_window if kind == "sliding_attention" else None)

    @jax.checkpoint
    def head(args):
        qh, kh, vh, gh = args                       # [T, hd] x 3, [T]
        s = qh @ kh.T / jnp.sqrt(jnp.float32(hd))
        o = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ vh
        return gh[:, None] * o

    o = jax.lax.map(head, (*(z.transpose(1, 0, 2) for z in (q, k, v)),
                           gate.T))
    return o.transpose(1, 0, 2).reshape(t, n_heads * hd) @ _f32(lp["wo"])


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


def route(m, router, top_k: int, route_scale: float):
    """``m [T, D]`` -> (gate matrix ``[T, E]``: a token's ``top_k``
    largest softmax scores divided by their sum and times
    ``route_scale``, zero elsewhere; the picks ``[T, top_k]``; the
    scores ``[T, E]``)."""
    s = jax.nn.softmax(m @ _f32(router), -1)
    vals, idx = jax.lax.top_k(s, top_k)
    vals = route_scale * vals / jnp.sum(vals, -1, keepdims=True)
    picked = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)   # [T, k, E]
    return jnp.sum(picked * vals[..., None], axis=1), idx, s


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


def ffn(x, lp, *, top_k: int, route_scale: float, eps: float):
    """The FFN branch of one layer, ``x [T, D]`` -> ``(branch [T, D],
    pick counts [E] over all experts, summed scores [E])``; a dense
    layer gives None for both counts."""
    m = _rmsnorm(x, lp["mlp_norm"], eps)
    if "router" not in lp:
        return swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
    gate, idx, s = route(m, lp["router"], top_k, route_scale)
    if lp["we_gate"].shape[0] < s.shape[-1]:
        # a share by itself: the gates carry no gradient to the router
        gate = jax.lax.stop_gradient(gate)
    counts = jnp.sum(jax.nn.one_hot(idx, s.shape[-1]), axis=(0, 1))
    y = routed(m, gate, lp) + swiglu(
        m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, counts, jnp.sum(s, axis=0)


def layer(x, lp, kind: str, n_heads: int, *, n_kv_heads: int,
          head_dim: int, top_k: int, sliding_window: int,
          rope_parameters: dict, route_scale: float = 1.0,
          eps: float = 1e-6, **_):
    """One whole block of ``kind``, ``x [T, D] -> (x [T, D], pick
    counts [E] or None, summed scores [E] or None)``."""
    x = x + attention(
        x, lp, kind, n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        sliding_window=sliding_window, rope_parameters=rope_parameters,
        eps=eps)
    y, counts, ssum = ffn(
        x, lp, top_k=top_k, route_scale=route_scale, eps=eps)
    return x + y, counts, ssum


def _sequence(params, ids, targets, kw):
    """One sequence ``ids, targets [T]`` -> (sum of the CE, pick counts
    ``[L_routed, E]``, summed scores ``[L_routed, E]``).
    ``kw["block"]`` (``jax.checkpoint``) wraps every layer call: a
    backward pass then holds one layer's intermediates at a time."""
    kw = dict(kw)
    wrap = kw.pop("block", None) or (lambda f: f)
    kinds = kw.pop("layer_types")
    heads = kw.pop("heads_per_layer")
    eps = kw.get("eps", 1e-6)
    x = _f32(params["embed"])[ids]
    counts, ssums = [], []
    for lp, kind, h in zip(params["layers"], kinds, heads, strict=True):
        x, c, s = wrap(
            lambda x, lp, kind=kind, h=h: layer(x, lp, kind, h, **kw))(x, lp)
        if c is not None:
            counts.append(c)
            ssums.append(s)

    @wrap
    def ce(hidden):
        logp = jax.nn.log_softmax(
            _rmsnorm(hidden, params["final_norm"], eps)
            @ _f32(params["lm_head"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    return ce(x), jnp.stack(counts), jnp.stack(ssums)


def loss_and_counts(params, inputs, targets, *, aux_coef: float = 0.0,
                    **kw):
    """``(loss, pick counts [L_routed, E] of the whole batch)`` over
    ``inputs/targets [B, T]``, one sequence at a time; the balance
    loss's moments are pooled over the batch first."""
    with jax.default_matmul_precision("highest"):
        one = jax.checkpoint(lambda args: _sequence(params, *args, kw))
        ce, counts, ssums = jax.lax.map(one, (inputs, targets))
        n = inputs.shape[0] * inputs.shape[1]
        n_experts = counts.shape[-1]
        counts = jax.lax.stop_gradient(counts.sum(0))     # [L_routed, E]
        f = counts / (n * kw["top_k"])
        p = ssums.sum(0) / n
        lb = jnp.mean(n_experts * jnp.sum(f * p, axis=-1))
        return jnp.sum(ce) / n + aux_coef * lb, counts


def loss(params, inputs, targets, **kw):
    """The training loss (module docstring) over ``inputs/targets [B,
    T]``."""
    return loss_and_counts(params, inputs, targets, **kw)[0]
