"""Plain reference of the ``nemotron_h`` decoder (NVIDIA-Nemotron-3-
Super-120B-A12B: a stack whose BLOCKS are each ONE sublayer — a
Mamba-2 mixer, a grouped-query attention without rotation or an expert
layer — with its one norm and its one residual add; squared-ReLU
experts of two products in a latent, a sigmoid router over all experts
with a selection bias, one shared expert at full width; an untied
head): forward, loss and, through ``jax.grad`` of ``loss``, gradients,
in float32 ``jax.numpy``, the recurrence a ``lax.scan`` over TOKENS —
no chunks, no kernels, no cache, no sort, and no import from
``theanompi_tpu``.

``h_0 = E[ids]`` on ``[T, D]`` (unscaled).  Block ``i`` of kind
``pattern[i]``: ``h <- h + f(rmsnorm(h; norm_i))``, eps 1e-5, no bias
anywhere but the convolution's::

    "M" (``H`` state heads of ``P`` channels, ``G`` groups of ``N``
         state channels, ``I = H P``; norm: ``attn_norm``):
      [z | xBC | dt] = a W_in        widths I | I + 2 G N | H
      xBC = silu(conv1d_causal(xBC; w [4, I + 2 G N], b))
                      depthwise, taps t-3..t: the LAST tap is the token
      [x | B | C] = xBC              widths I (H heads of P) | G N | G N
      dt = softplus(dt + dt_bias)    [T, H];   A = -exp(A_log)  [H]
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
                      a [P, N] state a head, S_{-1} = 0; a head reads
                      the B and C of its group
      y_t = S_t C_t + D x_t
      f = rmsnorm_groups(y * silu(z); ssm_norm [I]) W_out
                      the gate BEFORE the norm, the statistic over the
                      I / G channels of a group
    "*" (``Hq`` query heads over ``Hkv`` key/value heads of ``hd``;
         norm: ``attn_norm``):
      q = a Wq, k = a Wk, v = a Wv   NO rotation, no QK-norm
      f = softmax(hd ** -0.5 q k^T + causal) v Wo
    "E" (``E`` experts routed over, ``k`` picks; norm: ``mlp_norm``):
      s = sigmoid(a W_r)             float32, [T, E]
      picks = top-k of s + b         b: the selection bias (state)
      g = route_scale * s_picked / sum(s_picked)
      z = a W_dn                     [T, D_lat], the latent
      r = sum over the picks e of g_e relu(z W1_e)^2 W2_e
                      over the experts the tree HOLDS (experts [0,
                      held) of E: a pick of another adds nothing)
      f = r W_up + relu(a S1)^2 S2   the shared expert on ``a`` itself
    logits = rmsnorm(h_L; final_norm) W_head        UNTIED
    loss = mean cross-entropy over the rows the tree holds

A SHARE is computed as the whole is: the tree holds some of the
published heads (``wq [D, Hq hd]`` with fewer ``Hq``, ``ssm_in`` of
fewer heads and groups) and some of the experts, the keyword counts
say how many, and the partial ``W_out`` / ``Wo`` / routed product is
what goes on to the next block.  The vocabulary may be a slice:
``embed`` is what the tree holds, and ids, logits and loss run over
its rows.  The stack may be cut in depth: ``pattern`` names the blocks
the tree holds.

What is computed in blocks, none of which changes a value: the
recurrence runs ``TOKEN_BLOCK`` tokens at a time under
``jax.checkpoint``; attention one head at a time; the held experts one
at a time over ALL tokens (a token that did not pick an expert weighs
its product by zero); with ``block=jax.checkpoint`` every block call is
replayed in the backward.

Departures from the published model, each noted:

- The convolution's weight is held tap-major, ``[d_conv, channels]``
  (tap ``k`` multiplies position ``t - 3 + k``), where the published
  ``Conv1d`` holds ``[channels, 1, d_conv]``: the same numbers
  transposed.
- ``dt`` is not clipped (``time_step_limit`` ``(0, inf)``, the port's
  default; ``time_step_min`` / ``max`` / ``floor`` are the
  initialisation's).
- Attention is NOT rotated: the Nemotron-H family's attention blocks
  carry no positional embedding (the config's ``rope_theta`` and
  ``partial_rotary_factor`` are what its config class always writes).
- ``n_group`` 1 / ``topk_group`` 1 restrict nothing: the top-k is over
  all experts.  The routed experts' gates are a constant for the
  gradient where the tree holds only some of them (``held < E``): a
  share by itself holds its router (the program's rule, PERF.md §6,
  PR 37), so the router's leaf gets a zero gradient here as there.
- ``intermediate_size`` (2688) is unused: no ``-`` block in the
  pattern.  ``rescale_prenorm_residual`` is an initialisation rule.
- No dropout; the multi-token-prediction module is not here.

Weights are the program's parameter tree (they are data, made from the
seed): ``embed [V, D]``, ``final_norm``, ``lm_head [D, V]``,
``layers[i]``: an ``M`` block ``{attn_norm, ssm_in [D, 2 I + 2 G N +
H], ssm_conv_w [4, I + 2 G N], ssm_conv_b, ssm_dt_bias [H], ssm_a_log
[H], ssm_d [H], ssm_norm [I], ssm_out [I, D]}``, a ``*`` block
``{attn_norm, wq [D, Hq hd], wk, wv [D, Hkv hd], wo [Hq hd, D]}``, an
``E`` block ``{mlp_norm, router [D, E], w_lat_down [D, D_lat],
w_lat_up [D_lat, D], we_up [held, D_lat, F], we_down [held, F, D_lat],
ws_up [D, Fs], ws_down [Fs, D]}``.  A float32 product on a TPU runs in
reduced precision unless asked otherwise, so every entry point sets
``highest``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 128


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w, eps):
    ms = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * _f32(w)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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


def mamba(a, lp, *, mamba_n_heads: int, mamba_d_head: int,
          mamba_d_state: int, mamba_n_groups: int, eps: float):
    """An ``M`` block's branch on its normed input ``a [T, D]`` ->
    ``[T, D]``, over the heads and groups the tree holds."""
    t = a.shape[0]
    nh, p, n, g = mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups
    inner, gn = nh * p, g * n
    if lp["ssm_in"].shape[1] != 2 * inner + 2 * gn + nh:
        raise ValueError(
            f"ssm_in holds {lp['ssm_in'].shape[1]} columns: not [z | x B "
            f"C | dt] of {nh} heads of {p}, {g} groups of {n}")
    proj = a @ _f32(lp["ssm_in"])
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


def attention(a, lp, *, n_heads: int, n_kv_heads: int, head_dim: int):
    """A ``*`` block's branch on its normed input ``a [T, D]`` -> ``[T,
    D]``: no rotation, the scores times ``head_dim ** -0.5``."""
    t, hd = a.shape[0], head_dim
    if lp["wq"].shape != (a.shape[-1], n_heads * hd) or (
            lp["wk"].shape != (a.shape[-1], n_kv_heads * hd)):
        raise ValueError(
            f"the weights hold wq {lp['wq'].shape}, wk {lp['wk'].shape}: "
            f"not {n_heads} query and {n_kv_heads} key/value heads of {hd}")
    q = (a @ _f32(lp["wq"])).reshape(t, n_heads, hd)
    k = (a @ _f32(lp["wk"])).reshape(t, n_kv_heads, hd)
    v = (a @ _f32(lp["wv"])).reshape(t, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                   # [T, hd]
        s = hd ** -0.5 * (qh @ kh.T)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1) @ vh

    o = jax.lax.map(head, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(t, n_heads * hd) @ _f32(lp["wo"])


def route(a, lp, *, top_k: int, route_scale: float, select_bias=None):
    """``(gates [T, k], picks [T, k], scores [T, E])`` of an ``E``
    block's router on its normed input ``a [T, D]``."""
    s = jax.nn.sigmoid(a @ _f32(lp["router"]))
    chosen = s if select_bias is None else s + _f32(select_bias)
    _, picks = jax.lax.top_k(chosen, top_k)
    g = jnp.take_along_axis(s, picks, -1)
    return route_scale * g / jnp.sum(g, -1, keepdims=True), picks, s


def routed(a, lp, *, top_k: int, route_scale: float, select_bias=None,
           first: int = 0):
    """The routed experts' part of an ``E`` block's branch, ``[T, D]``:
    the tree's ``held`` experts are experts ``[first, first + held)``
    of the ``E`` the router scores (``first`` 0 in the program: its
    rank's share; the tie of the shares to the whole walks it)."""
    gates, picks, _ = route(a, lp, top_k=top_k, route_scale=route_scale,
                            select_bias=select_bias)
    n_experts, held = lp["router"].shape[1], lp["we_up"].shape[0]
    if held < n_experts:        # a share by itself holds its router
        gates = jax.lax.stop_gradient(gates)
    z = a @ _f32(lp["w_lat_down"])

    def one(r, e):
        w1, w2, idx = e
        g = jnp.sum(jnp.where(picks == idx, gates, 0.0), -1)   # 0: no pick
        return r + g[:, None] * (relu2(z @ _f32(w1)) @ _f32(w2)), None

    r, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(z),
        (lp["we_up"], lp["we_down"], first + jnp.arange(held)))
    return r @ _f32(lp["w_lat_up"])


def shared(a, lp):
    """The shared expert's part: ``relu(a S1)^2 S2`` at full width."""
    return relu2(a @ _f32(lp["ws_up"])) @ _f32(lp["ws_down"])


def block(h, lp, kind: str, *, eps: float = 1e-5, n_heads: int,
          n_kv_heads: int, head_dim: int, top_k: int, route_scale: float,
          select_bias=None, **mamba_kw):
    """One whole block of ``kind``, ``h [T, D] -> [T, D]``."""
    if kind == "M":
        return h + mamba(
            _rmsnorm(h, lp["attn_norm"], eps), lp, eps=eps, **mamba_kw)
    if kind == "*":
        return h + attention(
            _rmsnorm(h, lp["attn_norm"], eps), lp, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim)
    assert kind == "E", kind
    a = _rmsnorm(h, lp["mlp_norm"], eps)
    return h + routed(a, lp, top_k=top_k, route_scale=route_scale,
                      select_bias=select_bias) + shared(a, lp)


def _stack(params, ids, kw):
    """One sequence ``ids [T]`` through the blocks: ``(h_L [T, D],
    pick counts [expert blocks, E])`` — how many of the sequence's
    ``k T`` picks went to each of ALL experts, a row an ``E`` block
    (no gradient).  ``kw["block"]`` (``jax.checkpoint``) wraps every
    block call; ``kw["select_bias"]`` ``[expert blocks, E]``: a row an
    ``E`` block, in order (None: zeros)."""
    kw = dict(kw)
    wrap = kw.pop("block", None) or (lambda f: f)
    pattern = kw.pop("pattern")
    bias = kw.pop("select_bias", None)
    if len(pattern) != len(params["layers"]):
        raise ValueError(
            f"pattern names {len(pattern)} blocks, the tree holds "
            f"{len(params['layers'])}")
    rows = iter(() if bias is None else bias)
    counts = []
    h = _f32(params["embed"])[ids]
    for lp, kind in zip(params["layers"], pattern):
        more = {}
        if kind == "E":
            if bias is not None:
                more["select_bias"] = next(rows)
            _, picks, _ = route(
                _rmsnorm(h, lp["mlp_norm"], kw.get("eps", 1e-5)), lp,
                top_k=kw["top_k"], route_scale=kw["route_scale"], **more)
            counts.append(jnp.sum(jax.nn.one_hot(
                picks, lp["router"].shape[1], dtype=jnp.float32), (0, 1)))
        h = wrap(lambda h, lp, kind=kind, more=more: block(
            h, lp, kind, **kw, **more))(h, lp)
    return h, jax.lax.stop_gradient(jnp.stack(counts)) if counts else None


def sequence_logits(params, ids, kw):
    """One sequence ``ids [T]`` -> logits ``[T, V]`` over the rows the
    tree holds."""
    h, _ = _stack(params, ids, kw)
    return _rmsnorm(
        h, params["final_norm"], kw.get("eps", 1e-5)) @ _f32(params["lm_head"])


def _sequence(params, ids, targets, kw):
    """``(the sum of one sequence's cross-entropies, its pick
    counts)``."""
    wrap = kw.get("block") or (lambda f: f)

    @wrap
    def ce(h):
        logits = _rmsnorm(h, params["final_norm"], kw.get("eps", 1e-5)) @ (
            _f32(params["lm_head"]))
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    h, counts = _stack(params, ids, kw)
    return ce(h), counts


def bias_update(bias, counts, rate: float):
    """The selection bias after a step that counted ``counts [expert
    blocks, E]`` picks: ``rate`` toward balance by the sign alone."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(counts, -1, keepdims=True) - counts)


def logits(params, ids, **kw):
    """Logits ``[T, V]`` of one sequence ``ids [T]``."""
    with jax.default_matmul_precision("highest"):
        return sequence_logits(params, ids, kw)


def loss_and_counts(params, inputs, targets, **kw):
    """``(mean next-token cross-entropy over inputs/targets [B, T],
    the batch's pick counts [expert blocks, E])``, one sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        one = jax.checkpoint(lambda args: _sequence(params, *args, kw))
        ce, counts = jax.lax.map(one, (inputs, targets))
        return jnp.sum(ce) / inputs.size, (
            None if counts is None else jnp.sum(counts, 0))


def loss(params, inputs, targets, **kw):
    """Mean next-token cross-entropy over ``inputs/targets [B, T]``."""
    return loss_and_counts(params, inputs, targets, **kw)[0]
