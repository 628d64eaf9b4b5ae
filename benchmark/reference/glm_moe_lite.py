"""Plain reference of the ``glm4_moe_lite`` decoder (GLM-4.7-Flash:
latent attention, one leading dense layer, expert layers with a
shared expert beside the routed ones under a sigmoid router with a
selection bias, one multi-token-prediction module): forward, loss,
through ``jax.grad`` of ``loss`` gradients, and the selection bias's
rule, in float32 ``jax.numpy``, no kernels, no sort, no cache, and no
import from ``theanompi_tpu``.

Per layer on ``x [T, D]``, ``H`` heads (eps 1e-5, theta 1e6)::

    h      = rmsnorm(x; attn_norm)
    c_q    = rmsnorm(h W_qa; q_a_norm)
    q      = c_q W_qb -> [H, nope + rope] = [q_nope | q_rope]
    q_rope = rope(q_rope)
    [c_kv | k_r] = h W_kva                  [kv_rank | rope]
    c_kv   = rmsnorm(c_kv; kv_a_norm);  k_r = rope(k_r)
                                            ONE k_r a token, all heads
    [k_nope | v] = c_kv W_kvb -> [H, nope | v_dim]
    k      = [k_nope | k_r]
    x      = x + softmax(q k^T / sqrt(nope + rope) + causal) v  W_o
    h      = rmsnorm(x; mlp_norm)
    a dense layer (no router among its leaves):
             x = x + W_d( silu(W_g h) * (W_u h) )
    an expert layer:
             s = sigmoid(h W_r)             float32, E experts
             (e_j), j = 1..k = top-k of (s + b)
                                  b: the selection bias, no gradient
             g_j = scale * s[e_j] / (sum_j s[e_j] + 1e-20)
             x = x + Shared(h) + sum_{j : e_j held} g_j Expert_{e_j}(h)
    after the step, per expert layer:
             b_e += rate * sign(mean_e'(c_e') - c_e)
                      c: the step's picks per expert over ALL E
    main head: loss_main = mean_i CE(rmsnorm(x_L; final_norm) W_head, t_{i+1})
    MTP:  h'_i = [rmsnorm(Emb(t_{i+1}); enorm) | rmsnorm(x_L,i; hnorm)] W_eh
          y    = ExpertLayer(h')            its own weights and bias
          loss_mtp = sum_i CE(rmsnorm(y; head_norm) W_head, t_{i+2}) / N
                      a sequence's last position has no t_{i+2}: weight 0
    loss = loss_main + mtp_coef * loss_mtp

``x_L`` is the last layer's output BEFORE ``final_norm``; ``Emb`` and
``W_head`` are the main model's.

**One rank's share.**  The weights may hold only experts ``[0,
held)`` of the ``E`` the router scores (``we_* [held, ...]``): the
router, its top-k, the gates' renormalisation over all ``k`` picks
and the counts ``c`` stay over all ``E``; the sum over the picks runs
over the held experts alone, as the equation says.  With ``held ==
E`` it is the whole layer.  With ``held < E`` the gates are read
without a gradient: of a group's rank the gradient of ALL ``E``
experts' gates comes back to the router, of a share by itself only
the held experts', which would pull every pick towards them (it did:
two thirds of the picks within 70 steps, PERF.md, PR 37); such a
share holds its router and the bias rule alone moves the selection.
The vocabulary may be a slice as well:
``Emb`` and ``W_head`` are what the tree holds, and the ids come from
that range.

The routed sum is computed as the definition reads: a dense ``[T,
held]`` gate matrix, zero outside a token's picks, times the outputs
of ALL held experts, a block of tokens at a time; attention one head
at a time (``[T, T]`` scores).  Neither blocking changes a value.

Departures from the published model, each noted:

- RoPE rotates ADJACENT pairs (x[2i], x[2i+1]) where the Hugging Face
  port rotates (x[i], x[i + rope/2]): the same function under a fixed
  permutation of the rotary columns of W_qb and W_kva.  With weights
  from a seed nothing distinguishes the layouts; the program under
  test uses the adjacent one.
- ``rate`` (0.001) and ``mtp_coef`` (0.3) are not in ``config.json``
  (``topk_method: noaux_tc`` and ``num_nextn_predict_layers: 1`` name
  the mechanisms): DeepSeek-V3's report, sections 2.1-2.2 and 4.2,
  and the GLM-4.5 report.  Both are arguments.
- ``loss_mtp`` divides by all ``N`` positions, the weightless last
  one of each sequence among them (the program's row weights).
- The input of ``W_eh`` is the embedding's norm first, the hidden
  state's second (DeepSeek-V3's order).
- The family's sequence-wise balance loss (coefficient 1e-4) is left
  out: coefficient 0.
- ``n_group = topk_group = 1``: no group-limited routing to write.

Weights are the program's parameter tree (they are data, made from
the seed): ``embed [V, D]``, ``layers[i]{attn_norm, wq_a, q_a_norm,
wq_b, wkv_a, kv_a_norm, wkv_b, wo, mlp_norm}`` and either ``{w_gate,
w_up, w_down}`` or ``{router [D, E], we_gate [held, D, F], we_up,
we_down [held, F, D], ws_gate [D, Fs], ws_up, ws_down [Fs, D]}``,
``final_norm``, ``lm_head [D, V]``, ``mtp{enorm, hnorm, eh_proj [2D,
D], block{...an expert layer...}, head_norm}``.  ``bias [L_e, E]``
holds a row an expert layer, the MTP block's last.  A float32
product on a TPU runs in reduced precision unless asked otherwise,
so every entry point sets ``highest``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 256


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _rope(x, pos, theta):
    """x [T, H, r], pos [T]: rotate adjacent pairs by pos * theta^(-2i/r)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def attention(x, lp, *, n_heads: int, qk_nope: int, qk_rope: int,
              v_dim: int, eps: float, theta: float):
    """The attention branch of one layer, ``x [T, D] -> [T, D]``
    (the residual not added)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rmsnorm(x, lp["attn_norm"], eps)
    cq = _rmsnorm(h @ _f32(lp["wq_a"]), lp["q_a_norm"], eps)
    q = (cq @ _f32(lp["wq_b"])).reshape(t, n_heads, qk_nope + qk_rope)
    q = jnp.concatenate(
        [q[..., :qk_nope], _rope(q[..., qk_nope:], pos, theta)], -1
    )
    rank = lp["kv_a_norm"].shape[0]
    ckv = h @ _f32(lp["wkv_a"])
    k_r = _rope(ckv[:, None, rank:], pos, theta)            # [T, 1, rope]
    kv = (_rmsnorm(ckv[:, :rank], lp["kv_a_norm"], eps)
          @ _f32(lp["wkv_b"])).reshape(t, n_heads, qk_nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :qk_nope], jnp.broadcast_to(k_r, (t, n_heads, qk_rope))], -1
    )
    v = kv[..., qk_nope:]
    causal = pos[:, None] >= pos[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                   # [T, .]
        s = qh @ kh.T / jnp.sqrt(jnp.float32(qk_nope + qk_rope))
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1) @ vh

    a = jax.lax.map(head, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
    return a.transpose(1, 0, 2).reshape(t, n_heads * v_dim) @ _f32(lp["wo"])


def route(h, router, bias, top_k: int, scale: float):
    """``h [T, D]`` -> (gate matrix ``[T, E]``: ``scale * s / (sum of
    the token's picked s + 1e-20)`` at its ``top_k`` picks by ``s +
    bias`` and zero elsewhere; the picks ``[T, top_k]``; the scores)."""
    s = jax.nn.sigmoid(h @ _f32(router))
    chosen = s if bias is None else s + jax.lax.stop_gradient(_f32(bias))
    _, idx = jax.lax.top_k(chosen, top_k)
    picked = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=1)
    total = jnp.sum(picked * s, axis=-1, keepdims=True)
    return scale * picked * s / (total + 1e-20), idx, s


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


def routed(h, gate, lp):
    """sum over the HELD experts e of ``gate[:, e] * expert_e(h)``:
    all of them on every token, a block of tokens at a time."""
    wg, wu, wd = _f32(lp["we_gate"]), _f32(lp["we_up"]), _f32(lp["we_down"])
    t, d = h.shape
    gate = gate[:, :wg.shape[0]]
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


def ffn(x, lp, bias, *, top_k: int, scale: float, eps: float):
    """The FFN branch of one layer, ``x [T, D]`` -> ``(branch [T, D],
    shared part, routed part, pick counts [E] or None)``; the branch
    is the sum of the two parts (a dense layer has only the first)."""
    h = _rmsnorm(x, lp["mlp_norm"], eps)
    if "router" not in lp:
        y = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return y, y, jnp.zeros_like(y), None
    gate, idx, s = route(h, lp["router"], bias, top_k, scale)
    if lp["we_gate"].shape[0] < s.shape[-1]:
        # a share by itself: the gates carry no gradient to the router
        gate = jax.lax.stop_gradient(gate)
    shared = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    part = routed(h, gate, lp)
    counts = jnp.sum(jax.nn.one_hot(idx, s.shape[-1]), axis=(0, 1))
    return shared + part, shared, part, counts


def layer(x, lp, bias=None, *, n_heads: int, qk_nope: int, qk_rope: int,
          v_dim: int, top_k: int, scale: float, eps: float = 1e-5,
          theta: float = 1e6, **_):
    """One whole block, ``x [T, D] -> (x [T, D], pick counts or None)``."""
    x = x + attention(x, lp, n_heads=n_heads, qk_nope=qk_nope,
                      qk_rope=qk_rope, v_dim=v_dim, eps=eps, theta=theta)
    y, _, _, counts = ffn(x, lp, bias, top_k=top_k, scale=scale, eps=eps)
    return x + y, counts


def _sequence(params, bias, ids, targets, kw):
    """One sequence ``ids, targets [T]`` -> (sum of the main CE, sum
    of the MTP CE over the positions that have a token after next,
    pick counts ``[L_e, E]``).  ``kw["block"]`` (``jax.checkpoint``)
    wraps every layer call: a backward pass then holds one layer's
    intermediates at a time."""
    kw = dict(kw)
    wrap = kw.pop("block", None) or (lambda f: f)
    eps = kw.get("eps", 1e-5)
    embed, head = _f32(params["embed"]), _f32(params["lm_head"])
    rows = iter(bias) if bias is not None else None

    def block(x, lp):
        row = None
        if "router" in lp and rows is not None:
            row = next(rows)
        return wrap(lambda x, lp, row: layer(x, lp, row, **kw))(x, lp, row)

    x = embed[ids]
    counts = []
    for lp in params["layers"]:
        x, c = block(x, lp)
        if c is not None:
            counts.append(c)

    @wrap
    def ce(hidden, norm, labels):
        logp = jax.nn.log_softmax(_rmsnorm(hidden, norm, eps) @ head, -1)
        return -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]

    main = jnp.sum(ce(x, params["final_norm"], targets))
    after = jnp.zeros(())
    if "mtp" in params:
        mp = params["mtp"]
        both = jnp.concatenate([
            _rmsnorm(embed[targets], mp["enorm"], eps),
            _rmsnorm(x, mp["hnorm"], eps),
        ], -1)
        y, c = block(both @ _f32(mp["eh_proj"]), mp["block"])
        if c is not None:
            counts.append(c)
        # position i is held to t_{i+2} = targets[i + 1]
        after = jnp.sum(ce(y, mp["head_norm"], jnp.roll(targets, -1))[:-1])
    return main, after, jnp.stack(counts) if counts else jnp.zeros((0, 0))


def loss_and_counts(params, inputs, targets, *, bias=None,
                    mtp_coef: float = 0.3, **kw):
    """``(loss, pick counts [L_e, E] of the whole batch)`` over
    ``inputs/targets [B, T]``, one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        one = jax.checkpoint(
            lambda args: _sequence(params, bias, *args, kw)
        )
        main, after, counts = jax.lax.map(one, (inputs, targets))
        n = inputs.shape[0] * inputs.shape[1]
        total = (jnp.sum(main) + mtp_coef * jnp.sum(after)) / n
        return total, jax.lax.stop_gradient(counts.sum(0))


def loss(params, inputs, targets, **kw):
    """The training loss (module docstring) over ``inputs/targets [B,
    T]``; ``bias`` defaults to none (zeros: the start)."""
    return loss_and_counts(params, inputs, targets, **kw)[0]


def bias_update(bias, counts, rate: float = 0.001):
    """The selection bias after a step whose picks were ``counts [L_e,
    E]``: ``rate`` up for an expert under the layer's mean, down for
    one over it, unchanged at it."""
    counts = _f32(counts)
    return _f32(bias) + rate * jnp.sign(
        jnp.mean(counts, -1, keepdims=True) - counts
    )
