"""Mixture-of-Experts FFN with expert parallelism over the ``expert``
mesh axis (new-framework scope — SURVEY §2.2 row "EP/MoE", absent
upstream; the TPU-native design follows the GShard/Switch capacity
formulation because it is the one that keeps every shape static for
XLA).

Design:

- **Routing** is a per-token softmax over ``E`` experts in fp32 with
  deterministic top-k selection; the selected gates are renormalized
  to sum to one (the Mixtral convention) so an all-identical-experts
  MoE reproduces its dense FFN exactly — the anchor the unit tests
  assert.  A SIGMOID router picks by ``score + bias`` and gates by the
  scores alone, so ``top_k``'s own values are no use to it: its picked
  scores ride the ONE sort that picks them, as a second result behind
  the key (``_picked_scores``), and are never gathered element by
  element afterwards (PERF.md, PR 56).
- **Dispatch** is capacity-based and *slot-major*: every token's
  1st-choice slot is ranked before any token's 2nd choice, positions
  come from one cumulative sum over a [k·N, E] one-hot, and tokens
  beyond an expert's capacity ``C`` are dropped (their combine weight
  is zero — the residual stream carries them unchanged, as in Switch).
  The buffers are built by ONE int32 scatter + ONE row gather instead
  of the [N, E, C] one-hot einsums of the original GShard formulation
  — same math, none of the O(N·E·C) HBM traffic.
- **Expert parallelism**: with the ``expert`` mesh axis sized ``ep``,
  each device owns ``E/ep`` experts; one ``lax.all_to_all`` ships the
  per-expert capacity buffers to the owning devices and a second one
  ships the outputs back — XLA rides these on ICI like every other
  collective.  Expert weights compose with **TP** (``model`` axis) the
  Megatron way: gate/up column-sharded on the FFN dim, down row-sharded
  with the closing psum.
- **Aux losses**: the Switch load-balance loss
  ``E · Σ_e f_e · P_e`` (== 1 at perfect balance, any k) and the
  router z-loss ``mean(logsumexp(logits)²)``, returned separately so
  the model applies its own coefficients.

- **Dropless dispatch** (``capacity_factor=None``; ``ep == 1``): the
  published fine-grained models (64 experts, 8 picks) compute every
  pick, and zero drops on the capacity path would need ``C = N`` —
  an [E, N, D] buffer and E/k times the useful products.  Instead
  the ``k·N`` (token, pick) rows are sorted by expert id (stable, so
  slot-major inside an expert: the result does not depend on a
  tie-break), the rows gathered in that order, and the three SwiGLU
  products run as GROUPED products whose work follows the rows,
  forward and both backward products.  On a TPU, where the shapes
  tile, they are the kernels of ``ops/grouped_matmul.py`` over ONE
  tile plan built from the per-expert row counts per layer call
  (PERF.md, PR 31); elsewhere — CPU tests, the CPU mesh, odd shapes —
  ``lax.ragged_dot`` with the same counts, for its CPU lowering,
  autodiff and vma rules (``_tile_plan`` chooses, and logs a
  ``ragged_dot`` choice once per shape).  Each row's gate
  multiplies its hidden activations
  in fp32 inside the ``silu·up`` fusion (the down product is linear),
  so the combine is the plain fp32 sum of a token's ``k`` rows, read
  through the inverse permutation.  Dispatch (token rows out to
  expert order) and combine (expert order back, summed) are each
  other's transpose and carry each other as backward rule: gathers
  both ways, never a scatter-add.
- **A held range of the experts** (``held``: one expert-parallel
  rank's share of the layer, by itself): its rows are a prefix of the
  sorted picks, a quarter or an eighth of them at balance.  Only the
  sort's int32 arrays stay ``k·N`` long; every float array is laid
  out at ``R = held_rows_bound(k·N, held, E)`` rows, twice the
  balanced share, and a token's sum is formed slot by slot from
  those.  Exact for every routing: the layer runs in a loop over
  windows of ``R`` sorted rows that near balance makes ONE pass and
  for a routing past ``R`` as many as hold its rows
  (``_held_windows``); no pick is ever skipped.  Where a window is a
  larger source than XLA gathers fast from (``_sum_in_kernel``) the
  picks are sorted by (expert, token) and a token's sum is formed by
  the kernel of ``ops/held_rows_sum.py`` over the window's rows.
- **Two forms of expert**: a SwiGLU (gate, up and down products),
  or, where the layer is given no gate weights (``we_gate`` ``None``),
  ``relu2(x W_up) W_down`` — TWO products (``_gate_up`` gives no gate
  product and every tuple of ``(rows, gate, up)`` below holds ``None``
  in its place, through the loop's carry and the backward rule
  alike); ``shared_expert`` likewise.
- **Experts in a latent** (``moe_ffn``'s ``latent``): the router reads
  the block's input at full width, the experts its projection ``x
  w_down`` — ONE product before the dispatch, so the sort gathers rows
  of the latent's width — and the picks' sum goes back up through
  ``w_up``; both under the scope ``moe_latent``.
- **What the layer's remat may keep** (``MOE_RESIDUALS``): the
  dropless path names its gathered sorted rows, their gate and up
  products (under a held range the first window's, which the forward
  loop leaves in its carry) and the sort's two results; a
  ``jax.checkpoint`` whose policy saves them replays none of these,
  one whose policy does not rebuilds them as before
  (``Llama.remat_keep_calls`` keeps them for the calls that fit).
- **Spans**: ``jax.named_scope``s ``moe_route`` (router, top-k, aux
  moments), ``moe_dispatch`` (plan + row gather / capacity buffers),
  ``moe_experts`` (the products; inside it ``moe_tile_plan``, the
  kernels' tile plan), ``moe_combine`` (un-permute, gates) and, around
  a latent's two projections, ``moe_latent`` are in
  the metadata of every instruction of the layer, forward and
  backward (docs/OBSERVABILITY.md).

Capacity per device-expert is ``C = ceil(cf · k · N / E)`` rounded up
to a multiple of 8 (TPU sublane) where ``N`` is the LOCAL token count:
drops are layout-dependent exactly as in GShard (each shard ranks its
own tokens).  ``cf >= E/k`` guarantees zero drops (C == N) — the
setting the cross-layout invariance tests use.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from theanompi_tpu.ops import attention
from theanompi_tpu.ops import grouped_matmul as gmm
from theanompi_tpu.ops import held_rows_sum as hrs
from theanompi_tpu.ops.layers import swiglu
from theanompi_tpu.parallel.mesh import EXPERT_AXIS, MODEL_AXIS

logger = logging.getLogger(__name__)


def moe_capacity(
    n_tokens: int, n_experts: int, top_k: int, capacity_factor: float
) -> int:
    """Static per-expert capacity for ``n_tokens`` local tokens.

    Always a multiple of 8 (TPU sublane): the ``n_tokens`` clamp
    rounds UP to the next multiple, so a capacity near the token
    count may slightly exceed it — harmless (extra slots stay
    unfilled; zero-drop guarantees only need C >= N)."""
    c = int(-(-capacity_factor * top_k * n_tokens // n_experts))
    c = -(-c // 8) * 8  # sublane-align the buffer's token dim
    return max(8, min(c, -(-n_tokens // 8) * 8))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _picked_scores(scores, chosen, top_k: int):
    """``(take_along_axis(scores, eidx), eidx)`` for ``eidx`` the
    ``lax.top_k`` of ``chosen`` (``[N, E]`` float32 both), bit for bit
    and tie for tie, WITHOUT the element gather: ONE stable sort along
    the experts carries the scores and their indices behind the key.
    The key is whole numbers — a float's bits, the negative ones
    flipped, order as the floats do, ``~`` turns the order round — so
    equal ``chosen`` are equal keys and the stable sort leaves the
    lower index first, as ``top_k`` does (``chosen`` is never ``-0``:
    a sigmoid is not, and ``s + b`` rounds to ``+0`` where it is
    zero).  A gather of ``k N`` single elements costs 10 ns each on a
    TPU, 3.68 ms at 22 picks of 512 for 16 384 tokens beside a sort of
    1.46 (PERF.md, PR 56).  ``chosen`` only picks and gets no
    gradient."""
    return _picked_scores_fwd(scores, chosen, top_k)[0]


def _picked_scores_fwd(scores, chosen, top_k):
    bits = lax.bitcast_convert_type(chosen, jnp.int32)
    key = ~jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    iota = lax.broadcasted_iota(jnp.int32, chosen.shape, chosen.ndim - 1)
    _, picked, eidx = lax.sort(
        (key, scores, iota), dimension=-1, num_keys=1, is_stable=True
    )
    picked, eidx = picked[..., :top_k], eidx[..., :top_k]
    return (picked, eidx), (eidx, jnp.arange(chosen.shape[-1], dtype=jnp.int32))


def _picked_scores_bwd(top_k, res, cts):
    """The gather's transpose — the cotangent of pick j of token n
    added at ``[n, eidx[n, j]]`` — as a compare and a sum over the k
    picks (a token's picks differ, so one term of each sum is not
    zero): no scatter, and not ``lax.sort``'s own rule, which
    gathers."""
    eidx, experts = res
    hit = eidx[..., None] == experts
    return jnp.sum(jnp.where(hit, cts[0][..., None], 0.0), axis=-2), None


_picked_scores.defvjp(_picked_scores_fwd, _picked_scores_bwd)


def router_topk(x2, w_router, top_k: int, renormalize: bool = True, *,
                scoring: str = "softmax", select_bias=None,
                scale: float = 1.0):
    """fp32 router: returns (gates [N,k], expert ids [N,k], probs
    [N,E], logits [N,E]).  ``x2`` is [N, D].

    ``scoring="softmax"``: the top-k of a softmax over the experts,
    renormalised, times ``scale`` (a routed scaling factor).
    ``scoring="sigmoid"``: every expert's score is its own sigmoid;
    the picks are the top-k of ``score + select_bias`` (``[E]``, read
    without a gradient: it is state a rule moves, see
    ``select_bias_step``), the gates the picked SCORES, without the
    bias, renormalised (``+ 1e-20``) and times ``scale``; ``probs``
    are then the scores.  The picked scores come out of the sort that
    picks (``_picked_scores``: the scores are an operand of it), bit
    for bit what ``take_along_axis(scores, eidx)`` gives, ties
    included."""
    logits = x2.astype(jnp.float32) @ w_router.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen = scores
        if select_bias is not None:
            chosen = scores + lax.stop_gradient(
                select_bias.astype(jnp.float32)
            )
        gates, eidx = _picked_scores(scores, chosen, top_k)
        if renormalize:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return gates * scale, eidx, scores, logits
    assert scoring == "softmax", scoring
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, top_k)          # [N, k]
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if scale != 1.0:
        gates = gates * scale
    return gates, eidx, probs, logits


def select_bias_step(bias, f, picks: int, rate: float):
    """The selection bias after a step: ``bias [.., E]`` moved by
    ``rate`` toward balance, ``+`` for an expert that got fewer than
    the mean of the step's ``picks`` (token, pick) rows and ``-`` for
    one that got more, by the sign alone.  ``f [.., E]`` are the pick
    fractions ``moe_ffn`` gives (global over the batch axes, so every
    replica moves alike); the counts are whole numbers again before
    they are compared, so an expert exactly at the mean stays."""
    counts = jnp.round(f * picks)
    return bias + rate * jnp.sign(picks / f.shape[-1] - counts)


def aux_moments(eidx, probs, n_experts: int, batch_axes=()):
    """The load-balance loss's LINEAR moments: ``f`` [E] — fraction of
    (token, slot) picks routed to each expert (a constant wrt the
    gradient, as in Switch) — and ``p`` [E] — mean router probability.

    ``batch_axes`` names the mesh axes the token batch is sharded
    over: ``f`` and ``p`` are then GLOBAL means (two [E]-sized
    pmeans).  This makes the downstream product the true global
    balance objective — and exactly layout-invariant, where the
    per-shard product (mean_s Σ f_s·p_s) carries an f/p covariance
    term that changes with the sharding."""
    n, k = eidx.shape
    counts = jnp.sum(
        jax.nn.one_hot(eidx, n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    f = lax.stop_gradient(counts) / (n * k)
    p = jnp.mean(probs, axis=0)
    if batch_axes:
        f = lax.pmean(f, batch_axes)
        p = lax.pmean(p, batch_axes)
    return f, p


def load_balance_loss(eidx, probs, n_experts: int, batch_axes=()):
    """Switch-style aux loss over all k picks: ``E · Σ_e f_e · P_e``
    (see ``aux_moments``).  Equals 1.0 when both are uniform."""
    f, p = aux_moments(eidx, probs, n_experts, batch_axes)
    return n_experts * jnp.sum(f * p)


def router_z_loss(logits, batch_axes=()):
    """``mean(logsumexp(logits)²)`` — keeps router logits from
    drifting large (ST-MoE); coefficient applied by the caller.
    Globally token-averaged when ``batch_axes`` is given."""
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return lax.pmean(z, batch_axes) if batch_axes else z


def relu2(u):
    """``relu(u) ** 2`` (the published ``mlp_hidden_act: relu2``), in
    float32."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32)))


def _gate_up(rows, we_gate, we_up, product):
    """The gate and the up product of rows laid out for ``product(lhs,
    w)`` (batched over capacity buffers, or grouped over sorted rows).
    ``we_gate`` ``None`` (experts of TWO products, ``relu2(rows W_up)
    W_down``): no gate product, ``(None, u)``."""
    dt = rows.dtype
    if we_gate is None:
        return None, product(rows, we_up.astype(dt))
    return product(rows, we_gate.astype(dt)), product(rows, we_up.astype(dt))


def _gated_down(g, u, we_down, product, row_scale=None):
    """``silu(g) * u`` — without a gate product ``relu2(u)`` — through
    the down product.  ``row_scale`` (fp32, one per row) multiplies
    the hidden activations inside their own fusion: the down product
    is linear, so scaling its input rows scales its output rows."""
    dt = u.dtype
    h = relu2(u) if g is None else jax.nn.silu(g) * u
    if row_scale is not None:
        h = h.astype(jnp.float32) * row_scale[:, None]
    return product(h.astype(dt), we_down.astype(dt))


def _swiglu_experts(rows, we_gate, we_up, we_down, product, row_scale=None):
    """The expert products on ``rows``: ``_gate_up`` and
    ``_gated_down``."""
    return _gated_down(
        *_gate_up(rows, we_gate, we_up, product), we_down, product, row_scale
    )


def shared_expert(x, w_gate, w_up, w_down, model_axis=MODEL_AXIS):
    """The expert EVERY token goes through, beside the routed ones: a
    dense SwiGLU — ``w_gate`` ``None``: ``relu2(x W_up) W_down``, two
    products — with no routing and no gate, under the scope
    ``moe_shared``.  ``w_gate``/``w_up`` ``[D, F_loc]`` column-sharded
    and ``w_down`` ``[F_loc, D]`` row-sharded over ``model_axis``
    (``None``: replicated)."""
    with jax.named_scope("moe_shared"):
        dt = x.dtype
        if w_gate is None:
            h = relu2(x @ w_up.astype(dt)).astype(dt)
        else:
            h = swiglu(x @ w_gate.astype(dt), x @ w_up.astype(dt))
        y = h @ w_down.astype(dt)
        return lax.psum(y, model_axis) if model_axis is not None else y


# -- dropless: sorted rows, grouped products -------------------------------
#
# ``order`` lists the slot-major picks (pick ``j*N + t`` is token t's
# j-th choice) in expert order; ``inv`` is its inverse permutation.
# Dispatch and combine are each other's transpose, and each carries
# the other as its backward rule: a gather by a permutation and a
# gather from the N token rows, never the scatter-add autodiff would
# write.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gather_sorted(x2, order, inv, k, groups=0):
    """``x2 [N, D]`` -> the rows ``order`` lists (all ``k·N`` picks, or
    a window of ``R`` of them) in expert order: row ``i`` is token
    ``order[i] % N``.  ``groups``: ``_sum_picks``'."""
    return x2[order % x2.shape[0]]


def _gather_sorted_fwd(x2, order, inv, k, groups):
    return _gather_sorted(x2, order, inv, k, groups), (order, inv)


def _gather_sorted_bwd(k, groups, res, ct):
    with jax.named_scope("moe_dispatch"):
        return _sum_picks(ct, *res, k, groups).astype(ct.dtype), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sum_picks(rows, order, inv, k, groups=0):
    """Rows in expert order -> each token's ``k`` rows summed in fp32,
    ``[N, D]``.  ``rows`` are all ``k·N`` sorted rows, or a window of
    ``R`` of them (``order`` is then as short, and ``inv [k·N]`` each
    pick's place in the window): a pick sorted outside it adds a zero
    row, and the sum is formed slot by slot, a gather of ``N`` rows
    each, so nothing ``k·N`` rows long is written.  ``groups`` > 0
    (``_sum_in_kernel``): the window's rows are sorted by (expert,
    token) over that many experts, ``order`` is -1 for a row that is
    no held pick, and the sum runs in ``ops/held_rows_sum.py``'s
    kernel."""
    r, n = rows.shape[0], inv.shape[0] // k
    if groups:
        rows, tok = gmm.same_vma(
            (rows, jnp.where(order >= 0, order % n, -1))
        )[0]
        return hrs.held_rows_sum(rows, tok, n, groups)
    if r == k * n:
        return jnp.sum(
            rows[inv].reshape(k, -1, rows.shape[-1]).astype(jnp.float32),
            axis=0,
        )
    # (plain lax on arrays kept [1, N, .], and as few operations a slot
    # as there can be: the set-up seconds follow their number, three
    # sums a layer call and k slots a sum; PERF.md, PR 44)
    d = rows.shape[-1]
    at = inv.reshape(k, n, 1)
    here = lax.split((at >= 0) & (at < r), (1,) * k)
    at = lax.split(lax.clamp(0, at, r - 1), (1,) * k)
    rows_of = lax.GatherDimensionNumbers(
        offset_dims=(2,), collapsed_slice_dims=(0,), start_index_map=(0,))
    # (typed as the rows are ONCE, not by every slot's select and add)
    (zero, y), _ = gmm.same_vma(
        (jnp.zeros((1, n, d), rows.dtype), jnp.zeros((1, n, d), jnp.float32)),
        rows,
    )
    for at_j, here_j in zip(at, here):      # slot order, as the sum above
        row = lax.gather(rows, at_j, rows_of, (1, d),
                         mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        row = lax.select(lax.broadcast_in_dim(here_j, row.shape, (0, 1, 2)),
                         row, zero)
        y = y + row.astype(jnp.float32)
    return y.reshape(n, d)


def _sum_picks_fwd(rows, order, inv, k, groups):
    # (an empty slice carries the rows' dtype to the backward rule)
    return _sum_picks(rows, order, inv, k, groups), (order, inv, rows[:0])


def _sum_picks_bwd(k, groups, res, ct):
    order, inv, like = res
    with jax.named_scope("moe_combine"):
        return (_gather_sorted(ct.astype(like.dtype), order, inv, k, groups),
                None, None)


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)
_sum_picks.defvjp(_sum_picks_fwd, _sum_picks_bwd)


@jax.custom_vjp
def _permute(v, perm, inv_perm):
    """``v[perm]`` for a permutation, with its inverse at hand."""
    return v[perm]


_permute.defvjp(
    lambda v, perm, inv_perm: (v[perm], inv_perm),
    lambda inv_perm, ct: (ct[inv_perm], None, None),
)


@functools.lru_cache(maxsize=None)
def _log_ragged_choice(rows: int, d: int, f: int, dtype: str,
                       on_tpu: bool) -> None:
    """One log line per shape — a warning on TPU, where XLA's own
    grouped kernels run at half the matrix unit's rate (PERF.md,
    PR 31).  ``lru_cache`` is the once-only latch."""
    logger.log(
        logging.WARNING if on_tpu else logging.INFO,
        "moe: lax.ragged_dot for %d rows x %d x %d %s — %s",
        rows, d, f, dtype,
        "no aligned tile divides these shapes" if on_tpu
        else "not on TPU devices",
    )


def _window_sizes(group_sizes, start, rows: int):
    """How many of each group's sorted rows lie in ``[start, start +
    rows)``."""
    ends = jnp.cumsum(group_sizes) - start
    return (jnp.clip(ends, 0, rows)
            - jnp.clip(ends - group_sizes, 0, rows)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _window_plan(group_sizes, window, rows: int, block_rows: int):
    """The prefix tile plan of window ``window`` (``rows`` sorted rows
    each) of a held layer call.  One jitted function for the plan
    made outside the layer's loop and the one in it, forward and
    backward, of every layer call: traced once a process and lowered
    once a program, where each call site traced ``make_tile_plan``'s
    ninety operations again (the set-up seconds follow them; PERF.md,
    PR 44)."""
    return gmm.make_tile_plan(
        _window_sizes(group_sizes, window * rows, rows), rows, block_rows,
        prefix=True,
    )


def _tile_plan(group_sizes, rows: int, d: int, f: int, dtype,
               prefix: bool = False, *, window=None):
    """The ONE tile plan of a layer call's three grouped products over
    ``rows`` sorted rows, shared forward, replay and backward — on a
    TPU whose shapes the repo's kernels tile; ``None`` where
    ``lax.ragged_dot`` runs them.  ``prefix``: the groups cover only
    the first ``sum(group_sizes)`` rows (a held range of the experts);
    the products of the rows past them are zero, forward and backward
    (the kernels' prefix plan; off the TPU ``lax.ragged_dot``'s own
    lowering; refused on a TPU whose shapes do not tile).  ``window``:
    the plan of sorted rows ``[window * rows, (window + 1) * rows)`` of
    a held layer call's groups (``_window_plan``)."""
    on_tpu = attention._on_tpu()
    if (on_tpu and gmm.shapes_tile(rows, d, f, dtype)
            and gmm.shapes_tile(rows, f, d, dtype)):
        with jax.named_scope("moe_tile_plan"):
            plan = (
                gmm.make_tile_plan(group_sizes, rows, gmm.tile_rows(rows),
                                   prefix=prefix)
                if window is None else
                _window_plan(group_sizes, window, rows, gmm.tile_rows(rows))
            )
            return checkpoint_name(plan, gmm.TILE_PLAN_RESIDUAL)
    if prefix and on_tpu:
        # XLA's own grouped kernels on the chip compute the rows past
        # ``sum(group_sizes)`` with the last group's weights (PERF.md,
        # PR 37), where the CPU's lowering gives zeros
        raise NotImplementedError(
            f"a held range of the experts on a TPU needs shapes the "
            f"grouped kernels tile (rows {rows}, widths {d} and {f}, "
            f"{dtype}): lax.ragged_dot there does not leave the rows "
            f"past its groups zero"
        )
    _log_ragged_choice(rows, d, f, str(dtype), on_tpu)
    return None


class _GroupedProduct:
    """``product(lhs [rows, .], w [E, ., .])`` of a layer call: the
    kernels over ``plan``, or ``lax.ragged_dot`` where there is none
    (``_tile_plan``).  ``raw``: a product under a held range's prefix
    plan as the kernel wrote it, whose rows past the held ones are
    zeros only once read through ``held()`` — the select then fuses
    into the reader, and what a layer call keeps of the product is
    the kernel's own output."""

    def __init__(self, group_sizes, plan):
        self.group_sizes, self.plan = group_sizes, plan

    def __call__(self, lhs, w, raw: bool = False):
        if self.plan is None:
            return lax.ragged_dot(lhs, w, self.group_sizes)
        return gmm.grouped_matmul(lhs, w, self.plan,
                                  raw=raw and self.plan.prefix)

    def held(self, out):
        return out if self.plan is None else gmm.held_rows(out, self.plan)


def _grouped_product(group_sizes, rows: int, d: int, f: int, dtype,
                     prefix: bool = False):
    """The ``_GroupedProduct`` over the plan ``_tile_plan`` builds
    HERE."""
    return _GroupedProduct(
        group_sizes, _tile_plan(group_sizes, rows, d, f, dtype, prefix)
    )


# A held range's rows are a prefix of the sorted picks, ``k·N · held /
# E`` of them at balance.  Twice that is the static length every float
# array of the layer has (``held_rows_bound``): a router that keeps
# within a few percent of balance (a share by itself holds its router;
# PERF.md, PR 37) never passes it, and for one that does the layer's
# loop takes further passes over the rows past it (``_held_windows``).
_BOUND_OVER_BALANCE = 2


def held_rows_bound(picks: int, held: int | None, n_experts: int) -> int:
    """``R``: how many of a layer call's ``picks`` sorted (token, pick)
    rows the dropless layer lays out at a time when it holds experts
    ``[0, held)`` of ``n_experts`` — ``_BOUND_OVER_BALANCE`` times the
    held experts' rows at balance, rounded up to the grouped kernels'
    row tile, and ``picks`` where that leaves nothing to skip (``held``
    is ``None``, every expert is held, the held ones are half of them
    or more).  A function of what the layer is called with, not a
    knob; ``obs/routing.py`` counts a step's layer calls against it."""
    if held is None or _BOUND_OVER_BALANCE * held >= n_experts:
        return picks
    tile = gmm.tile_rows(picks) or 8
    rows = -(-_BOUND_OVER_BALANCE * picks * held // n_experts)
    return min(picks, -(-rows // tile) * tile)


# XLA gathers at the memory's rate only from a source its memory-space
# assignment keeps on the chip: on the v5e the step lies between 108
# and 126 MB, and past it a row costs 41 ns, real or masked (PERF.md,
# PR 44).  A window of sorted rows larger than this is summed by the
# kernel of ``ops/held_rows_sum.py``.
_ON_CHIP_SOURCE_BYTES = 96 * 2 ** 20


def _sum_in_kernel(rows: int, n: int, d: int, dtype) -> bool:
    """Whether a held layer call sums its tokens' rows out of a window
    of ``rows`` sorted rows in the kernel: on a TPU, where the window
    is a larger source than XLA gathers fast from and the kernel's
    blocks divide the shapes.  The sort then goes by (expert, token),
    which is what the kernel's static grid counts on."""
    return (attention._on_tpu()
            and rows * d * jnp.dtype(dtype).itemsize > _ON_CHIP_SOURCE_BYTES
            and hrs.shapes_tile(rows, n, d))


# What a call of the dropless layer names for the layer's remat
# (``jax.ad_checkpoint.checkpoint_name``; ``Llama.remat_keep_calls``
# says which calls' policy saves them): the gathered sorted rows, their
# gate and up products (two-product experts: the one, under the up
# product's name) — under a held range the FIRST window's — and the
# sort's two results.  A call that keeps them replays no gather of
# its rows, neither grouped product and neither sort; ``silu(g) * u``,
# the masters' casts, the router and the down product's operand stay
# replayed.  The capacity path names none.
MOE_RESIDUALS = ("moe_rows", "moe_gate", "moe_up", "moe_order", "moe_inv")


def _named(arrays, names):
    """``arrays`` under ``names`` for the layer's remat; ``None`` (the
    gate product of two-product experts) stays ``None``."""
    return tuple(
        a if a is None else checkpoint_name(a, name)
        for a, name in zip(arrays, names)
    )


def _sorted_rows(x2, we_gate, we_up, order, inv, product, *, k: int,
                 groups: int = 0):
    """The first half of the sorted rows' way through the layer: the
    rows ``order`` lists gathered, their gate and their up product,
    ``(rows [., D], g [., F], u [., F])`` — the three arrays of
    ``MOE_RESIDUALS``, named on the values the second half and the
    products' backward read."""
    with jax.named_scope("moe_dispatch"):
        rows = checkpoint_name(
            _gather_sorted(x2, order, inv, k, groups), MOE_RESIDUALS[0]
        )
    with jax.named_scope("moe_experts"):
        g, u = _gate_up(rows, we_gate, we_up,
                        functools.partial(product, raw=True))
        return (rows, *_named((g, u), MOE_RESIDUALS[1:3]))


def _sorted_sum(g, u, gates, we_down, order, inv, product, *, k: int,
                model_axis, groups: int = 0):
    """The second half: each row's gate folded into ``silu(g) * u``,
    the down product, and each token's rows summed.  ``[N, D]`` fp32."""
    with jax.named_scope("moe_dispatch"):
        row_gate = _permute(gates.T.reshape(-1), order, inv)
    with jax.named_scope("moe_experts"):
        out = _gated_down(g if g is None else product.held(g),
                          product.held(u), we_down, product,
                          row_scale=row_gate)
        if model_axis is not None:
            out = lax.psum(out, model_axis)               # close row-parallel
    with jax.named_scope("moe_combine"):
        return _sum_picks(out, order, inv, k, groups)


def _sorted_experts(x2, gates, we_gate, we_up, we_down, order, inv, product,
                    *, k: int, model_axis):
    """All ``k·N`` sorted rows through the layer (``order`` every pick,
    ``inv`` its inverse): ``_sorted_rows`` and ``_sorted_sum`` over
    the grouped ``product()``.  ``[N, D]`` fp32."""
    with jax.named_scope("moe_experts"):
        grouped = product()
    _, g, u = _sorted_rows(x2, we_gate, we_up, order, inv, grouped, k=k)
    return _sorted_sum(g, u, gates, we_down, order, inv, grouped, k=k,
                       model_axis=model_axis)


# A held layer call runs over windows of ``bound`` sorted rows, each
# over ``(floats, rest)`` = ``((x2, we_gate, we_up, we_down), (gates,
# order, inv, group_sizes, plan))``: ``order`` padded to whole windows,
# ``inv`` each pick's place among ALL sorted rows, ``plan`` window 0's,
# made where the layer's remat keeps it (a later window makes its
# own).  ``w`` is the window, a traced counter.

def _window_picks(w, rest, *, bound: int, groups: int):
    """``(order, inv)`` of window ``w``: its ``bound`` picks and each
    pick's place in it (a pick outside it adds nothing to its token's
    sum).  ``groups``: ``_sum_picks``'."""
    _, order, inv, sizes, _ = rest
    with jax.named_scope("moe_dispatch"):
        order = lax.dynamic_slice(order, (w * bound,), (bound,))
        if groups:          # the kernel's sum: no token past the held rows
            place = w * bound + jnp.arange(bound, dtype=jnp.int32)
            order = jnp.where(place < jnp.sum(sizes), order, -1)
        return order, inv - w * bound


def _window_product(w, floats, rest, *, bound: int):
    """The grouped product over window ``w``'s rows."""
    x2, _, we_up, _ = floats
    *_, sizes, plan = rest
    with jax.named_scope("moe_experts"):
        if plan is None:                # off the TPU: lax.ragged_dot
            return _GroupedProduct(
                _window_sizes(sizes, w * bound, bound), None)
        return _GroupedProduct(None, lax.cond(
            w == 0, lambda: plan, lambda: _tile_plan(
                sizes, bound, *we_up.shape[1:], x2.dtype, prefix=True,
                window=w)))


def _window(w, kept, floats, rest, *, k: int, bound: int, groups: int):
    """Window ``w`` through the layer: ``(its part of every token's
    sum, its rows, gate and up products)`` — the latter only where the
    loop's carry ``kept`` holds such arrays (the rule's forward pass;
    ``()`` in a forward pass nothing differentiates)."""
    x2, we_gate, we_up, we_down = floats
    order, inv = _window_picks(w, rest, bound=bound, groups=groups)
    product = _window_product(w, floats, rest, bound=bound)
    rows, g, u = _sorted_rows(x2, we_gate, we_up, order, inv, product,
                              k=k, groups=groups)
    y = _sorted_sum(g, u, rest[0], we_down, order, inv, product, k=k,
                    model_axis=None, groups=groups)
    return y, (rows, g, u) if kept else ()


def _window_bwd(w, kept, floats, rest, ct, *, k: int, bound: int,
                groups: int):
    """Window ``w`` transposed: ``(ct``'s gradient to ``floats``, the
    window's rows, gate and up products).  Window 0 reads the three
    from the carry ``kept``, where the forward pass left them (or the
    layer's remat rebuilt them); a window past the first rebuilds its
    own.  ``silu(g) * u`` and the down product's operand are replayed
    either way, neither grouped product's forward kernel is."""
    x2, we_gate, we_up, we_down = floats
    order, inv = _window_picks(w, rest, bound=bound, groups=groups)
    product = _window_product(w, floats, rest, bound=bound)
    # (behind a barrier: XLA otherwise moves the replayed ``silu``'s
    # exponential INTO the branches, a fourth array a call)
    rows, g, u = lax.optimization_barrier(lax.cond(
        w == 0, lambda: kept, lambda: _sorted_rows(
            x2, we_gate, we_up, order, inv, product, k=k, groups=groups)))
    # the second half replayed and transposed at (g, u) ...
    dg, du, d_down = jax.vjp(jax.checkpoint(
        lambda g, u, we_down: _sorted_sum(
            g, u, rest[0], we_down, order, inv, product, k=k,
            model_axis=None, groups=groups),
        prevent_cse=False), g, u, we_down)[1](ct)
    # ... and the first half's two products at ``rows``: a grouped
    # product's own rule reads its operands alone
    with jax.named_scope("moe_experts"):
        d_rows, d_gate, d_up = jax.vjp(
            lambda *operands: _gate_up(
                *operands, functools.partial(product, raw=True)),
            rows, we_gate, we_up)[1]((dg, du))
    with jax.named_scope("moe_dispatch"):
        dx2 = _sum_picks(d_rows, order, inv, k, groups).astype(x2.dtype)
    return (dx2, d_gate, d_up, d_down), (rows, g, u)


def _over_windows(one, zero, kept, floats, rest, *more, bound: int,
                  last_first: bool = False, **how):
    """``zero`` plus the first result of ``one(w, kept, floats, rest,
    *more)`` over the windows of ``bound`` sorted rows that hold rows
    of the held experts — window 0 always, the others only for a
    routing past the bound — and the last pass's second result, which
    each pass hands the next as ``kept``.  ``last_first``: window 0
    is the LAST pass (its ``kept`` is what the loop leaves).  ONE loop
    and one traced body for both: a second body for the windows past
    the first — traced, lowered, its kernels compiled and loaded
    beside the first, never run near balance — was 3 to 4 s of a run's
    set-up (PERF.md, PR 44)."""
    *_, group_sizes, _ = rest
    windows = jnp.maximum(1, -(-jnp.sum(group_sizes) // bound))

    def another(carry):
        i, acc, kept = carry
        add, kept = one(windows - 1 - i if last_first else i, kept, floats,
                        rest, *more, bound=bound, **how)
        return i + 1, jax.tree.map(lax.add, acc, add), kept

    # (the sums typed as the operands are, under a checked shard_map)
    zero, kept = gmm.same_vma((zero, kept), floats)[0]
    return lax.while_loop(
        lambda carry: carry[0] < windows, another,
        (jnp.zeros((), jnp.int32), zero, kept),
    )[1:]


def _windows(k, bound, groups, floats, rest, keep: bool):
    """A held layer call, exact for every routing: the first ``bound``
    sorted rows through the layer, and — only where the held experts'
    rows pass them — the next ``bound`` and so on, each window's part
    of every token's sum added (``_over_windows``).  Near balance the
    loop runs one pass; at worst (every pick held) ``k·N / bound`` of
    them.  No array is ever longer than ``bound``.  ``keep``: window
    0's rows, gate and up products come back beside the sum, through
    the loop's carry (each pass writes its own over the last one's and
    window 0 is the last pass: no copy, no second body; the carry
    starts as buffers nobody fills, ``lax.empty`` — no pass reads the
    one before — where zeros were 0.9 ms a call of Mellum's step,
    PERF.md, PR 51)."""
    x2, we_gate, we_up, _ = floats
    f = we_up.shape[2]
    kept = tuple(
        None if width is None else lax.empty((bound, width), x2.dtype)
        for width in (x2.shape[1], None if we_gate is None else f, f)
    ) if keep else ()
    return _over_windows(_window, jnp.zeros(x2.shape, jnp.float32), kept,
                         floats, rest, k=k, bound=bound, groups=groups,
                         last_first=True)


def _all_windows(k, bound, groups, floats, rest):
    """``_windows``' sum.  Differentiated by a rule of its own
    (``_held_windows``; a ``while_loop`` has none): the forward pass
    also gives window 0's three arrays of ``MOE_RESIDUALS``, named, so
    that a layer call whose remat saves them transposes window 0 from
    them and one whose remat does not rebuilds them in its replay;
    every window past the first is rebuilt and transposed in the
    backward loop, the windows' gradients summed in their order.
    ``rest`` gets none: the gates of a held share carry no gradient
    (``moe_ffn``)."""
    return _windows(k, bound, groups, floats, rest, keep=False)[0]


def _held_windows_fwd(k, bound, groups, floats, rest):
    y, kept = _windows(k, bound, groups, floats, rest, keep=True)
    return y, (floats, rest, _named(kept, MOE_RESIDUALS[:3]))


def _held_windows_bwd(k, bound, groups, res, ct):
    floats, rest, kept = res
    return _over_windows(_window_bwd, jax.tree.map(jnp.zeros_like, floats),
                         kept, floats, rest, ct, k=k, bound=bound,
                         groups=groups)[0], None


_held_windows = jax.custom_vjp(_all_windows, nondiff_argnums=(0, 1, 2))
_held_windows.defvjp(_held_windows_fwd, _held_windows_bwd)


def _dropless_experts(x2, gates, eidx, we_gate, we_up, we_down, *,
                      n_experts: int, model_axis, held: int | None = None):
    """Every pick computed: sort, gather, grouped SwiGLU with each
    row's gate folded into its hidden activations, and the sum of a
    token's rows.  Returns ``y [N, D]`` fp32.

    ``held``: only experts ``[0, held)`` of the ``n_experts`` routed
    over are here (one expert-parallel rank's share, by itself).  The
    picks are sorted over ALL experts as ever, so the held experts'
    rows are a prefix of the sorted rows; the groups are those
    experts' alone, the products compute that prefix and give zeros
    past it, and a pick of an expert not held adds exactly zero to
    its token's sum, forward and backward.  Nothing stands in for the
    ranks that hold the rest.

    What is ``k·N`` long: the sort's three int32 arrays (``order``,
    ``inv``, the picks' expert ids).  Every float array — the gathered
    rows ``[., D]``, their gates, the products' ``[., F]`` and ``[.,
    D]``, their gradients — is ``R = held_rows_bound(k·N, held,
    n_experts)`` long: ``k·N`` with all experts here, and under a held
    range twice that range's rows at balance.  The held rows of a
    routing that passes ``R`` are not skipped: the loop the layer
    runs in (``_held_windows``; one pass near balance) goes on over
    the next ``R`` sorted rows, and the next, until it has them all,
    so ``aux["dropped"]`` stays 0 by construction.  Inside an expert
    the rows stay slot-major; where the tokens' sums run in the kernel
    (``_sum_in_kernel``) they go token by token instead, the order its
    visits count on."""
    n, _ = x2.shape
    k = eidx.shape[1]
    bound = held_rows_bound(k * n, held, n_experts)
    if held is not None:
        n_experts = held        # the groups: the experts that are here
    # where the tokens' sums run in the kernel: how many experts' rows
    # it counts on, sorted by (expert, token)
    groups = (n_experts if bound < k * n
              and _sum_in_kernel(bound, *x2.shape, x2.dtype) else 0)
    with jax.named_scope("moe_dispatch"):
        flat_e = eidx.T.reshape(-1).astype(jnp.int32)     # slot-major [k*N]
        picks = jnp.arange(k * n, dtype=jnp.int32)
        # stable: inside an expert the rows stay slot-major (or, for
        # the kernel, go token by token), so the order (and the sums
        # below) never hang on a tie-break
        _, order = lax.sort(
            (flat_e * n + picks % n if groups else flat_e, picks),
            num_keys=1, is_stable=True)
        _, inv = lax.sort((order, picks), num_keys=1)
        # (named: a call whose remat keeps them replays neither sort)
        order = checkpoint_name(order, MOE_RESIDUALS[3])
        inv = checkpoint_name(inv, MOE_RESIDUALS[4])
        group_sizes = jnp.sum(
            flat_e[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None],
            axis=0, dtype=jnp.int32,
        )
    shape = (*we_up.shape[1:], x2.dtype)
    if bound == k * n:
        return _sorted_experts(
            x2, gates, we_gate, we_up, we_down, order, inv,
            lambda: _grouped_product(group_sizes, k * n, *shape,
                                     prefix=held is not None),
            k=k, model_axis=model_axis,
        )
    if (k * n) % bound:
        with jax.named_scope("moe_dispatch"):
            order = jnp.pad(order, (0, -(k * n) % bound))     # whole windows
    with jax.named_scope("moe_experts"):
        # the first window's plan, made where the layer's remat keeps
        # it (a later window makes its own, in the loop)
        plan = _tile_plan(group_sizes, bound, *shape, prefix=True, window=0)
    # one type for every operand and every window's result under a
    # checked shard_map, cast OUTSIDE the backward rule: the cast's own
    # transpose sums a gradient over the axes its operand did not vary
    # on (``gmm.same_vma``), and no collective runs in the loop.  The
    # leaves are cast out here too: their gradients then come back as
    # the kernels write them, in the compute dtype
    floats, gates = gmm.same_vma(
        (x2, *(w if w is None else w.astype(x2.dtype)
               for w in (we_gate, we_up, we_down))),
        lax.stop_gradient(gates),
    )
    y = _held_windows(
        k, bound, groups, floats, (gates, order, inv, group_sizes, plan)
    )
    if model_axis is not None:
        with jax.named_scope("moe_experts"):
            y = lax.psum(y, model_axis)                   # close row-parallel
    return y


# -- capacity: fixed buffers, drops ------------------------------------------

def _capacity_experts(x2, gates, eidx, we_gate, we_up, we_down, *,
                      n_experts: int, capacity: int, ep: int, expert_axis,
                      model_axis):
    """GShard/Switch dispatch into ``[E, C, D]`` buffers; picks beyond
    an expert's capacity are dropped.  Returns ``(y [N, D] fp32,
    dropped picks of this shard)``."""
    n, d = x2.shape
    top_k = eidx.shape[1]
    e, c = n_experts, capacity
    with jax.named_scope("moe_dispatch"):
        # -- slot-major dispatch plan (all int32, one cumsum) --------------
        # slot-major flatten: slot j's block holds every token's j-th
        # pick, so capacity ranks all 1st choices before any 2nd choice
        flat_e = eidx.T.reshape(-1)                       # [k*N]
        onehot = (
            flat_e[:, None] == jnp.arange(e, dtype=flat_e.dtype)[None, :]
        ).astype(jnp.int32)                               # [k*N, E]
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1
        )[:, 0]                                           # rank within expert
        keep = pos < c
        dest = jnp.where(keep, flat_e * c + pos, e * c)   # e*c = drop sentinel
        tok = jnp.arange(top_k * n, dtype=jnp.int32) % n  # slot-major token

        # inverse plan: which token fills each (expert, capacity) slot
        # (0 = empty; only the sentinel slot ever collides)
        src = jnp.zeros((e * c + 1,), jnp.int32).at[dest].set(tok + 1)
        src = src[: e * c]
        filled = src > 0
        buf = jnp.where(
            filled[:, None],
            x2[jnp.maximum(src - 1, 0)],
            jnp.zeros((), x2.dtype),
        ).reshape(e, c, d)

        # -- ship buffers to the expert owners -----------------------------
        if ep > 1:
            # [E, C, D] -> [E/ep, ep*C, D]: each device keeps its own
            # experts' rows from every peer in the expert group
            buf = lax.all_to_all(
                buf, expert_axis, split_axis=0, concat_axis=1, tiled=True
            )

    # -- expert SwiGLU (batched matmuls; TP over the FFN dim) --------------
    with jax.named_scope("moe_experts"):
        out = _swiglu_experts(
            buf, we_gate, we_up, we_down,
            lambda lhs, w: jnp.einsum("ecd,edf->ecf", lhs, w),
        )
        if model_axis is not None:
            out = lax.psum(out, model_axis)               # close row-parallel

    # -- ship outputs home + weighted combine ------------------------------
    with jax.named_scope("moe_combine"):
        if ep > 1:
            out = lax.all_to_all(
                out, expert_axis, split_axis=1, concat_axis=0, tiled=True
            )
        out_pad = jnp.concatenate(
            [out.reshape(e * c, d), jnp.zeros((1, d), out.dtype)]
        )
        contrib = out_pad[dest].astype(jnp.float32)       # dropped -> zero row
        w = gates.T.reshape(-1) * keep                    # [k*N] fp32
        y = jnp.sum(
            (contrib * w[:, None]).reshape(top_k, n, d), axis=0
        )
        return y, jnp.sum(~keep).astype(jnp.float32)


def moe_ffn(
    x,
    w_router,
    we_gate,
    we_up,
    we_down,
    *,
    n_experts: int,
    top_k: int = 2,
    capacity_factor: float | None = 1.25,
    expert_axis: str | None = EXPERT_AXIS,
    model_axis: str | None = MODEL_AXIS,
    batch_axes: tuple = (),
    renormalize: bool = True,
    scoring: str = "softmax",
    select_bias=None,
    route_scale: float = 1.0,
    held: int | None = None,
    latent=None,
):
    """The mixture-of-experts FFN on local token shards (call inside
    shard_map): routed experts that are SwiGLUs (three products), or,
    with ``we_gate`` ``None``, ``relu2(x W_up) W_down`` (two).

    - ``x``: [B, T_loc, D] activations (any float dtype; expert
      matmuls run in ``x.dtype``, routing/combine in fp32).
    - ``w_router``: [D, E] replicated.
    - ``we_gate``/``we_up``: [E_loc, D, F_loc]; ``we_down``:
      [E_loc, F_loc, D] — expert-sharded over ``expert_axis``,
      FFN-dim-sharded over ``model_axis`` (either may be ``None`` /
      size-1 for a replicated layout).
    - ``capacity_factor``: a number sizes the capacity buffers (picks
      beyond them are dropped); ``None`` is the dropless path (every
      pick computed; ``ep == 1`` only).
    - ``renormalize``: selected gates rescaled to sum to one (Mixtral)
      or left as the softmax gave them (OLMoE, ``norm_topk_prob``
      false).
    - ``scoring``, ``select_bias``, ``route_scale``: the router's form
      (``router_topk``): a softmax top-k, or sigmoid scores picked
      under a selection bias ``[E]`` and scaled.
    - ``held``: the leaves hold experts ``[0, held)`` of the
      ``n_experts`` routed over — one expert-parallel rank's share of
      the layer, computed by itself (dropless, no ``expert`` axis):
      the returned ``y`` is the held experts' part of every token's
      sum, and ``aux["f"]`` stays the pick fractions over ALL experts.
      With ``held < n_experts`` the gates carry no gradient to the
      router (below): a share by itself holds it.  With less than
      half the experts held the layer's float arrays are
      ``held_rows_bound(k·N, held, n_experts)`` rows long, not
      ``k·N``; a routing that sends the held experts more rows than
      that is computed all the same, ``R`` rows at a time
      (``_dropless_experts``), and ``aux["dropped"]`` stays 0.
    - ``latent``: ``(w_down [D, D_lat], w_up [D_lat, D])``, replicated:
      the ROUTER reads ``x`` at full width and the experts its
      projection ``x w_down`` (the expert leaves are ``D_lat`` wide):
      one product before the dispatch, so the gathered rows are
      ``D_lat`` wide, and one after the picks' sum, ``y = (sum of the
      picks) w_up``; both under the scope ``moe_latent``.

    Returns ``(y [B, T_loc, D], aux)`` with ``aux = {"lb": load
    balance loss, "z": router z-loss, "f": [E] pick fractions, "p":
    [E] mean router probs, "dropped": picks no expert computed}``,
    all globalized over ``batch_axes`` (the
    mesh axes sharding the token batch) so they are exactly
    layout-invariant — see ``load_balance_loss``.  ``f``/``p`` are the
    LINEAR moments behind ``lb``: a caller that splits one batch into
    microbatches (pipeline parallelism) should average them across the
    microbatches first and form ``E·Σ f·p`` after, which keeps the
    loss independent of the microbatch count too.
    """
    b, t, d = x.shape
    n = b * t
    e = n_experts
    x2 = x.reshape(n, d)

    ep = lax.axis_size(expert_axis) if expert_axis is not None else 1
    assert e % ep == 0, f"n_experts {e} must divide by ep {ep}"
    if held is None:
        assert we_up.shape[0] == e // ep, (
            f"expert leaf holds {we_up.shape[0]} experts, expected "
            f"{e}/{ep} = {e // ep}"
        )
    else:
        assert capacity_factor is None and ep == 1, (
            "a held range of the experts runs dropless and by itself "
            "(capacity_factor=None, no expert axis)"
        )
        assert we_up.shape[0] == held <= e, (we_up.shape, held, e)

    with jax.named_scope("moe_route"):
        gates, eidx, probs, logits = router_topk(
            x2, w_router, top_k, renormalize, scoring=scoring,
            select_bias=select_bias, scale=route_scale,
        )
        if held is not None and held < e:
            # on a rank of a group the gates' gradient comes back
            # from all ``e`` experts; of a share by itself only from
            # the held ones, and every such gradient says "prefer
            # these" (two thirds of the picks within 70 steps: PERF.md,
            # PR 37).  A part of that gradient is worse than none
            gates = lax.stop_gradient(gates)
        f, p = aux_moments(eidx, probs, e, batch_axes)
        aux = {
            "f": f,
            "p": p,
            "lb": e * jnp.sum(f * p),
            "z": router_z_loss(logits, batch_axes),
        }

    if latent is not None:
        with jax.named_scope("moe_latent"):
            x2 = x2 @ latent[0].astype(x2.dtype)

    if capacity_factor is None:
        assert ep == 1, (
            "dropless MoE (capacity_factor=None) runs with ep == 1 only: "
            "expert parallelism needs a ragged all-to-all"
        )
        y = _dropless_experts(
            x2, gates, eidx, we_gate, we_up, we_down,
            n_experts=e, model_axis=model_axis, held=held,
        )
        dropped = jnp.zeros((), jnp.float32)    # by construction
    else:
        y, dropped = _capacity_experts(
            x2, gates, eidx, we_gate, we_up, we_down, n_experts=e,
            capacity=moe_capacity(n, e, top_k, capacity_factor), ep=ep,
            expert_axis=expert_axis, model_axis=model_axis,
        )
        if batch_axes:
            dropped = lax.psum(dropped, batch_axes)
    aux["dropped"] = dropped
    y = y.astype(x.dtype)
    if latent is not None:
        with jax.named_scope("moe_latent"):
            y = y @ latent[1].astype(x.dtype)
    return y.reshape(b, t, d), aux
