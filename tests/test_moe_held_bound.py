"""The dropless expert layer under a held range lays its float arrays
out at a static bound ``R`` of the sorted rows (``held_rows_bound``)
and is exact all the same: it runs in a loop over windows of ``R``
sorted rows, one pass near balance and as many as hold the held rows
past it (``parallel/moe.py`` ``_held_windows``).  Held here to the full-length
path, written out plainly below as it ran before the bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from theanompi_tpu.obs import routing
from theanompi_tpu.parallel import moe

# 40 tokens x 4 picks over 16 experts, 4 of them held: 160 sorted rows
# (no kernel tile divides them: the bound rounds to 8), 40 held at
# balance, R = 80
N, D, F, E, K, HELD = 40, 16, 8, 16, 4, 4
PICKS = K * N
R = moe.held_rows_bound(PICKS, HELD, E)

# how many of its 4 picks each token gives the held experts
ROUTINGS = {
    "no_held_pick": [0] * N,
    "balance": [1] * N,
    "held_rows_equal_the_bound": [2] * N,
    "one_row_past_the_bound": [3] + [2] * (N - 1),
    "every_pick_held": [4] * N,
}


def _picks(held_picks, seed=0, held=HELD, e=E):
    """``eidx [N, K]``: token t picks ``held_picks[t]`` distinct held
    experts and the rest from those not held, in a shuffled slot
    order."""
    rng = np.random.default_rng(seed)
    rows = []
    for h in held_picks:
        row = np.concatenate([
            rng.choice(held, h, replace=False),
            held + rng.choice(e - held, K - h, replace=False),
        ])
        rows.append(rng.permutation(row))
    return jnp.asarray(np.stack(rows), jnp.int32)


def _operands(dtype, seed=1, held=HELD):
    rng = np.random.default_rng(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    gates = jnp.asarray(rng.uniform(0.1, 1.0, (N, K)), jnp.float32)
    return (arr(N, D), arr(held, D, F, scale=0.25),
            arr(held, D, F, scale=0.25), arr(held, F, D, scale=0.25)), gates


def full_length(x2, we_gate, we_up, we_down, gates, eidx):
    """The held layer as it ran before the bound: every array ``k·N``
    rows long, plain indexing, autodiff's own backward."""
    n, k = eidx.shape
    flat_e = eidx.T.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.argsort(order)
    sizes = jnp.sum(flat_e[:, None] == jnp.arange(we_gate.shape[0])[None],
                    axis=0, dtype=jnp.int32)
    rows = x2[order % n]
    gate = gates.T.reshape(-1)[order]
    dt = x2.dtype
    h = (jax.nn.silu(lax.ragged_dot(rows, we_gate, sizes))
         * lax.ragged_dot(rows, we_up, sizes))
    h = (h.astype(jnp.float32) * gate[:, None]).astype(dt)
    out = lax.ragged_dot(h, we_down, sizes)
    return jnp.sum(out[inv].reshape(k, n, -1).astype(jnp.float32), axis=0)


def bounded(x2, we_gate, we_up, we_down, gates, eidx, e=E):
    return moe._dropless_experts(
        x2, gates, eidx, we_gate, we_up, we_down, n_experts=e,
        model_axis=None, held=we_gate.shape[0],
    )


def _value_and_grads(layer, floats, gates, eidx):
    def loss(*floats):
        y = layer(*floats, gates, eidx)
        return jnp.sum(jnp.sin(y)), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                       has_aux=True)(*floats)
    return (y, *grads)


def test_bound_is_twice_the_balanced_share_or_all_the_rows():
    assert R == 80 < PICKS
    assert moe.held_rows_bound(131072, 16, 64) == 65536       # Mellum's cell
    assert moe.held_rows_bound(65536, 8, 64) == 16384         # GLM's
    # rounded UP to the kernels' row tile
    assert moe.held_rows_bound(2048, 3, 64) == 512
    assert moe.held_rows_bound(144, 1, 8) == 40
    # nothing to skip: all the rows
    for held in (None, 64, 32, 40):
        assert moe.held_rows_bound(131072, held, 64) == 131072


@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_bounded_layer_equals_the_full_length_path(routing, dtype, tol):
    """``y`` and the gradients to ``x`` and to the three expert leaves,
    on both sides of the bound and at its edge."""
    eidx = _picks(ROUTINGS[routing])
    n_held = int(jnp.sum(eidx < HELD))
    assert n_held == sum(ROUTINGS[routing])
    assert (n_held > R) == (routing in ("one_row_past_the_bound",
                                        "every_pick_held"))
    floats, gates = _operands(jnp.dtype(dtype))
    want = _value_and_grads(full_length, floats, gates, eidx)
    got = _value_and_grads(bounded, floats, gates, eidx)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * max(np.abs(w).max(), 1e-6))
    if n_held:
        assert all(np.asarray(g, np.float32).any() for g in got)


@pytest.mark.parametrize("routing", ["balance", "every_pick_held"])
def test_bounded_layer_under_jit_and_remat(routing):
    """As the step runs it: jitted, under a ``jax.checkpoint`` that
    keeps nothing (the backward replays every window it takes)."""
    eidx = _picks(ROUTINGS[routing], seed=3)
    floats, gates = _operands(jnp.float32, seed=4)
    want = _value_and_grads(full_length, floats, gates, eidx)
    got = jax.jit(
        lambda floats, gates, eidx: _value_and_grads(
            jax.checkpoint(bounded), floats, gates, eidx)
    )(floats, gates, eidx)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * np.abs(w).max())


@pytest.mark.parametrize("held, e, held_picks, windows", [
    (4, 16, 4, 2),      # every pick held: 160 rows in windows of 80
    (3, 24, 2, 2),      # 80 held rows in windows of 40
    (3, 24, 3, 3),      # 120
], ids=["two_of_80", "two_of_40", "three_of_40"])
def test_a_kept_call_past_its_bound(held, e, held_picks, windows):
    """A routing past the bound under a layer call whose remat keeps
    ``MOE_RESIDUALS``: window 0 is transposed from the arrays the
    forward loop left, each window past it is rebuilt in the backward
    loop, and the gradients are the unbounded layer's.  One loop body
    a direction, and nothing of the layer in the remat's replay."""
    bound = moe.held_rows_bound(PICKS, held, e)
    eidx = _picks([held_picks] * N, seed=6, held=held, e=e)
    assert -(-int(jnp.sum(eidx < held)) // bound) == windows
    floats, gates = _operands(jnp.float32, seed=7, held=held)
    want = _value_and_grads(full_length, floats, gates, eidx)
    def step(policy):
        return jax.jit(
            lambda floats, gates, eidx: _value_and_grads(
                jax.checkpoint(lambda *args: bounded(*args, e=e),
                               policy=policy),
                floats, gates, eidx))

    def replayed(step):
        """Primitives in the layer call's replay (the backward loop's
        own remat of a window's second half stands inside that
        loop)."""
        return {
            eqn.primitive.name
            for eqn, stack in _stacked(
                jax.make_jaxpr(step)(floats, gates, eidx).jaxpr)
            if "rematted_computation" in stack
            and "<while>" not in stack.split("rematted_computation")[0]
        }

    kept = step(jax.checkpoint_policies.save_only_these_names(
        *moe.MOE_RESIDUALS))
    for g, w in zip(kept(floats, gates, eidx), want):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * np.abs(w).max())
    assert kept.lower(floats, gates, eidx).as_text().count(
        "stablehlo.while") == 2
    assert not {"while", "sort", "gather"} & replayed(kept)
    assert {"while", "sort"} <= replayed(step(None))


def _stacked(jaxpr, stack=""):
    """Every equation with the name stack it runs under (a sub-jaxpr's
    stacks are relative to the equation that holds it, whose primitive
    stands in the stack as ``<name>``)."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _stacked(sub, f"{here}<{eqn.primitive.name}>")


def _layer(h, router, leaves, held, lo=0):
    """``moe_ffn`` over experts ``[lo, lo + held)`` of the router's
    (asked for by rolling its columns, so that they come first: the
    same scores and picks under other names)."""
    e = router.shape[1]
    y, aux = moe.moe_ffn(
        h, jnp.roll(router, -lo, axis=1),
        *(w[lo:lo + (held or e)] for w in leaves),
        n_experts=e, top_k=3, capacity_factor=None, expert_axis=None,
        model_axis=None, held=held,
    )
    return y, aux


def _skewed_layer():
    """8 experts in 4 shares of 2, 48 tokens x 3 picks: a share's bound
    is 72 of the 144 rows.  The router leans on experts 0 and 1, so
    share 0 passes its bound and the others stay far under theirs."""
    rng = np.random.default_rng(5)
    n, d, f, e = 48, 16, 8, 8
    h = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)
    router = rng.standard_normal((d, e)) / 4
    logits = np.asarray(h[0]) @ router
    lean = np.zeros(e)
    lean[:2] = 2.0 * np.abs(logits).max()
    # a bias on two experts' scores: a column of ones in the tokens
    h = jnp.concatenate([h, jnp.ones((1, n, 1))], axis=-1)
    router = jnp.asarray(np.vstack([router, lean]), jnp.float32)
    leaves = [
        jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
        for s in ((e, d + 1, f), (e, d + 1, f), (e, f, d + 1))
    ]
    return h, router, leaves


def test_the_shares_add_up_with_one_share_past_its_bound():
    h, router, leaves = _skewed_layer()
    n, e = h.shape[1], router.shape[1]
    bound = moe.held_rows_bound(3 * n, 2, e)
    assert bound == 72
    whole, aux = _layer(h, router, leaves, None)
    parts, rows_held = [], []
    for r in range(4):
        y, aux_r = _layer(h, router, leaves, 2, lo=2 * r)
        parts.append(y)
        rows_held.append(int(round(float(aux_r["f"][:2].sum()) * 3 * n)))
        assert float(aux_r["dropped"]) == 0.0
        np.testing.assert_allclose(jnp.roll(aux_r["f"], 2 * r), aux["f"],
                                   atol=1e-6)
    assert rows_held[0] == 2 * n > bound            # every token picks both
    assert sum(rows_held) == 3 * n and max(rows_held[1:]) < bound
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)

    # and the gradient to the tokens: the shares' sum is the whole
    # layer's with its gates held (a share's gates carry no gradient)
    def routed(h, held, lo=0):
        return jnp.sum(_layer(h, router, leaves, held, lo)[0] ** 2)

    def whole_with_gates_held(h):
        gates, eidx, _, _ = moe.router_topk(h[0], router, 3)
        y = moe._dropless_experts(
            h[0], lax.stop_gradient(gates), eidx, *leaves, n_experts=e,
            model_axis=None)
        return y

    _, vjp = jax.vjp(whole_with_gates_held, h)
    ct = jnp.ones((n, h.shape[2]), jnp.float32)
    got = sum(
        jax.vjp(lambda h, r=r: _layer(h, router, leaves, 2, 2 * r)[0],
                h)[1](ct[None])[0]
        for r in range(4)
    )
    np.testing.assert_allclose(got, vjp(ct)[0], rtol=2e-5, atol=2e-6)


def _lowered(held, e=16):
    floats, gates = _operands(jnp.float32)
    leaves = [jnp.concatenate([w] * (e // HELD))[:held or e]
              for w in floats[1:]]
    eidx = _picks(ROUTINGS["balance"])

    def layer(x2, gates, eidx, *leaves):
        return moe._dropless_experts(
            x2, gates, eidx, *leaves, n_experts=e, model_axis=None,
            held=held)

    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(layer(*a)), argnums=(0, 3, 4, 5),
    )).lower(floats[0], gates, eidx, *leaves).as_text()


@pytest.mark.parametrize("held", [None, 16, 8], ids=str)
def test_nothing_to_skip_lowers_to_the_full_length_text(held, monkeypatch):
    """All experts here, or half of them and more: no loop, the
    full-length gathers, letter for letter the text of a layer whose
    bound is all its rows."""
    text = _lowered(held)
    assert "stablehlo.while" not in text and "stablehlo.case" not in text
    assert f"tensor<{PICKS}x{D}xf32>" in text
    monkeypatch.setattr(moe, "held_rows_bound", lambda picks, *_: picks)
    assert _lowered(held) == text


def _float_rows(jaxpr, rows, found, loops=True):
    """Shapes of the float arrays of ``rows`` rows (a column of gates
    aside) that ``jaxpr`` makes anywhere: through calls, remats, custom
    rules and — unless ``loops`` is false — ``while`` bodies.  Into a
    set, or into a dict with the primitives that made each."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = v.aval
            if (len(getattr(aval, "shape", ())) == 2
                    and aval.shape[0] == rows and aval.shape[1] > 1
                    and jnp.issubdtype(aval.dtype, jnp.floating)):
                if isinstance(found, dict):
                    found.setdefault(aval.shape, set()).add(
                        eqn.primitive.name)
                else:
                    found.add(aval.shape)
        if loops or eqn.primitive.name != "while":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _float_rows(sub, rows, found, loops)
    return found


def test_no_full_length_float_array_anywhere():
    """Forward and backward of the held layer write no ``[k·N, D]`` or
    ``[k·N, F]`` float array: ``[R, .]`` ones, all of them in the loop
    over the windows (its first pass is the part every routing runs)
    but window 0's rows, gate and up products, which the forward loop
    hands the backward one through its carry (``MOE_RESIDUALS``): the
    carry's unfilled buffers (``lax.empty``), the loop's results and
    their names.  The same walk
    finds the long ones in the full-length layer.  One loop each way,
    and none where there is nothing to skip."""
    floats, gates = _operands(jnp.float32)
    eidx = _picks(ROUTINGS["balance"])

    def both(layer):
        return jax.make_jaxpr(
            lambda *floats: _value_and_grads(layer, floats, gates, eidx)
        )(*floats).jaxpr

    assert not _float_rows(both(bounded), PICKS, set())
    assert _float_rows(both(bounded), R, set()) == {(R, D), (R, F)}
    outside = {}
    _float_rows(both(bounded), R, outside, loops=False)
    assert set(outside) == {(R, D), (R, F)}
    assert set().union(*outside.values()) == {"empty", "while", "name"}
    assert _float_rows(both(full_length), PICKS, set()) == {
        (PICKS, D), (PICKS, F)}
    text = jax.jit(
        lambda *floats: _value_and_grads(bounded, floats, gates, eidx)
    ).lower(*floats).as_text()
    assert text.count("stablehlo.while") == 2       # forward, backward


def test_counters_name_the_bound_and_the_layers_past_it():
    """Three layer calls of 160 picks, 4 of 16 experts held (bound
    80): 40, 80 and 81 rows held.  One is past its bound."""
    def layer(rows_held):
        rows = np.zeros(E)
        rows[:HELD] = rows_held // HELD
        rows[0] += rows_held % HELD
        rows[HELD] = PICKS - rows_held
        return [*(rows / PICKS), 0.0]

    got = routing.moe_counters(
        np.array([layer(40), layer(80), layer(81)]), PICKS, held=HELD)
    assert got["moe_rows_held"] == [40, 80, 81]
    assert got["moe_rows_bound"] == R == 80
    assert got["moe_held_overflow_layers"] == 1
    assert got["moe_dropped_picks"] == 0
    assert routing.last_moe_counters() is got
    plain = routing.moe_counters(np.array([layer(40)]), PICKS)
    assert "moe_rows_bound" not in plain
    assert "moe_held_overflow_layers" not in plain
