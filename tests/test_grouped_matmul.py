"""The grouped-product kernels (``ops/grouped_matmul.py``) against
``lax.ragged_dot`` in the Pallas interpreter: value and both
gradients, every way a group can lie in the row tiles; under
``jax.checkpoint``; under the checked ``shard_map`` at ``tp=2``; and
the expert layer's choice between the two.

Nothing here says anything about speed (PERF.md, PR 31, has the chip's
numbers); ``tests/test_chip_compile.py`` compiles the kernels for the
described v5e.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from theanompi_tpu.ops import attention
from theanompi_tpu.ops import grouped_matmul as gm
from theanompi_tpu.ops import held_rows_sum as hrs
from theanompi_tpu.parallel import make_mesh, moe

TM = 128            # row tile of these tests: 4 tiles of 512 rows
K, N = 256, 128

# how a group can lie in the tiles (each sums to 512 rows)
GROUPS = {
    "balanced": [128, 128, 128, 128],
    "skewed": [24, 40, 400, 48],
    "ends_inside_tiles": [100, 156, 130, 126],
    "empty_groups": [200, 0, 0, 312],
    "empty_first_and_last": [0, 256, 256, 0],
    "empty_at_a_tile_edge": [128, 0, 128, 256],
    "one_group_holds_every_row": [0, 0, 512, 0],
    "tiny_groups": [1, 2, 3, 506],
    "more_groups_than_tiles": [64] * 8,
}
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def _operands(sizes, dtype, k=K, n=N, seed=0):
    m = int(np.sum(sizes))
    ka, kb, kc = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(ka, (m, k), dtype),
        (jax.random.normal(kb, (len(sizes), k, n)) / 16).astype(dtype),
        jax.random.normal(kc, (m, n), dtype),
        jnp.asarray(sizes, jnp.int32),
    )


def _close(got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0)


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in a jaxpr, sub-jaxprs opened."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, primitive)
    return n


def _value_and_grads(product, lhs, rhs, ct):
    out, vjp = jax.vjp(product, lhs, rhs)
    return (out, *vjp(ct))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("groups", GROUPS, ids=str)
def test_value_and_both_gradients_match_ragged_dot(groups, dtype):
    lhs, rhs, ct, sizes = _operands(GROUPS[groups], dtype)
    plan = gm.make_tile_plan(sizes, lhs.shape[0], TM)
    got = _value_and_grads(
        lambda a, b: gm.grouped_matmul(a, b, plan, interpret=True),
        lhs, rhs, ct,
    )
    want = _value_and_grads(
        lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs, ct
    )
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype
        _close(g, w, TOL[dtype])
    if min(GROUPS[groups]) == 0:      # an empty group's gradient: zeros
        empty = np.asarray(GROUPS[groups]) == 0
        assert not np.asarray(got[2], np.float32)[empty].any()


# a HELD range of the experts: the groups cover a prefix of the 512
# rows (each list: the held groups' sizes; the rest of the rows belong
# to groups that are not here)
PREFIXES = {
    "no_rows_held": [0, 0],
    "one_tile": [100, 28],
    "ragged_count": [70, 0, 131],
    "ends_at_a_tile_edge": [128, 128],
    "all_rows": [200, 312],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("held", PREFIXES, ids=str)
def test_prefix_plan_computes_the_held_rows_and_zeros_past_them(held, dtype):
    """Forward and both backward kernels under a plan whose groups
    cover only the first rows: the value and the input gradient are
    ``ragged_dot``'s on the prefix and exactly zero past it, the
    weight gradient ignores the rows past it — whatever those rows
    hold (here NaN in the cotangent's and huge values in the
    operand's)."""
    sizes = PREFIXES[held]
    n_held = sum(sizes)
    lhs, rhs, ct, _ = _operands([512], dtype)
    rhs = jnp.tile(rhs, (len(sizes), 1, 1)) * (
        1 + jnp.arange(len(sizes), dtype=jnp.float32)[:, None, None]
    ).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    plan = gm.make_tile_plan(sizes, 512, TM, prefix=True)
    assert plan.prefix and int(plan.group_offsets[-1]) == n_held
    # the visits end with the prefix: no tile past it is named
    real = int(plan.n_visits[0])
    assert int(np.max(np.asarray(plan.tile_ids))) <= max(n_held - 1, 0) // TM
    assert (np.asarray(plan.tile_ids)[real:]
            == np.asarray(plan.tile_ids)[real - 1]).all()
    got = _value_and_grads(
        lambda a, b: gm.grouped_matmul(a, b, plan, interpret=True),
        lhs, rhs, ct,
    )
    want = _value_and_grads(
        lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs, ct
    )
    for g, w in zip(got, want):
        _close(g, w, TOL[dtype])
    for rows in got[:2]:
        assert not np.asarray(rows[n_held:], np.float32).any()
    # what lies past the prefix never reaches a result
    tail = jnp.arange(512)[:, None] >= n_held
    dirty = _value_and_grads(
        lambda a, b: gm.grouped_matmul(a, b, plan, interpret=True),
        jnp.where(tail, jnp.asarray(1e30, dtype), lhs), rhs,
        jnp.where(tail, jnp.asarray(jnp.nan, dtype), ct),
    )
    if n_held < 512:
        for g, d in zip(got, dirty):
            np.testing.assert_array_equal(
                np.asarray(g, np.float32), np.asarray(d, np.float32))


def test_full_plan_is_the_prefix_plan_of_all_rows():
    """A plan made without ``prefix`` masks nothing (its text is the
    one the all-experts layer always had) and visits what the prefix
    plan of the same sizes visits."""
    sizes = jnp.asarray(GROUPS["skewed"], jnp.int32)
    full = gm.make_tile_plan(sizes, 512, TM)
    held = gm.make_tile_plan(sizes, 512, TM, prefix=True)
    assert not full.prefix and held.prefix
    for a, b in zip(gm._plan_arrays(full), gm._plan_arrays(held)):
        np.testing.assert_array_equal(a, b)
    out = jnp.ones((512, N))
    assert gm.held_rows(out, full) is out          # nothing added
    assert gm.held_rows(out, held) is not out


def test_held_layer_on_the_kernels_matches_ragged_dot(
    kernels_in_the_interpreter,
):
    """The expert layer with 4 of its 8 experts held, on the kernels
    (in the interpreter) against ``lax.ragged_dot``: value and the
    gradients of every operand."""
    def loss(x, router, wg, wu, wd):
        y, aux = moe.moe_ffn(
            x, router, wg[:4], wu[:4], wd[:4], n_experts=E, top_k=TOP_K,
            capacity_factor=None, expert_axis=None, model_axis=None,
            held=4,
        )
        return jnp.sum(jnp.sin(y)) + aux["lb"]

    grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    args = _layer_args()
    want = grads(*args)                     # off the TPU: ragged_dot
    kernels_in_the_interpreter(True)
    got = grads(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 2e-5)
    assert np.asarray(got[1][2][:4]).any()
    assert not np.asarray(got[1][2][4:]).any()      # experts not held


@pytest.mark.parametrize("sums", ["gathered", "in_the_kernel"])
@pytest.mark.parametrize("lean", [0.0, 40.0],
                         ids=["within_the_bound", "past_the_bound"])
def test_held_windows_on_the_kernels_match_ragged_dot(
    kernels_in_the_interpreter, monkeypatch, lean, sums
):
    """One of 8 experts held, 1024 tokens x 2 picks: the layer lays out
    512 of its 2048 sorted rows (``moe.held_rows_bound``) under a plan
    of 512, and a router that leans on the held expert sends it more —
    the loop's further windows, each under a plan of its own.  Value
    and the gradients of every operand, the kernels (in the
    interpreter) against ``lax.ragged_dot``; the tokens' sums as XLA
    gathers them, and in ``held_rows_sum``'s kernel over rows sorted
    by (expert, token), as for a window too large to gather from."""
    assert moe.held_rows_bound(2 * 1024, 1, E) == 512
    summed, in_kernel = [], hrs.held_rows_sum
    if sums == "in_the_kernel":
        monkeypatch.setattr(moe, "_ON_CHIP_SOURCE_BYTES", 0)
    monkeypatch.setattr(
        hrs, "held_rows_sum", lambda rows, *a: (
            summed.append(rows.shape), in_kernel(rows, *a, interpret=True)
        )[1],
    )
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (1, 1024, D)).at[..., 0].set(1.0)
    router = jax.random.normal(ks[1], (D, E)).at[0, 0].add(lean)
    _, _, wg, wu, wd = _layer_args()

    def loss(x, router, wg, wu, wd):
        y, aux = moe.moe_ffn(
            x, router, wg[:1], wu[:1], wd[:1], n_experts=E, top_k=TOP_K,
            capacity_factor=None, expert_axis=None, model_axis=None,
            held=1,
        )
        return jnp.sum(jnp.sin(y)) + aux["lb"], aux["f"][0] * 2 * 1024

    grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    want = grads(x, router, wg, wu, wd)     # off the TPU: ragged_dot
    rows_held = float(want[0][1])
    assert (rows_held > 512) == bool(lean) and 64 < rows_held < 2048
    kernels_in_the_interpreter(True)
    got = grads(x, router, wg, wu, wd)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 2e-5)
    assert np.asarray(got[1][2][:1]).any()
    # (forward, its replay, and the dispatch's transpose)
    assert set(summed) == ({(512, D)} if sums == "in_the_kernel" else set())


@pytest.mark.parametrize("groups", GROUPS, ids=str)
def test_plan_visits_every_tile_of_every_group_in_order(groups):
    sizes = np.asarray(GROUPS[groups])
    m, e = int(sizes.sum()), len(sizes)
    plan = gm.make_tile_plan(jnp.asarray(sizes, jnp.int32), m, TM)
    n = int(plan.n_visits[0])
    gids, tids = np.asarray(plan.group_ids), np.asarray(plan.tile_ids)
    assert gids.shape == tids.shape == (m // TM + e - 1,)
    assert 1 <= n <= gids.shape[0]
    ends = np.cumsum(sizes)
    want = []
    for g, (start, end) in enumerate(zip(ends - sizes, ends)):
        if start == end:              # visited once, computes nothing
            want.append((g, min(start // TM, m // TM - 1)))
        else:
            want += [(g, t) for t in range(start // TM, -(-end // TM))]
    assert list(zip(gids[:n], tids[:n])) == want
    # the padding repeats the last visit: no block index moves
    assert (gids[n:] == gids[n - 1]).all() and (tids[n:] == tids[n - 1]).all()
    assert np.all(np.diff(gids) >= 0) and np.all(np.diff(tids) >= 0)
    assert np.array_equal(np.asarray(plan.group_offsets), [0, *ends])


@pytest.mark.parametrize("groups", ["ends_inside_tiles", "tiny_groups",
                                    "empty_groups"])
@pytest.mark.parametrize(
    "kind,tiles,sub_rows",
    [("fwd", (128, 128), 128), ("fwd", (256, 128), 128),
     ("dlhs", (128, 128), 128), ("drhs", (128, 128), 128),
     ("drhs", (256, 128), 128), ("fwd", (256, 256), 32),
     ("fwd", (128, 256), 64), ("dlhs", (256, 256), 32),
     ("drhs", (256, 256), 32)],
    ids=str,
)
def test_kernels_at_several_tiles_per_dimension(kind, tiles, sub_rows,
                                                groups):
    """More than one contracting tile (the accumulator path of (a) and
    (b)), more than one output block a group (c), and a partial visit
    computed in blocks smaller than its tile."""
    lhs, rhs, ct, sizes = _operands(GROUPS[groups], jnp.float32,
                                    k=256, n=256)
    plan = gm.make_tile_plan(sizes, lhs.shape[0], TM)
    want = _value_and_grads(
        lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs, ct
    )
    if kind == "fwd":
        got, ref = gm._rows_call(
            lhs, rhs, plan, transpose_rhs=False, tiles=tiles,
            sub_rows=sub_rows, interpret=True), want[0]
    elif kind == "dlhs":
        got, ref = gm._rows_call(
            ct, rhs, plan, transpose_rhs=True, tiles=tiles,
            sub_rows=sub_rows, interpret=True), want[1]
    else:
        got, ref = gm._weights_call(
            lhs, ct, plan, jnp.float32, tiles=tiles, sub_rows=sub_rows,
            interpret=True), want[2]
    _close(got, ref, TOL[jnp.float32])


@pytest.mark.parametrize("saved", [True, False], ids=["plan_saved", "full"])
def test_under_checkpoint(saved):
    """The layer's remat: the same gradients, and with the plan's name
    in the policy the replay builds no second plan."""
    lhs, rhs, ct, sizes = _operands(GROUPS["ends_inside_tiles"], jnp.float32)

    def layer(a, b, sizes):
        plan = checkpoint_name(
            gm.make_tile_plan(sizes, a.shape[0], TM), gm.TILE_PLAN_RESIDUAL
        )
        h = jnp.tanh(gm.grouped_matmul(a, b, plan, interpret=True))
        return jnp.sum(h * ct)

    policy = jax.checkpoint_policies.save_only_these_names(
        *([gm.TILE_PLAN_RESIDUAL] if saved else [])
    )
    grad = jax.grad(jax.checkpoint(layer, policy=policy), argnums=(0, 1))
    want = jax.grad(
        lambda a, b: jnp.sum(jnp.tanh(lax.ragged_dot(a, b, sizes)) * ct),
        argnums=(0, 1),
    )(lhs, rhs)
    for g, w in zip(grad(lhs, rhs, sizes), want):
        _close(g, w, TOL[jnp.float32])
    jaxpr = jax.make_jaxpr(grad)(lhs, rhs, sizes).jaxpr
    # forward, replay, two backward kernels; a plan is two cumulative
    # sums (over the groups' rows and over their visits)
    assert _count(jaxpr, "pallas_call") == 4
    assert _count(jaxpr, "cumsum") == (2 if saved else 4)


@pytest.fixture
def kernels_in_the_interpreter(monkeypatch):
    """The expert layer takes the kernel path, as on the chip, and the
    kernels run in the TPU interpreter (the one that lowers under a
    checked ``shard_map``; the HLO interpreter is the one
    ``jax.checkpoint`` can partially evaluate)."""
    def use(interpret):
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            gm, "grouped_matmul",
            functools.partial(gm.grouped_matmul, interpret=interpret),
        )
    return use


E, TOP_K, D, F, N_TOK = 8, 2, 128, 256, 256


def _layer_args(dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(1), 5)
    return (
        jax.random.normal(ks[0], (1, N_TOK, D), dtype),
        jax.random.normal(ks[1], (D, E), jnp.float32),
        jax.random.normal(ks[2], (E, D, F), jnp.float32) / 8,
        jax.random.normal(ks[3], (E, D, F), jnp.float32) / 8,
        jax.random.normal(ks[4], (E, F, D), jnp.float32) / 8,
    )


def _layer_loss(x, router, wg, wu, wd, model_axis=None):
    y, aux = moe.moe_ffn(
        x, router, wg, wu, wd, n_experts=E, top_k=TOP_K,
        capacity_factor=None, expert_axis=None, model_axis=model_axis,
        renormalize=False,
    )
    return jnp.sum(y.astype(jnp.float32) ** 2) + aux["lb"] + aux["z"]


_layer_grads = jax.value_and_grad(_layer_loss, argnums=(0, 1, 2, 3, 4))


def test_expert_layer_on_the_kernels_matches_ragged_dot(
    kernels_in_the_interpreter
):
    args = _layer_args()
    want = _layer_grads(*args)              # off the TPU: ragged_dot
    kernels_in_the_interpreter(True)
    got = _layer_grads(*args)
    _close(got[0], want[0], 1e-5)
    for g, w in zip(got[1], want[1]):
        _close(g, w, 1e-5)
    # under the layer's remat, with what ``Llama`` saves
    policy = jax.checkpoint_policies.save_only_these_names(
        gm.TILE_PLAN_RESIDUAL
    )
    kept = jax.value_and_grad(
        jax.checkpoint(_layer_loss, policy=policy), argnums=(0, 1, 2, 3, 4)
    )(*args)
    for g, w in zip(kept[1], got[1]):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_expert_layer_under_checked_shard_map_tp2(kernels_in_the_interpreter):
    """``tp=2``: rows replicated over ``model``, expert weights sharded
    over it; the kernels' outputs carry the union of their operands'
    varying axes and the rows' gradient is summed over ``model``."""
    args = _layer_args()
    want = _layer_grads(*args)
    kernels_in_the_interpreter(pltpu.InterpretParams())
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    col, row = P(None, None, "model"), P(None, "model", None)
    sharded = jax.jit(jax.shard_map(
        jax.value_and_grad(
            functools.partial(_layer_loss, model_axis="model"),
            argnums=(0, 1, 2, 3, 4),
        ),
        mesh=mesh, in_specs=(P(), P(), col, col, row),
        out_specs=(P(), (P(), P(), col, col, row)),
    ))
    got = sharded(*args)
    _close(got[0], want[0], 1e-5)
    for g, w in zip(got[1], want[1]):
        _close(g, w, 1e-5)


@pytest.mark.parametrize(
    "on_tpu,shape,kernels",
    [(False, (512, 128, 256), False),      # CPU: today's path
     (True, (512, 128, 256), True),
     (True, (512, 96, 256), False),        # a width no lane tile divides
     (True, (520, 128, 256), False)],      # rows no row tile divides
    ids=["off_tpu", "on_tpu", "odd_width", "odd_rows"],
)
def test_layer_chooses_by_device_and_shape(monkeypatch, caplog, on_tpu,
                                           shape, kernels):
    rows, d, f = shape
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    moe._log_ragged_choice.cache_clear()
    sizes = jnp.full((4,), rows // 4, jnp.int32)
    product = moe._grouped_product
    with caplog.at_level("INFO", logger=moe.logger.name):
        jaxpr = jax.make_jaxpr(
            lambda a, b: product(sizes, rows, d, f, a.dtype)(a, b)
        )(jnp.zeros((rows, d), jnp.bfloat16),
          jnp.zeros((4, d, f), jnp.bfloat16))
        jax.make_jaxpr(             # a second trace logs nothing more
            lambda a, b: product(sizes, rows, d, f, a.dtype)(a, b)
        )(jnp.zeros((rows, d), jnp.bfloat16),
          jnp.zeros((4, d, f), jnp.bfloat16))
    text = str(jaxpr)
    assert ("pallas_call" in text) == kernels
    assert ("ragged_dot" in text) == (not kernels)
    said = [r for r in caplog.records if "lax.ragged_dot" in r.getMessage()]
    assert len(said) == (0 if kernels else 1)
    if said:
        assert said[0].levelname == ("WARNING" if on_tpu else "INFO")


def test_shapes_tile_and_tiles_follow_shapes():
    bf = jnp.bfloat16
    assert gm.shapes_tile(131072, 2048, 1024, bf)      # the OLMoE cell
    assert gm.shapes_tile(131072, 1024, 2048, bf)
    assert not gm.shapes_tile(131072, 2000, 1024, bf)
    assert not gm.shapes_tile(1000, 2048, 1024, bf)
    assert gm.tile_rows(131072) % 128 == 0
    for c, o in [(2048, 1024), (1024, 2048), (256, 128), (4096, 14336)]:
        tc, to = gm._rows_tiles(c, o, bf)
        assert c % tc == 0 and o % to == 0 and tc % 128 == 0 and to % 128 == 0
        tk, tn = gm._weights_tiles(c, o, bf)
        assert c % tk == 0 and o % tn == 0


@pytest.mark.parametrize("c, o, dtype, want", [
    # the cells' shapes: OLMoE's and GLM's tile as PR 31 measured them
    (2048, 1024, "bfloat16", (2048, 1024)),
    (1024, 2048, "bfloat16", (1024, 2048)),
    (2048, 1536, "bfloat16", (2048, 768)),
    (1536, 2048, "bfloat16", (1536, 1024)),
    # Mellum's 2304 x 896: 4.13 MB, the WHOLE block (a contraction cut
    # in two re-reads the weights every visit: 39 % of the roofline
    # against 84 %, PERF.md, PR 41), either way round
    (2304, 896, "bfloat16", (2304, 896)),
    (896, 2304, "bfloat16", (896, 2304)),
    # past 4 MiB the old rule: the contraction whole up to 2048, then
    # the output columns by bytes
    (2304, 896, "float32", (1152, 896)),
    (4096, 14336, "bfloat16", (2048, 1024)),
], ids=str)
def test_a_weight_block_is_whole_where_it_fits(c, o, dtype, want):
    assert gm._rows_tiles(c, o, jnp.dtype(dtype)) == want
    assert gm.shapes_tile(131072, c, o, jnp.dtype(dtype))


def test_plan_refuses_rows_its_tile_does_not_divide():
    with pytest.raises(ValueError, match="do not divide"):
        gm.make_tile_plan(jnp.asarray([100, 100], jnp.int32), 200, 128)
