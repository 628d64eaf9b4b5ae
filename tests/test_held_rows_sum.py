"""``ops/held_rows_sum.py``: each token's rows summed out of a window
of rows sorted by (expert, token) — the plan of (token tile, row
chunk) visits made on the device, its static bound, and the kernel (in
the Pallas interpreter) against a plain scatter-add.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import attention
from theanompi_tpu.ops import held_rows_sum as hrs
from theanompi_tpu.parallel import moe

TN, CH = hrs.TILE_TOKENS, hrs.CHUNK_ROWS


def _window(n_tokens, n_rows, groups, fill, seed, start=0):
    """``tok [n_rows]``: rows ``[start, start + n_rows)`` of ``groups``
    experts' picks, each expert's tokens ascending (a token once an
    expert, picked with probability ``fill``), ``-1`` past them."""
    rng = np.random.default_rng(seed)
    tok = np.concatenate([
        np.sort(rng.choice(n_tokens, rng.binomial(n_tokens, fill),
                           replace=False))
        for _ in range(groups)
    ])[start:start + n_rows]
    return np.concatenate(
        [tok, np.full(n_rows - len(tok), -1)]).astype(np.int32)


# name: (tokens, rows, experts, share of the tokens an expert gets,
# first row of the window)
LAYOUTS = {
    "balance": (1024, 1024, 4, 0.125, 0),
    "no_real_row": (512, 256, 3, 0.0, 0),
    "a_few_rows": (1024, 1024, 4, 0.01, 0),
    "every_token_in_every_expert": (1024, 2048, 4, 1.0, 0),
    "window_cut_by_its_length": (512, 512, 2, 0.9, 0),
    "window_from_inside_an_expert": (1024, 1024, 4, 0.6, 700),
    "one_expert": (2048, 512, 1, 0.2, 0),
    "more_tiles_than_chunks": (4096, 256, 2, 0.03, 0),
}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_plan_visits_every_pair_with_a_row_once_tile_by_tile(layout):
    n_tokens, n_rows, groups, fill, start = LAYOUTS[layout]
    tok = _window(n_tokens, n_rows, groups, fill, seed=1, start=start)
    plan = hrs.make_sum_plan(jnp.asarray(tok), n_tokens, groups)
    real = int((tok >= 0).sum())
    n = int(plan.n_visits[0])
    assert plan.tiles.shape == plan.chunks.shape == (
        hrs.n_visits_bound(n_rows, n_tokens, groups),)
    assert 0 < n <= plan.tiles.shape[0]           # the bound holds
    visits = list(zip(np.asarray(plan.tiles).tolist(),
                      np.asarray(plan.chunks).tolist()))
    assert visits[:n] == sorted(set(visits[:n]))  # once each, tile by tile
    assert set(visits[n:]) <= {visits[n - 1]}     # then the last, repeated
    needed = set(zip((tok[:real] // TN).tolist(),
                     (np.arange(real) // CH).tolist()))
    assert needed <= set(visits[:n])
    # every block of y is written: every tile has a visit
    assert {t for t, _ in visits[:n]} == set(range(n_tokens // TN))
    # and nothing beyond what is needed but that
    assert set(visits[:n]) - needed <= {(t, 0) for t in range(n_tokens // TN)}


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-6), ("bfloat16", 0.0)])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_sums_each_tokens_rows(layout, dtype, tol):
    """Against a scatter-add in float64.  bf16 rows: a row times one is
    exact and the sum is fp32, so the result equals the fp32 sum of the
    bf16 rows to the last bit but the order of its additions."""
    n_tokens, n_rows, groups, fill, start = LAYOUTS[layout]
    tok = _window(n_tokens, n_rows, groups, fill, seed=2, start=start)
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((n_rows, 128)), dtype)
    want = np.zeros((n_tokens, 128))
    np.add.at(want, tok[tok >= 0], np.asarray(rows, np.float64)[tok >= 0])
    got = hrs.held_rows_sum(rows, jnp.asarray(tok), n_tokens, groups,
                            interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (n_tokens, 128)
    np.testing.assert_allclose(got, want, rtol=tol or 1e-6,
                               atol=(tol or 1e-6) * groups)
    # rows that are no held pick add nothing, whatever they hold
    junk = jnp.where((jnp.asarray(tok) >= 0)[:, None], rows, 1e30)
    np.testing.assert_array_equal(
        hrs.held_rows_sum(junk.astype(dtype), jnp.asarray(tok), n_tokens,
                          groups, interpret=True), got)


def test_the_static_grid_counts_a_visit_a_chunk_and_a_tile_an_expert():
    assert hrs.n_visits_bound(65536, 16384, 16) == 512 + 17 * 64    # Mellum
    assert hrs.n_visits_bound(16384, 16384, 8) == 128 + 9 * 64      # GLM
    # every second token from 129 on in every expert: 960 rows an
    # expert, so a chunk of 128 rows (256 tokens) lies across two
    # tiles and across the experts' edges — a visit a chunk and nearly
    # one more a (tile, expert), close to the bound
    n_tokens, groups = 2048, 8
    tok = np.tile(np.arange(129, n_tokens, 2), groups).astype(np.int32)
    plan = hrs.make_sum_plan(jnp.asarray(tok), n_tokens, groups)
    n, bound = int(plan.n_visits[0]), plan.tiles.shape[0]
    assert bound == 60 + 9 * 8
    assert len(tok) // CH + groups * (n_tokens // TN) // 2 <= n <= bound


@pytest.mark.parametrize("shape, on_tpu, want", [
    # Mellum's window: 65536 x 2304 bf16 = 302 MB
    ((65536, 16384, 2304), True, True),
    # GLM's: 16384 x 2048 bf16 = 67 MB, a source XLA gathers fast from
    ((16384, 16384, 2048), True, False),
    ((65536, 16384, 2304), False, False),          # off the TPU
    ((65536 + 64, 16384, 2304), True, False),      # no whole chunks
    ((65536, 16384 + 8, 2304), True, False),       # no whole tiles
], ids=["mellum", "glm", "cpu", "ragged_rows", "ragged_tokens"])
def test_which_windows_are_summed_in_the_kernel(monkeypatch, shape, on_tpu,
                                                want):
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    assert moe._sum_in_kernel(*shape, jnp.bfloat16) is want


def test_sum_in_the_kernel_under_the_checked_shard_map(monkeypatch):
    """As the layer calls it (``moe._sum_picks`` with ``groups``) on a
    data x model mesh: the rows vary over both axes, the sort's
    ``order`` over ``data`` alone; the kernel's operands and its plan
    are typed alike and each shard sums its own rows."""
    import functools

    import jax
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel import make_mesh

    monkeypatch.setattr(
        hrs, "held_rows_sum",
        functools.partial(hrs.held_rows_sum,
                          interpret=pltpu.InterpretParams()),
    )
    n, k, groups, r = 256, 2, 2, 256
    rng = np.random.default_rng(5)
    tok = np.stack([_window(n, r, groups, 0.3, seed) for seed in (6, 7)])
    order = np.where(tok >= 0, tok + n * rng.integers(0, k, tok.shape), -1)
    rows = rng.standard_normal((2, r, 128)).astype(np.float32)
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])

    def one(rows, order):
        rows = rows[0] * (1.0 + lax.axis_index("model"))
        y = moe._sum_picks(rows, order[0], jnp.zeros(k * n, jnp.int32), k,
                           groups)
        return y[None, None]

    got = jax.jit(jax.shard_map(
        one, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data", "model"),
    ))(jnp.asarray(rows), jnp.asarray(order.astype(np.int32)))
    for shard in range(2):
        want = np.zeros((n, 128))
        real = tok[shard] >= 0
        np.add.at(want, tok[shard][real], rows[shard][real])
        for m in range(2):
            np.testing.assert_allclose(got[shard, m], (1 + m) * want,
                                       rtol=1e-5, atol=1e-5)
