"""The two flash-attention kernels (forward; the one backward kernel
that builds each score tile once for dQ, dK and dV) against dense
attention, in the Pallas interpreter, over the tilings their shape
function can choose:
one block, several outer blocks, several score tiles inside a block —
so that every case set holds a tile wholly under the diagonal (the
body without a mask), one the diagonal crosses and one that is
skipped — and the shape function itself.  Under a WINDOW (a query
sees itself and the ``window - 1`` keys before it) the same kernels
walk a band: the cases hold windows under a score tile's width, equal
to it, between it and a row block, a multiple of the fetched block,
and one no query's reach falls short of.

Tolerances are those of ``tests/test_ring_attention.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import attention
from theanompi_tpu.ops.attention import (
    FlashPlan,
    FlashTiles,
    _auto_block,
    _band,
    _band_steps,
    _flash_bwd_call,
    _flash_fwd_call,
    _flash_tiles,
    _sub_block_kind,
    _walked_index,
    flash_attention_tpu,
    flash_tiles_summary,
    mha_reference,
    walked_tiles,
)

B, H = 1, 2

# (T_q, T_k, (rows, major, sub) of the forward, of the backward)
TILINGS = {
    "one_block": (32, 32, (32, 32, 32), (32, 32, 32)),
    "outer_blocks": (64, 64, (16, 16, 16), (16, 16, 16)),
    "sub_tiles": (64, 64, (32, 64, 16), (32, 64, 16)),
    "sub_tiles_wider_than_rows": (64, 64, (16, 64, 32), (16, 32, 32)),
    "whole_axis_resident": (96, 96, (32, 96, 32), (48, 96, 16)),
    # ring attention's visiting blocks and a decoder's prefix
    "short_queries": (32, 64, (16, 32, 16), (16, 32, 16)),
    "short_keys": (64, 32, (16, 32, 16), (16, 64, 16)),
}


def _kinds(t_rows, t_walk, tiles, rows_are_queries):
    """Which bodies a causal kernel with these tiles takes."""
    rows, _, sub = tiles
    seen = set()
    for r in range(0, t_rows, rows):
        for lo in range(0, t_walk, sub):
            clear, crossed = _sub_block_kind(
                lo, sub, r, rows, rows_are_queries
            )
            seen.add("clear" if clear else "crossed" if crossed else "skipped")
    return seen


def test_case_sets_hold_every_kind_of_tile():
    for name, (t_q, t_k, on_q, on_k) in TILINGS.items():
        if name == "one_block":
            continue
        assert _kinds(t_q, t_k, on_q, True) == {"clear", "crossed", "skipped"}, name
        assert _kinds(t_k, t_q, on_k, False) == {"clear", "crossed", "skipped"}, name


# windows against the tilings' score tiles (16 or 32 wide) and row
# blocks: under a tile, a tile, between tile and rows, a multiple of
# the fetched block, and past every query's reach
WINDOWS = (5, 16, 24, 32, 200)


def _band_cases():
    """(tiling, window): every query keeps a key to see (where the
    queries outrun the keys, a window that reaches back to them)."""
    for name, (t_q, t_k, _, _) in TILINGS.items():
        for window in WINDOWS:
            if t_q - t_k < window:
                yield name, window
        if t_q > t_k:
            yield name, t_q - t_k + 8


BAND_CASES = list(_band_cases())


def test_band_cases_hold_every_kind_of_tile():
    """Over the cases: a tile the band's lower edge crosses, one both
    edges cross (a window under a tile's width), clear ones between
    the edges and skipped ones on either side."""
    seen = set()
    for name, window in BAND_CASES:
        t_q, t_k, on_q, _ = TILINGS[name]
        rows, _, sub = on_q
        i = np.arange(t_q)[:, None]
        j = np.arange(t_k)[None, :]
        for r in range(0, t_q, rows):
            for lo in range(0, t_k, sub):
                tile = np.s_[r:r + rows, lo:lo + sub]
                diag = (j <= i)[tile]
                edge = (i - j < window)[tile]
                if not (diag & edge).any():
                    seen.add("before" if not edge.any() else "after")
                elif (diag & edge).all():
                    seen.add("clear")
                else:
                    seen.add(("diag" if not diag.all() else "")
                             + ("edge" if not edge.all() else ""))
    assert seen == {"before", "after", "clear", "diag", "edge", "diagedge"}


@pytest.mark.parametrize(
    "tiling,window",
    [(name, None) for name in TILINGS] + BAND_CASES, ids=str,
)
def test_a_skipped_step_names_a_block_that_exists(tiling, window):
    """The clamped index maps stay inside the walked axis (the
    interpreter would clamp a stray index itself; the chip's DMA would
    not) and never name a block a step with work would not; under a
    window a step past the band's last block names that block again
    (already resident: nothing is fetched) and folds nothing."""
    t_q, t_k, on_q, on_k = TILINGS[tiling]
    for tiles, t_rows, t_walk, rows_are_queries in (
        (FlashTiles(*on_q), t_q, t_k, True), (FlashTiles(*on_k), t_k, t_q, False),
    ):
        n = t_walk // tiles.major
        index = _walked_index(True, tiles, rows_are_queries, n, window)
        steps = n if window is None else _band_steps(
            t_rows, t_walk, tiles, rows_are_queries, window)
        assert 1 <= steps <= n
        for r in range(t_rows // tiles.rows):
            first, last = (0, n - 1) if window is None else _band(
                r, tiles, rows_are_queries, window, n)
            assert 0 <= first <= last < n
            with_work = set()
            for w in range(n):          # over the WHOLE axis
                kinds = [
                    _sub_block_kind(w * tiles.major + c, tiles.sub,
                                    r * tiles.rows, tiles.rows,
                                    rows_are_queries, window)
                    for c in range(0, tiles.major, tiles.sub)
                ]
                if any(clear or crossed for clear, crossed in kinds):
                    with_work.add(w)
            # every block with work lies in the band, within the steps
            assert with_work <= set(range(first, min(first + steps, last + 1)))
            for w in range(steps):
                got = int(index(r, w))
                assert 0 <= got < n
                block = w if window is None else first + w
                if block in with_work:
                    assert got == block      # a step with work fetches its own
                elif window is not None:
                    assert first <= got <= last


def _operands(rng, t_q, t_k, d, dtype=jnp.float32):
    def draw(t):
        return jnp.asarray(rng.standard_normal((B, H, t, d)), dtype)
    return draw(t_q), draw(t_k), draw(t_k), draw(t_q)


def _dense(q, k, v, g, causal, window=None):
    """Output, logsumexp and the three gradients of dense attention."""
    out, vjp = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, causal=causal, window=window),
        q, k, v,
    )
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        t_q, t_k = s.shape[-2:]
        i, j = jnp.arange(t_q)[:, None], jnp.arange(t_k)[None, :]
        mask = i >= j if window is None else (i >= j) & (i - j < window)
        s = jnp.where(mask, s, -jnp.inf)
    return (out, jax.nn.logsumexp(s, axis=-1)) + vjp(g)


def _kernels(q, k, v, g, causal, on_q, on_k, window=None):
    sm = q.shape[-1] ** -0.5
    out, lse = _flash_fwd_call(
        q, k, v, causal, sm, FlashTiles(*on_q), True, window
    )
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    dq, dk, dv = _flash_bwd_call(
        q, k, v, g, lse, delta, causal, sm,
        FlashPlan(FlashTiles(*on_q), FlashTiles(*on_k)), True, window,
    )
    return out, lse, dq, dk, dv


def _assert_close(got, want, label, names=("out", "lse", "dq", "dk", "dv")):
    for name, a, b in zip(names, got, want):
        tol = 2e-5 if name in ("out", "lse") else 2e-4
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=tol, atol=tol,
            err_msg=f"{name} ({label})",
        )


@pytest.mark.parametrize("tiling", TILINGS, ids=str)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d", [64, 128], ids=["hd64", "hd128"])
def test_kernels_match_dense_attention(rng, d, causal, tiling):
    t_q, t_k, on_q, on_k = TILINGS[tiling]
    q, k, v, g = _operands(rng, t_q, t_k, d)
    got = _kernels(q, k, v, g, causal, on_q, on_k)
    want = _dense(q, k, v, g, causal)
    assert got[1].shape == (B, H, t_q)          # lane-dense at the edge
    _assert_close(got, want, tiling)


@pytest.mark.parametrize("tiling,window", BAND_CASES, ids=str)
def test_window_kernels_match_dense_attention(rng, tiling, window):
    """Forward and the three gradients under a window, over the band's
    own grid axis, against dense attention with the explicit mask; a
    window past every query's reach gives what no window gives."""
    t_q, t_k, on_q, on_k = TILINGS[tiling]
    q, k, v, g = _operands(rng, t_q, t_k, 64)
    got = _kernels(q, k, v, g, True, on_q, on_k, window)
    _assert_close(got, _dense(q, k, v, g, True, window), (tiling, window))
    if window >= t_q:
        _assert_close(got, _kernels(q, k, v, g, True, on_q, on_k),
                      (tiling, "no window"))


def _gradients(q, k, v, g, causal, window=None, **blocks):
    """(dq, dk, dv) of ``flash_attention_tpu`` in the interpreter and of
    dense attention: the shape function's own plan, or ``blocks``."""
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_tpu(
            q, k, v, causal=causal, window=window, interpret=True, **blocks),
        q, k, v,
    )
    _, dense = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, causal=causal, window=window),
        q, k, v,
    )
    return vjp(g), dense(g)


@pytest.mark.parametrize("d", [64, 128, 256], ids=lambda d: f"hd{d}")
@pytest.mark.parametrize(
    "causal,window",
    [(True, None), (True, 512), (True, 1024), (False, None)],
    ids=["causal", "w512", "w1024", "full"],
)
def test_backward_kernel_at_the_shape_functions_tiles(rng, causal, window, d):
    """The one backward kernel under the tiles ``_flash_tiles`` gives
    the cells (512 rows, a walked block of several score tiles, the
    band's two or three steps a key block) at T 2048, two batch-heads
    (the dQ scratch is one head's: the second has to find it zeroed),
    every head dim the cells hand it: dQ summed over four key blocks
    in the scratch, dK and dV over the walked queries."""
    q, k, v, g = _operands(rng, 2048, 2048, d)
    plan = _flash_tiles(2048, 2048, d, q.dtype, window)
    assert plan.bwd.rows == 512 and 2048 // plan.bwd.rows == 4
    got, want = _gradients(q, k, v, g, causal, window)
    _assert_close(got, want, (causal, window, d), ("dq", "dk", "dv"))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize(
    "t_q,t_k", [(1024, 2048), (2048, 1024), (1536, 512)], ids=str,
)
def test_backward_kernel_where_queries_and_keys_differ(rng, t_q, t_k, causal):
    """Ring attention's visiting block and a decoder's prefix: the dQ
    scratch is ``[T_q, d]`` whatever ``T_k``, and under causality a
    key block no query sees (``T_k > T_q``) folds nothing into it."""
    q, k, v, g = _operands(rng, t_q, t_k, 128)
    got, want = _gradients(q, k, v, g, causal)
    _assert_close(got, want, (t_q, t_k, causal), ("dq", "dk", "dv"))


@pytest.mark.parametrize(
    "t,blocks", [
        (60, dict(block_q=20, block_k=12)),
        (60, dict(block_q=12, block_k=20, bwd_block_q=30, bwd_block_k=10)),
        (68, {}),                       # no aligned block: the whole axes
        (72, dict(bwd_block_q=24, bwd_block_k=36)),
    ], ids=str,
)
@pytest.mark.parametrize("window", [None, 17], ids=["causal", "w17"])
@pytest.mark.parametrize("d", [64, 128], ids=["hd64", "hd128"])
def test_backward_kernel_under_ragged_explicit_blocks(
    rng, d, t, blocks, window
):
    """Blocks no vector register tiles (the interpreter runs them; the
    chip is never handed one): the scratch's rows are addressed by the
    walked block's first query whatever its size."""
    q, k, v, g = _operands(rng, t, t, d)
    got, want = _gradients(q, k, v, g, True, window, **blocks)
    _assert_close(got, want, (t, blocks, window), ("dq", "dk", "dv"))


def test_backward_refuses_a_dq_sum_vmem_cannot_hold():
    """The one limit of the one-kernel backward, told where it is met
    and not deep in Mosaic: a call whose float32 dQ sum and output
    block take it past ``_VMEM_MOST`` (64k queries at head dim 128 in
    bf16 are the last that fit; a row under a register's lanes counts
    as the 128 lanes VMEM gives it)."""
    for t, d in ((65536, 128), (32768, 256), (65536, 64)):
        assert attention._bwd_vmem_limit(t, d, "bfloat16") == attention._VMEM_MOST
    x = jax.ShapeDtypeStruct((1, 1, 131072, 128), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((1, 1, 131072), jnp.float32)
    plan = _flash_tiles(131072, 131072, 128, "bfloat16")
    with pytest.raises(ValueError, match="shard the sequence"):
        jax.eval_shape(
            lambda q, k, v, g, lse, delta: _flash_bwd_call(
                q, k, v, g, lse, delta, True, 0.1, plan, False),
            x, x, x, x, stat, stat,
        )


@pytest.mark.parametrize("tiling,window", BAND_CASES, ids=str)
def test_the_walk_visits_exactly_the_bands_tiles(tiling, window):
    """For each of the two kernels the score tiles the walk folds
    (``walked_tiles``: the kernels' own ``_band`` and
    ``_sub_block_kind`` on integers) are exactly those that hold a
    visible pair of the explicit ``[T_q, T_k]`` mask — none outside
    the band, none inside it missed — and the masked body goes to
    exactly those the mask does not fill."""
    t_q, t_k, on_q, on_k = TILINGS[tiling]
    i, j = np.arange(t_q)[:, None], np.arange(t_k)[None, :]
    mask = (j <= i) & (i - j < window)
    for tiles, rows_are_queries in ((FlashTiles(*on_q), True),
                                    (FlashTiles(*on_k), False)):
        rows, _, sub = tiles
        seen = mask if rows_are_queries else mask.T     # [rows axis, walked]
        want = []
        for r in range(seen.shape[0] // rows):
            for lo in range(0, seen.shape[1], sub):
                tile = seen[r * rows:(r + 1) * rows, lo:lo + sub]
                if tile.any():
                    want.append((r, lo, not tile.all()))
        t_rows, t_walk = seen.shape
        got = walked_tiles(t_rows, t_walk, tiles, rows_are_queries,
                           True, window)
        assert got == want


def test_the_dispatch_takes_a_window_that_binds_and_drops_one_that_cannot(
    rng,
):
    q, k, v, _ = _operands(rng, 64, 64, 64)
    blocks = dict(block_q=16, block_k=16, interpret=True)
    bound = flash_attention_tpu(q, k, v, window=24, **blocks)
    np.testing.assert_allclose(
        np.asarray(bound), np.asarray(mha_reference(q, k, v, window=24)),
        rtol=2e-5, atol=2e-5)
    # window >= T IS the plain causal call, under the plain name
    text = jax.jit(
        lambda *a: flash_attention_tpu(*a, window=64, **blocks)
    ).lower(q, k, v).as_text()
    assert "_flash_jit" in text and "_flash_window_jit" not in text
    text = jax.jit(
        lambda *a: flash_attention_tpu(*a, window=63, **blocks)
    ).lower(q, k, v).as_text()
    assert "_flash_window_jit" in text and "_flash_jit" not in text
    with pytest.raises(ValueError, match="causal"):
        flash_attention_tpu(q, k, v, causal=False, window=8, **blocks)


@pytest.mark.parametrize("tiling", ["one_block", "sub_tiles"], ids=str)
def test_bf16_gradients_stay_within_rounding_of_the_parents(rng, tiling):
    """The backward feeds ``p`` and ``ds`` to its products in the input
    dtype, as the forward always has (the parent cast the other operand
    up instead, and the matrix unit rounded both: PERF.md, PR 32), and
    the scale rounds into the query block.  Against the parent's
    formula — the same bf16 inputs, ``p`` and ``ds`` kept float32 —
    the gradients move by bf16 rounding of an operand, no more."""
    t_q, t_k, on_q, on_k = TILINGS[tiling]
    q, k, v, g = _operands(rng, t_q, t_k, 64, jnp.bfloat16)
    out, _, dq, dk, dv = _kernels(q, k, v, g, True, on_q, on_k)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    want = _dense(*f32, True)
    for name, a, b in zip(("out", "dq", "dk", "dv"),
                          (out, dq, dk, dv), want[:1] + want[2:]):
        scale = float(jnp.max(jnp.abs(b)))
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        # 2**-8: half a bf16 ulp of the operands, with room for the
        # result's own rounding
        assert err <= 2 ** -6 * scale, (name, err, scale)


# every (T, head_dim, dtype) the repo's models and tests hand the
# shape function: the cells (4096 x 128, 8192 x 64 / 128 / 256),
# chip_smoke's proxy and the
# chip-less compiles (2048 x 64 / 128, 256 x 128), the serving
# decoder's prefill buckets, ring shards, the float32 test lengths
MODEL_SHAPES = [
    (t, d, dtype)
    for dtype in ("bfloat16", "float32")
    for d in (16, 64, 128, 256)
    for t in (8, 16, 24, 32, 40, 60, 64, 68, 96, 128, 192, 256, 512, 640,
              1000, 1024, 1280, 2048, 3000, 4096, 8192, 16384, 32768)
]


@pytest.mark.parametrize("t,d,dtype", MODEL_SHAPES, ids=str)
def test_shape_function(monkeypatch, t, d, dtype):
    monkeypatch.setenv("TM_FLASH_BWD_BLOCKS", "128,128")  # read by nothing
    plan = _flash_tiles(t, t, d, dtype)
    if _auto_block(t, dtype) is None:
        assert plan is None                    # the dense path, as before
        return
    sublane = 8 if dtype == "float32" else 16
    # the backward is the one kernel at every shape (its form is the
    # plan's: ``fwd`` and ``bwd``, head dim 64 — the hybrid cell's —
    # among them), over a walked block that counts a row as VMEM
    # holds it: at least a register's lanes wide
    assert plan._fields == ("fwd", "bwd")
    assert plan.bwd.major * max(d, 128) * np.dtype(dtype).itemsize <= (
        1 << 20) or plan.bwd.major == 128
    assert plan.bwd == plan.fwd or d < 128
    for rows, major, sub in plan:
        assert t % rows == 0 and t % major == 0 and major % sub == 0
        for block in (rows, major, sub):
            # Mosaic: a block dim is the whole axis or (8|16, 128)-aligned
            assert block == t or block % 128 == 0
            assert block % sublane == 0
        # one fetched block of the walked axis stays a small part of
        # the 16 MB of scoped VMEM, double-buffered, for K and V
        assert major * d * np.dtype(dtype).itemsize <= 1 << 20 or major == 128
    monkeypatch.delenv("TM_FLASH_BWD_BLOCKS")
    assert _flash_tiles(t, t, d, dtype) == plan


def test_dense_path_exactly_where_no_block_tiles():
    for t, d, dtype in MODEL_SHAPES:
        for t_k in (t, 2 * t):
            want = bool(_auto_block(t, dtype) and _auto_block(t_k, dtype))
            assert (_flash_tiles(t, t_k, d, dtype) is not None) == want


def test_summary_counts_the_masked_tiles(monkeypatch):
    assert flash_tiles_summary(4096, 4096, 128, "bfloat16") == {}  # off the TPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert flash_tiles_summary(68, 68, 128, "bfloat16") == {}      # no block
    full = flash_tiles_summary(4096, 4096, 128, "bfloat16", causal=False)
    assert {k["masked_share"] for k in full.values()} == {0.0}
    monkeypatch.setattr(
        attention, "_flash_tiles",
        lambda *a: FlashPlan(FlashTiles(512, 4096, 512),
                             FlashTiles(512, 4096, 256)),
    )
    got = flash_tiles_summary(4096, 4096, 128, "bfloat16")
    assert set(got) == {"fwd", "bwd"}           # the kernels a shape runs
    assert got["fwd"] == {
        # 8 row blocks see 1..8 tiles of 512 keys: 36, one crossed each
        "outer": [512, 4096], "inner": [512, 512], "tiles": 36,
        "masked_share": round(8 / 36, 4),
    }
    # 8 key blocks are seen by 2, 4, .. 16 tiles of 256 queries, two
    # of them crossed by the diagonal
    assert got["bwd"] == {
        "outer": [512, 4096], "inner": [512, 256], "tiles": 72,
        "masked_share": round(16 / 72, 4),
    }


def test_summary_counts_the_bands_tiles(monkeypatch):
    """The cell's window layers, T 8192 and window 1024: a row block
    of 512 folds three ``[512, 512]`` tiles (two crossed, one clear),
    the first two blocks one and two; a fetched block is a tile."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    got = flash_tiles_summary(8192, 8192, 128, "bfloat16", window=1024)
    assert set(got) == {"fwd", "bwd"}
    for kernel in ("fwd", "bwd"):
        assert got[kernel] == {
            "outer": [512, 512], "inner": [512, 512],
            "tiles": 1 + 2 + 14 * 3,
            "masked_share": round((1 + 1 + 14 * 2) / 45, 4),
        }, kernel
    plan = _flash_tiles(8192, 8192, 128, "bfloat16", 1024)
    assert _band_steps(8192, 8192, plan.fwd, True, 1024) == 3
    assert _band_steps(8192, 8192, plan.bwd, False, 1024) == 3
    # the same band at the hybrid cell's head dim
    assert flash_tiles_summary(8192, 8192, 64, "bfloat16", window=1024) == got
    # the full layer beside them walks the triangle: 136 tiles
    full = flash_tiles_summary(8192, 8192, 128, "bfloat16")
    assert full["fwd"]["tiles"] == 16 * 17 // 2
    # a window no query's reach falls short of is no window
    assert flash_tiles_summary(
        8192, 8192, 128, "bfloat16", window=8192) == full


def test_worker_summary_names_the_tiles(monkeypatch):
    from theanompi_tpu.workers import bsp_worker

    config = dict(
        dim=32, n_heads=2, n_kv_heads=1, ffn_dim=64, vocab=32, seq_len=32,
        batch_size=2, n_train=8, n_val=4, compute_dtype="float32",
        n_layers=1, n_epochs=1, seed=3,
    )
    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama", config=config, verbose=False,
    )
    assert res["flash_tiles"] == {}             # dense attention here
    from theanompi_tpu.models.llama import Llama

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    tiles = Llama(dict(config, seq_len=4096, dim=256)).flash_tiles()
    assert set(tiles) == {"fwd", "bwd"}
    assert all(0 < k["masked_share"] < 0.5 for k in tiles.values())
    # the hybrid cell's shape: the forward walks the whole axis, the
    # backward half of it a grid step (PERF.md, PR 54)
    tiles = Llama(dict(config, seq_len=8192, dim=128,
                       compute_dtype="bfloat16")).flash_tiles()
    assert (tiles["fwd"]["outer"], tiles["bwd"]["outer"]) == (
        [512, 8192], [512, 4096])
