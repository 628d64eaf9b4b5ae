"""The Mamba-2 mixer's pieces (``ops/ssd.py``) on the CPU in float32:
the chunked scan against the token-by-token recurrence — outputs and
the gradients of all six inputs —, the convolution against a
hand-written four-tap sum, the gated norm against its formula."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import ssd, ssd_kernel

B, T, H, P, G, N = 2, 24, 4, 8, 2, 16


def _inputs(seed=0, t=T, wide=True):
    """Decays drawn WIDE: ``dt A`` from about -0.002 to -3 a step, so
    some heads forget within a token and some carry the whole
    sequence — a dead carry between chunks then shows."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, t, H, P), jnp.float32)
    dt = jnp.exp(jax.random.uniform(
        k[1], (B, t, H), jnp.float32, np.log(1e-3), np.log(0.3 if wide else 0.01)
    ))
    A = -jax.random.uniform(k[2], (H,), jnp.float32, 1.0, 10.0)
    Bm = jax.random.normal(k[3], (B, t, G, N), jnp.float32)
    Cm = jax.random.normal(k[4], (B, t, G, N), jnp.float32)
    D = jax.random.normal(k[5], (H,), jnp.float32)
    return x, dt, A, Bm, Cm, D


# the whole sequence one chunk; many chunks; a length that is no
# multiple of the chunk; a chunk longer than the sequence
CHUNKS = [(T, T), (T, 4), (T, 8), (T - 3, 8), (T, 64)]


@pytest.mark.parametrize("t,chunk", CHUNKS, ids=str)
def test_chunked_scan_equals_the_recurrence(t, chunk):
    args = _inputs(t=t)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_scan(*args, chunk)
        want = ssd.ssd_reference(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,chunk", CHUNKS, ids=str)
def test_chunked_scan_gradients_equal_the_recurrences(t, chunk):
    args = _inputs(seed=1, t=t)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, t, H, P), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(
            loss(lambda *a: ssd.ssd_scan(*a, chunk)), argnums=range(6)
        )(*args)
        want = jax.grad(loss(ssd.ssd_reference), argnums=range(6))(*args)
    for name, g, w_ in zip("x dt A B C D".split(), got, want):
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * float(jnp.max(jnp.abs(w_))),
            err_msg=name,
        )


def test_a_dead_carry_between_chunks_fails(monkeypatch):
    """The test above can tell: with the carry cut the chunked form
    misses the reference by far more than its tolerance."""
    args = _inputs()
    monkeypatch.setattr(
        ssd, "_carried_states", lambda states, total: jnp.zeros_like(states)
    )
    got = ssd.ssd_scan(*args, 4)
    want = ssd.ssd_reference(*args)
    assert float(jnp.max(jnp.abs(got - want))) > 0.1


def test_scan_counters_read_the_decay_and_the_carried_state():
    args = _inputs()
    x, dt, A = args[:3]
    _, one = ssd.ssd_scan(*args, T, with_stats=True)
    _, many = ssd.ssd_scan(*args, 8, with_stats=True)
    # one chunk: the cumulative dt A of the whole sequence, no carry
    np.testing.assert_allclose(
        one[0], jnp.min(jnp.sum(dt * A, axis=1)), rtol=1e-5)
    assert float(one[1]) == 0.0
    per_chunk = jnp.sum((dt * A).reshape(B, T // 8, 8, H), axis=2)
    np.testing.assert_allclose(many[0], jnp.min(per_chunk), rtol=1e-5)
    assert float(many[1]) > 0.0


def test_scan_in_bfloat16_keeps_float32_decays():
    """bf16 operands, float32 decays and accumulation: within bf16's
    rounding of the float32 result, not of a bf16 cumulative sum."""
    args = _inputs(wide=False)
    want = ssd.ssd_reference(*args)
    x, dt, A, Bm, Cm, D = args
    got = ssd.ssd_scan(
        x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16),
        Cm.astype(jnp.bfloat16), D, 8,
    )
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.max(err)) < 0.05 * float(jnp.max(jnp.abs(want)))


def test_convolution_is_a_causal_four_tap_sum():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k[0], (2, 7, 5), jnp.float32)
    w = jax.random.normal(k[1], (4, 5), jnp.float32)
    b = jax.random.normal(k[2], (5,), jnp.float32)
    got = np.asarray(ssd.causal_conv_silu(x, w, b))
    xs = np.asarray(x)
    for t in range(7):
        pre = np.asarray(b).copy()
        for tap in range(4):
            src = t - 3 + tap       # the last tap is the token itself
            if src >= 0:
                pre = pre + np.asarray(w)[tap] * xs[:, src]
        np.testing.assert_allclose(
            got[:, t], pre / (1 + np.exp(-pre)), rtol=1e-5, atol=1e-6)
    # the first token sees itself alone
    pre0 = np.asarray(b) + np.asarray(w)[3] * xs[:, 0]
    np.testing.assert_allclose(
        got[:, 0], pre0 / (1 + np.exp(-pre0)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_groups", [1, 2])
def test_gated_norm_gates_before_the_statistic(n_groups):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    y = jax.random.normal(k[0], (2, 5, 8), jnp.float32)
    z = jax.random.normal(k[1], (2, 5, 8), jnp.float32)
    w = jax.random.normal(k[2], (8,), jnp.float32)
    g = np.asarray(y) * (np.asarray(z) / (1 + np.exp(-np.asarray(z))))
    grouped = g.reshape(2, 5, n_groups, -1)
    want = (
        grouped / np.sqrt((grouped ** 2).mean(-1, keepdims=True) + 1e-5)
    ).reshape(2, 5, 8) * np.asarray(w)
    np.testing.assert_allclose(
        ssd.gated_rms_norm(y, z, w, 1e-5, n_groups), want,
        rtol=1e-5, atol=1e-6)
    # not the norm of y gated afterwards
    ys, silu_z = np.asarray(y), g / np.asarray(y)
    after = (ys / np.sqrt((ys ** 2).mean(-1, keepdims=True) + 1e-5)
             * np.asarray(w) * silu_z)
    assert np.max(np.abs(want - after)) > 0.1


def test_initial_values_are_mamba2s():
    sizes = dict(n_heads=8, head_dim=8, d_state=16, n_groups=1)
    assert ssd.mamba_sizes(**sizes) == (64, 96, 168)
    p = ssd.mamba_init(
        jax.random.PRNGKey(0), 32, d_conv=4,
        dense=lambda key, shape: jnp.zeros(shape), **sizes,
    )
    dt = jax.nn.softplus(p["ssm_dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001
    a = jnp.exp(p["ssm_a_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert p["ssm_in"].shape == (32, 168) and p["ssm_out"].shape == (64, 32)
    assert p["ssm_conv_w"].shape == (4, 96)
    assert float(jnp.abs(p["ssm_conv_w"]).max()) <= 0.5
    np.testing.assert_array_equal(p["ssm_d"], 1.0)


# -- the Pallas kernels (``ops/ssd_kernel.py``) in the interpreter ----------
#
# At the published widths of a head (P 64) and of the state (N 128),
# few heads and short sequences.

KP, KN, KL = 64, 128, 128


def _kernel_inputs(seed, t, h, g, dtype, b=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, t, h, KP), jnp.float32)
    dt = jnp.exp(jax.random.uniform(
        k[1], (b, t, h), jnp.float32, np.log(1e-3), np.log(0.3)))
    A = -jax.random.uniform(k[2], (h,), jnp.float32, 1.0, 10.0)
    Bm = jax.random.normal(k[3], (b, t, g, KN), jnp.float32)
    Cm = jax.random.normal(k[4], (b, t, g, KN), jnp.float32)
    D = jax.random.normal(k[5], (h,), jnp.float32)
    return x.astype(dtype), dt, A, Bm.astype(dtype), Cm.astype(dtype), D


def _on_kernels(args, chunk, **kw):
    """The scan through the kernels in the interpreter, over the rule's
    tiles — or, for two groups of two heads, over a block of heads the
    chip's tiling would not take and the interpreter does (as the
    flash kernels' tests hand it blocks of their own)."""
    x, B = args[0], args[3]
    h, g = x.shape[2], B.shape[2]
    tiles = ssd_kernel.scan_tiles(
        x.shape[1], chunk, x.shape[3], B.shape[3], h // g, h)
    if tiles is None:
        tiles = ssd_kernel.ScanTiles(chunk, h // g, 128)
    return ssd._scan_on_kernels(*args, tiles, interpret=True, **kw)


# (T, chunk, heads, groups): one chunk; several; two groups of heads;
# a chunk of two blocks of the mask (the blocks above the diagonal are
# never built) and two batch rows
KERNEL_SHAPES = [(128, 128, 2, 1), (384, 128, 2, 1), (256, 128, 4, 2),
                 (512, 256, 2, 1)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("t,chunk,h,g", KERNEL_SHAPES, ids=str)
def test_kernel_scan_equals_xlas_form_and_the_recurrence(t, chunk, h, g, dtype):
    args = _kernel_inputs(3, t, h, g, dtype, b=2 if chunk == 256 else 1)
    with jax.default_matmul_precision("highest"):
        got, got_stats = jax.jit(
            lambda *a: _on_kernels(a, chunk, with_stats=True))(*args)
        xla, xla_stats = jax.jit(
            lambda *a: ssd.ssd_scan(*a, chunk, with_stats=True))(*args)
        want = jax.jit(ssd.ssd_reference)(
            *(a.astype(jnp.float32) for a in args))
    assert got.dtype == dtype
    # both counters, equal on both paths
    np.testing.assert_allclose(got_stats, xla_stats, rtol=1e-3)
    assert (float(got_stats[1]) > 0) == (t > chunk)
    got, xla = got.astype(jnp.float32), xla.astype(jnp.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
        np.testing.assert_allclose(got, xla, rtol=2e-4, atol=2e-3)
    else:       # as near the float32 recurrence as XLA's form comes
        scale = float(jnp.max(jnp.abs(want)))
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 0.05 * scale
        assert err < 2 * float(jnp.max(jnp.abs(xla - want))) + 1e-3 * scale


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("t,chunk,h,g", KERNEL_SHAPES[1:], ids=str)
def test_kernel_scan_gradients_equal_autodiffs_of_xlas_form(
        t, chunk, h, g, dtype):
    args = _kernel_inputs(4, t, h, g, dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape, jnp.float32)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=range(6)))

    with jax.default_matmul_precision("highest"):
        got = grads(lambda *a: _on_kernels(a, chunk))(*args)
        xla = grads(lambda *a: ssd.ssd_scan(*a, chunk))(*args)
        want = grads(ssd.ssd_reference)(
            *(a.astype(jnp.float32) for a in args))
    for name, g_, x_, w_ in zip("x dt A B C D".split(), got, xla, want):
        assert g_.dtype == x_.dtype, name
        g_, x_ = g_.astype(jnp.float32), x_.astype(jnp.float32)
        top = float(jnp.max(jnp.abs(w_)))
        if dtype == jnp.float32:    # the limits of the XLA form's own test
            np.testing.assert_allclose(
                g_, w_, rtol=2e-3, atol=2e-3 * top, err_msg=name)
            np.testing.assert_allclose(
                g_, x_, rtol=2e-3, atol=2e-3 * top, err_msg=name)
        else:
            # bfloat16 operands: no further from the float32 recurrence
            # than autodiff of XLA's form is (the log-decay's gradient
            # is a difference of large sums: an operand rounded on one
            # side of it alone puts dA off by a tenth and more)
            norm = float(jnp.linalg.norm(w_))
            err = float(jnp.linalg.norm(g_ - w_))
            assert err < 0.03 * norm, name
            assert err < 2 * float(jnp.linalg.norm(x_ - w_)) + 1e-3 * norm, name


# what the rule picks: the kernels where chunk, state and heads tile
# (as many heads a grid step as make 1024 rows of x), XLA's form for a
# padded tail, a chunk or a state that is no multiple of 128, channels
# that fill no 16-row tile, a block of heads that is neither a
# multiple of eight nor all of them
RULE = [
    ((8192, 256, 64, 128, 64, 64), (256, 16, 128)),
    ((384, 128, 64, 128, 2, 2), (128, 2, 128)),
    ((256, 128, 64, 128, 8, 16), (128, 8, 128)),
    ((256, 128, 128, 128, 4, 4), (128, 4, 128)),
    ((8192, 256, 32, 128, 64, 64), (256, 32, 128)),
    ((256, 128, 64, 128, 1, 1), (128, 1, 128)),
    ((200, 128, 64, 128, 2, 2), None),
    ((T, 8, P, N, H // G, H), None),
    ((256, 128, 64, 16, 2, 2), None),
    ((256, 128, 40, 128, 2, 2), None),
    ((256, 128, 64, 128, 2, 4), None),
]


@pytest.mark.parametrize("shape,want", RULE, ids=str)
def test_the_rule_says_which_path_a_shape_takes(shape, want, monkeypatch):
    got = ssd_kernel.scan_tiles(*shape)
    assert got == (want and ssd_kernel.ScanTiles(*want))
    # off the TPU ``ssd_scan`` keeps XLA's form whatever the shape ...
    assert ssd.scan_kernel_tiles(*shape) is None
    # ... and on one it asks the rule
    from theanompi_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert ssd.scan_kernel_tiles(*shape) == got


def test_ssd_scan_takes_the_kernels_where_the_rule_says(monkeypatch):
    """``ssd_scan`` itself, steered as a TPU would steer it (the
    interpreter in the kernels' place): a shape the rule takes goes
    through ``ssd_chunks``, a length that is no multiple of the chunk
    through XLA's form, and both equal the recurrence."""
    calls = []
    monkeypatch.setattr(ssd, "scan_kernel_tiles", ssd_kernel.scan_tiles)
    real = ssd_kernel.ssd_chunks

    def counted(*a):
        calls.append(a[-2])
        return real(*a[:-1], True)

    monkeypatch.setattr(ssd_kernel, "ssd_chunks", counted)
    for t in (256, 200):
        args = _kernel_inputs(5, t, 2, 1, jnp.float32)
        with jax.default_matmul_precision("highest"):
            got = ssd.ssd_scan(*args, KL)
            want = ssd.ssd_reference(*args)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
        # T 256 went through the kernels, T 200 (a padded tail) did not
        assert calls == [ssd_kernel.ScanTiles(128, 2, 128)]


def test_a_dead_carry_between_chunks_fails_on_the_kernels_too(monkeypatch):
    """The kernels carry the state in scratch.  With the carry cut —
    ``exp(cum)`` and ``exp(total)`` of ``_chunk_rows`` zeroed: what a
    chunk reads from the state it starts from and what it hands on —
    the output misses the recurrence by far more than the tolerance."""
    args = _kernel_inputs(6, 384, 2, 1, jnp.float32)
    want = ssd.ssd_reference(*args)
    with jax.default_matmul_precision("highest"):
        sound, stats = _on_kernels(args, KL, with_stats=True)
    assert float(jnp.max(jnp.abs(sound - want))) < 2e-3
    assert float(stats[1]) > 0.0
    real = ssd_kernel._chunk_rows

    def no_carry(*a):
        dt, cum, e, w, e_end = real(*a)
        return dt, cum, 0.0 * e, w, 0.0 * e_end

    monkeypatch.setattr(ssd_kernel, "_chunk_rows", no_carry)
    dead = _on_kernels(args, KL)
    assert float(jnp.max(jnp.abs(dead - want))) > 0.1
