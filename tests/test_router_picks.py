"""The sigmoid router's picked scores (``parallel/moe.router_topk``)
against the plain form kept here: an element gather of the scores at
``top_k``'s picks.  The program's form has no gather (PERF.md, PR 56)
and has to give the same bits, ties included, and the same gradient
where the gates carry one."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from theanompi_tpu.parallel import moe

N = 96
SHAPES = [(4, 64), (22, 512), (3, 8)]


def _plain(x2, w_router, top_k, renormalize, select_bias, scale):
    logits = x2.astype(jnp.float32) @ w_router.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    chosen = scores
    if select_bias is not None:
        chosen = scores + lax.stop_gradient(select_bias.astype(jnp.float32))
    _, eidx = lax.top_k(chosen, top_k)
    gates = jnp.take_along_axis(scores, eidx, axis=-1)
    if renormalize:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * scale, eidx, scores, logits


def _router(x2, w_router, top_k, renormalize, select_bias, scale):
    return moe.router_topk(x2, w_router, top_k, renormalize, scoring="sigmoid",
                           select_bias=select_bias, scale=scale)


def _draw(e, bias, ties, seed=0):
    """Logits through an identity router (the product is exact), so
    the scores are the test's to set.  ``ties``: the logits are a few
    levels (sigmoids sixteenths of the range apart and more) and the
    bias one of two values, fewer pairs than experts, so every row
    has equal ``chosen`` and most rows a tie across the k-th pick."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((N, e)).astype(np.float32)
    b = (0.3 * rng.standard_normal(e)).astype(np.float32) if bias else None
    if ties:
        step = 1.0 if e <= 8 else 0.5
        logits = np.clip(np.round(logits / step) * step, -1, 1).astype(np.float32)
        b = (0.25 * (np.arange(e) % 2)).astype(np.float32) if bias else None
    return (jnp.asarray(logits), jnp.eye(e, dtype=jnp.float32),
            None if b is None else jnp.asarray(b))


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("renormalize", [True, False], ids=["renorm", "raw"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("k,e", SHAPES)
def test_the_picks_and_gates_are_the_plain_forms(k, e, bias, renormalize, ties):
    x2, w, b = _draw(e, bias, ties)
    if ties:
        chosen = np.asarray(jax.nn.sigmoid(x2)) + (0 if b is None else np.asarray(b))
        assert all(len(np.unique(row)) < e for row in chosen)
    want = jax.jit(functools.partial(_plain, top_k=k, renormalize=renormalize,
                                     scale=1.8))(x2, w, select_bias=b)
    got = jax.jit(functools.partial(_router, top_k=k, renormalize=renormalize,
                                    scale=1.8))(x2, w, select_bias=b)
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt))
    assert got[1].dtype == want[1].dtype and got[0].dtype == want[0].dtype


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("k,e", SHAPES)
def test_the_gates_gradient_is_the_gathers_transpose(k, e, bias, ties):
    """A router whose gates carry the gradient (no held range): the
    gradients to ``w_router`` and ``x2`` — through the renormalised
    picked scores and through the ``[N, E]`` scores that feed
    ``aux["p"]`` — are the plain form's."""
    x2, w, b = _draw(e, bias, ties, seed=1)
    rng = np.random.default_rng(2)
    wg = jnp.asarray(rng.standard_normal((N, k)).astype(np.float32))
    wp = jnp.asarray(rng.standard_normal((N, e)).astype(np.float32))

    def grads(route):
        def loss(x2, w):
            gates, _, probs, _ = route(x2, w, k, True, b, 2.5)
            return jnp.sum(gates * wg) + jnp.sum(probs * wp)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(x2, w)

    for got, want in zip(grads(_router), grads(_plain)):
        assert np.abs(np.asarray(want)).max() > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_no_gather_is_left_in_the_lowered_router():
    """Forward and gradient of a sigmoid router lower without a
    gather or a scatter; the plain form's has both."""
    x2, w, b = _draw(64, True, False)

    def text(route):
        loss = lambda x2, w: jnp.sum(route(x2, w, 4, True, b, 1.0)[0] ** 2)  # noqa: E731
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x2, w).as_text()

    assert "gather" in text(_plain) and "scatter" in text(_plain)
    got = text(_router)
    assert "gather" not in got and "scatter" not in got
    assert got.count("stablehlo.sort") == 1
