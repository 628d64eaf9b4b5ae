"""Grouped-query attention's projections (PR 45): the one-pass
rotation ``rope`` against the stride-2 form it replaced, and
``Llama._gqa_qkv`` — products that write the kernels' layout, QK-norm
over that layout, one rotation pass — against the form it replaced,
``_heads(col_parallel(..))`` and the stride-2 rotation.  The old forms
live on HERE, as oracles; float32 and bfloat16, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.models.llama import (Llama, _heads, rms_norm, rope,
                                        rope_table)
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel import tp as tp_lib

YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 64, "beta_fast": 32.0,
        "beta_slow": 1.0}


def rope_stride2(x, pos, theta=10000.0, inv_freq=None, factor=1.0):
    """The oracle: ``rope`` as it was until PR 45 — the pairs' two
    halves cut out by stride-2 lane slices, rotated, stacked and
    reshaped back."""
    d = x.shape[-1]
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]    # [T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def rope_tail_stride2(x, pos, theta, inv_freq, factor, nope):
    """The oracle with ``nope`` leading channels: a slice at ``nope``,
    the stride-2 form on the tail, joined back."""
    if not nope:
        return rope_stride2(x, pos, theta, inv_freq, factor)
    return jnp.concatenate([
        x[..., :nope],
        rope_stride2(x[..., nope:], pos, theta, inv_freq, factor),
    ], axis=-1)


@pytest.mark.parametrize("nope", [0, 12, 16])
@pytest.mark.parametrize("table", ["theta", "yarn"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rope_is_the_stride2_form(dtype, table, nope):
    """One pass ``x * cos + (x @ swap) * sin`` against the sliced
    form, by ``rope_theta`` and by a YaRN table with its factor, with
    and without leading channels that carry no position: the values
    are the same float32 expression (bfloat16 results equal bit for
    bit), and the gradient — the same pass at the negative angle,
    rounded once — is autodiff's of the oracle within the dtype's
    rounding."""
    d = 32
    x = jax.random.normal(jax.random.key(21), (2, 3, 8, d)).astype(dtype)
    ct = jax.random.normal(jax.random.key(22), x.shape).astype(dtype)
    pos = jnp.arange(5, 13)
    inv, factor = (
        (None, 1.0) if table == "theta" else rope_table(YARN, d - nope))
    assert table == "theta" or factor > 1.05

    got, got_vjp = jax.vjp(
        lambda x: rope(x, pos, 1e4, inv, factor, nope), x)
    want, want_vjp = jax.vjp(
        lambda x: rope_tail_stride2(x, pos, 1e4, inv, factor, nope), x)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    f32 = lambda a: np.asarray(a, np.float32)
    if dtype == "bfloat16":
        np.testing.assert_array_equal(f32(got), f32(want))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(f32(got[..., :nope]), f32(x[..., :nope]))
    tol = 1e-6 if dtype == "float32" else 2 ** -7
    dx, dx_want = got_vjp(ct)[0], want_vjp(ct)[0]
    assert dx.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(f32(dx), f32(dx_want), rtol=tol, atol=tol)


def test_a_rotation_turns_back_by_its_own_backward_rule():
    """The backward rule is the rotation by the negative angle: with
    factor 1 it undoes the forward to float32's rounding."""
    x = jax.random.normal(jax.random.key(23), (1, 2, 16, 16), jnp.float32)
    pos = jnp.arange(16)
    y, vjp = jax.vjp(lambda x: rope(x, pos, 1e4, nope=4), x)
    np.testing.assert_allclose(vjp(y)[0], x, rtol=1e-5, atol=1e-5)


def _gqa_qkv_relaid(model, p, xn, pos, kind="full_attention"):
    """The oracle: ``_gqa_kind``'s operands as it built them until
    PR 45 — flat products, QK-norm over the flat row, ``_heads`` on
    each, the stride-2 rotation, the repeat."""
    eps = model.norm_eps
    h_loc = model.n_heads // model.tp
    hkv_loc = model.n_kv_heads // model.tp
    hd = model.head_dim
    q = tp_lib.col_parallel(xn, p["wq"])
    k = tp_lib.col_parallel(xn, p["wk"])
    if model.qk_norm:
        q = rms_norm(q, p["q_norm"], eps, model.n_heads * hd)
        k = rms_norm(k, p["k_norm"], eps, model.n_kv_heads * hd)
    q, k = _heads(q, h_loc, hd), _heads(k, hkv_loc, hd)
    v = _heads(tp_lib.col_parallel(xn, p["wv"]), hkv_loc, hd)
    inv_freq, factor = model._rope_tables[kind]
    q = rope_stride2(q, pos, model.rope_theta, inv_freq, factor)
    k = rope_stride2(k, pos, model.rope_theta, inv_freq, factor)
    rep = h_loc // hkv_loc
    if rep != 1:
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    return q, k, v


GQA = {
    "8:1": dict(n_heads=8, n_kv_heads=1),
    "4:1": dict(n_heads=8, n_kv_heads=2),
    "1:1": dict(n_heads=4, n_kv_heads=4),
    "1:1 qk_norm": dict(n_heads=4, n_kv_heads=4, qk_norm=True),
}


def _operands_and_gradients(form, model, lp, xn, cts, tp):
    """``form``'s q, k, v and, under the cotangents ``cts``, the
    gradients of the leaves and of ``xn``, inside the vma-checked
    ``shard_map`` the step uses (a ``model`` axis of ``tp``)."""
    mesh = make_mesh(devices=jax.devices()[:tp], model=tp)
    heads = P(None, "model")
    leaf = {"wq": heads, "wk": heads, "wv": heads,
            "q_norm": P("model"), "k_norm": P("model")}
    specs = {name: leaf[name] for name in lp}

    def scalar(lp, xn, *cts):
        qkv = form(lp, xn)
        total = sum(jnp.sum(a.astype(jnp.float32) * c)
                    for a, c in zip(qkv, cts))
        return lax.psum(total, "model"), qkv

    def run(lp, xn, *cts):
        (_, qkv), grads = jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True)(lp, xn, *cts)
        return qkv, grads

    return jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(specs, P(), heads, heads, heads),
        out_specs=((heads, heads, heads), (specs, P())),
    ))(lp, xn, *cts)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("group", GQA, ids=str)
def test_gqa_operands_equal_the_relaid_form(group, dtype, tol, tp):
    """``_gqa_qkv`` writes the kernels' operands from products in the
    kernels' layout and one rotation pass; the relaid form above is
    the same mathematics.  q, k, v (in bfloat16 v, the same product
    column for column, bit for bit) and, under random cotangents, the
    gradients of ``xn``, ``wq``, ``wk``, ``wv`` (and ``q_norm``,
    ``k_norm``), for 8, 4 and 1 query heads a key head, with QK-norm,
    alone and under a ``model`` axis of 2."""
    knobs = dict(GQA[group])
    if knobs["n_kv_heads"] < tp:     # whole key heads a shard
        knobs = {k: v * tp if k != "qk_norm" else v for k, v in knobs.items()}
    hd, t, b = 16, 24, 2
    model = Llama(dict(
        knobs, dim=48, head_dim=hd, n_layers=1, ffn_dim=64, vocab=64,
        seq_len=t, batch_size=b, compute_dtype=dtype, tp=tp))
    params = model._init_full_params(jax.random.key(31))
    names = ("wq", "wk", "wv") + (
        ("q_norm", "k_norm") if model.qk_norm else ())
    lp = {name: params["layers"][0][name] for name in names}
    if model.qk_norm:                # weights that are not all ones
        lp["q_norm"] = 1 + 0.1 * jax.random.normal(
            jax.random.key(32), lp["q_norm"].shape)
        lp["k_norm"] = 1 + 0.1 * jax.random.normal(
            jax.random.key(33), lp["k_norm"].shape)
    xn = jax.random.normal(jax.random.key(34), (b, t, model.dim)).astype(dtype)
    pos = jnp.arange(t)
    cts = [jax.random.normal(jax.random.key(35 + i),
                             (b, model.n_heads, t, hd)) for i in range(3)]

    got, got_g = _operands_and_gradients(
        lambda lp, xn: model._gqa_qkv(lp, xn, pos, "full_attention"),
        model, lp, xn, cts, tp)
    want, want_g = _operands_and_gradients(
        lambda lp, xn: _gqa_qkv_relaid(model, lp, xn, pos),
        model, lp, xn, cts, tp)
    f32 = lambda a: np.asarray(a, np.float32)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape == (b, model.n_heads, t, hd), name
        assert g.dtype == w.dtype == jnp.dtype(dtype), name
        assert np.abs(f32(g) - f32(w)).max() <= tol * np.abs(f32(w)).max(), name
    if dtype == "bfloat16":
        np.testing.assert_array_equal(f32(got[2]), f32(want[2]))
    assert set(got_g[0]) == set(names)
    for name, g, w in [("xn", got_g[1], want_g[1])] + [
            (n, got_g[0][n], want_g[0][n]) for n in names]:
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.abs(f32(g) - f32(w)).max() <= tol * np.abs(f32(w)).max(), name
