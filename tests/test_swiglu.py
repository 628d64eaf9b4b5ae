"""``ops.layers.swiglu``: the dense MLP's ``silu(g) * u`` with a
backward that hands its consumers arrays (PR 27).

The function may change WHERE the gradient of the activation is
computed, never what it is: value and both gradients are held to
autodiff of the plain expression — alone, under ``jax.checkpoint``,
through one dense ``Llama`` block with its columns sharded ``tp=2``
under the vma-checked ``shard_map``, and through a whole train step
(the parameter update against the plain formulation's).  That the
TPU compiler then keeps the gradient out of the products' operands is
``tests/test_chip_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.models import llama as llama_mod
from theanompi_tpu.models.llama import Llama
from theanompi_tpu.ops.layers import swiglu
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.utils import Recorder


def plain(g, u):
    """What ``Llama._layer`` computed before ``swiglu`` existed."""
    return jax.nn.silu(g) * u


# float32 to 1e-6; bfloat16 to its rounding (8 bits of mantissa)
TOL = {"float32": 1e-6, "bfloat16": 2.0 ** -7}


def _close(got, want, dtype):
    assert got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


def _inputs(dtype, shape=(4, 24, 64)):
    kg, ku, kh = jax.random.split(jax.random.PRNGKey(27), 3)
    return tuple(
        (3.0 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        for k in (kg, ku, kh)
    )


@pytest.mark.parametrize("wrap", ["bare", "checkpoint", "jit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_value_and_both_gradients_match_autodiff(dtype, wrap):
    g, u, dh = _inputs(dtype)
    around = {"bare": lambda f: f, "checkpoint": jax.checkpoint,
              "jit": jax.jit}[wrap]
    out, vjp = jax.vjp(around(swiglu), g, u)
    want, want_vjp = jax.vjp(around(plain), g, u)
    _close(out, want, dtype)
    for got, ref in zip(vjp(dh), want_vjp(dh)):
        _close(got, ref, dtype)


def test_backward_is_one_named_pass_behind_a_barrier():
    """The traced backward: the ``mlp_act_grad`` scope brackets the
    gradient and an ``optimization_barrier`` pins the pair."""
    g, u, dh = _inputs("bfloat16", (2, 8, 16))
    text = jax.jit(
        lambda g, u, dh: jax.vjp(swiglu, g, u)[1](dh)
    ).lower(g, u, dh).as_text(debug_info=True)
    assert "optimization_barrier" in text
    assert "mlp_act_grad" in text
    # the forward alone has neither: serving traces the plain product
    forward = jax.jit(swiglu).lower(g, u).as_text(debug_info=True)
    assert "optimization_barrier" not in forward
    assert "mlp_act_grad" not in forward


SMALL = dict(
    dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
    vocab=32, seq_len=32, batch_size=4, lr=0.5, optimizer="sgd",
    n_train=64, n_val=32, remat=True,
)


def _model(devices, *, data=1, tp=1, **over):
    m = Llama(dict(SMALL, tp=tp, **over))
    m.build_model(n_replicas=data)
    m.compile_iter_fns(mesh=make_mesh(
        data=data, model=tp, devices=devices[: data * tp]
    ))
    return m


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_layer_tp2_under_checked_shard_map(devices8, monkeypatch,
                                                 dtype):
    """One dense block, ``w_gate``/``w_up`` column shards on two
    devices: output and the gradient of every leaf and of the input
    equal the plain formulation's."""
    m = _model(devices8, tp=2, compute_dtype=dtype)
    specs = m._specs["layers"][0]
    p = m.params["layers"][0]
    x = jax.random.normal(
        jax.random.PRNGKey(3), (2, SMALL["seq_len"], SMALL["dim"]),
        jnp.float32,
    ).astype(dtype)

    def run():
        # everything traced is built anew: a cached trace would not
        # see the patched global
        layer = jax.checkpoint(m._layer)

        def body(p, x):
            def loss(p, x):
                y = layer(p, x, jnp.arange(x.shape[1]))
                return jnp.sum(y.astype(jnp.float32) ** 2), y
            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(p, x)
            return y, grads

        return jax.jit(jax.shard_map(
            body, mesh=m.mesh, in_specs=(specs, P()),
            out_specs=(P(), (specs, P())),
        ))(p, x)

    y, (dp, dx) = run()
    monkeypatch.setattr(llama_mod, "swiglu", plain)
    y0, (dp0, dx0) = run()
    _close(y, y0, dtype)
    _close(dx, dx0, dtype)
    for name in dp0:
        scale = float(jnp.max(jnp.abs(dp0[name])))
        np.testing.assert_allclose(
            np.asarray(dp[name], np.float32) / scale,
            np.asarray(dp0[name], np.float32) / scale,
            rtol=0, atol=TOL[dtype], err_msg=name,
        )


@pytest.mark.parametrize("layout", [dict(data=1), dict(data=2, tp=2)],
                         ids=["1chip", "dp2xtp2"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_train_step_update_equals_plain_formulation(devices8, monkeypatch,
                                                    dtype, layout):
    """One SGD step of the whole model: loss and every leaf's update
    equal the plain formulation's."""
    def one_update():
        m = _model(devices8, compute_dtype=dtype,
                   batch_size=4 // layout["data"], **layout)
        before = jax.tree.map(np.asarray, jax.device_get(m.params))
        rec = Recorder(rank=0)
        m.train_iter(0, rec)
        rec.flush()
        after = jax.device_get(m.params)
        return rec.train_losses[-1], jax.tree.map(
            lambda a, b: np.asarray(b) - a, before, after)

    loss, delta = one_update()
    monkeypatch.setattr(llama_mod, "swiglu", plain)
    loss0, delta0 = one_update()
    assert loss == loss0            # the forward is the same program
    flat0 = dict(jax.tree_util.tree_leaves_with_path(delta0))
    for path, d in jax.tree_util.tree_leaves_with_path(delta):
        d0 = flat0[path]
        scale = float(np.max(np.abs(d0)))
        assert scale > 0, path
        np.testing.assert_allclose(
            d / scale, d0 / scale, rtol=0, atol=TOL[dtype],
            err_msg=jax.tree_util.keystr(path),
        )
