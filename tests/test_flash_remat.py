"""What the per-layer remat keeps, and that keeping it skips a kernel.

A layer's attention needs three kernels: forward, dK/dV, dQ.  Under
``jax.checkpoint`` with no policy the backward runs a fourth, the
forward kernel again, because the backward rule of ``_flash`` reads
the forward rule's own ``out`` and ``lse``.  ``ops/attention.py``
names those two (``FLASH_RESIDUALS``) and ``Llama._forward`` saves
them, so the replay holds no kernel.  Counted here in the gradient's
jaxpr (``pallas_call`` equations; the kernels themselves run in the
Pallas interpreter), for a bare layer function and for the model's
own train step; ``tests/test_chip_compile.py`` counts the same in a
text compiled for the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from theanompi_tpu.models.llama import Llama
from theanompi_tpu.ops import attention
from theanompi_tpu.ops.attention import FLASH_RESIDUALS, flash_attention_tpu
from theanompi_tpu.parallel import make_mesh

B, H, T, D = 2, 2, 32, 16


def _count(jaxpr, primitive="pallas_call"):
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


def _layer(causal, t_k, name_after_call=None):
    """A stand-in for a decoder block: projections from ``x``, the
    flash kernel, an output projection.  K and V come from the first
    ``t_k`` positions."""
    def layer(w, x):
        def heads(a):
            return a.reshape(B, -1, H, D).transpose(0, 2, 1, 3)

        q, k, v = heads(x @ w), heads(x[:, :t_k] @ (0.5 * w)), \
            heads(x[:, :t_k] @ (2.0 * w))
        o = flash_attention_tpu(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        if name_after_call:
            o = checkpoint_name(o, name_after_call)
        return jnp.tanh(o.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ w)

    return layer


def _grad(layer, policy):
    return jax.grad(
        lambda w, x: jax.checkpoint(layer, policy=policy)(w, x).sum(),
        argnums=(0, 1),
    )


def _inputs():
    kw, kx = jax.random.split(jax.random.key(0))
    return (0.2 * jax.random.normal(kw, (H * D, H * D)),
            jax.random.normal(kx, (B, T, H * D)))


def _model_policy():
    return jax.checkpoint_policies.save_only_these_names(
        *Llama(dict(remat=True)).remat_saves
    )


CASES = pytest.mark.parametrize(
    "causal,t_k", [(True, T), (False, T), (True, T // 2), (False, T // 2)],
    ids=["causal-equal", "full-equal", "causal-short_k", "full-short_k"],
)


@CASES
def test_policy_drops_the_replayed_forward_kernel(causal, t_k):
    layer, args = _layer(causal, t_k), _inputs()
    kept = jax.make_jaxpr(_grad(layer, _model_policy()))(*args)
    full = jax.make_jaxpr(_grad(layer, None))(*args)
    assert (_count(kept.jaxpr), _count(full.jaxpr)) == (3, 4)


@CASES
def test_gradient_is_bitwise_full_remats(causal, t_k):
    layer, args = _layer(causal, t_k), _inputs()
    kept = jax.jit(_grad(layer, _model_policy()))(*args)
    full = jax.jit(_grad(layer, None))(*args)
    for a, b in zip(kept, full):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.abs(a).max()) > 0


def test_a_name_after_the_call_skips_nothing():
    """The knob this replaced (``remat_save=("attn_out",)``) named the
    layer's copy of the output: the array is saved and the kernel
    still runs again, for the logsumexp.  Do not put it back."""
    layer, args = _layer(True, T, name_after_call="attn_out"), _inputs()
    policy = jax.checkpoint_policies.save_only_these_names("attn_out")
    assert _count(jax.make_jaxpr(_grad(layer, policy))(*args).jaxpr) == 4
    both = jax.checkpoint_policies.save_only_these_names(
        "attn_out", *FLASH_RESIDUALS
    )
    assert _count(jax.make_jaxpr(_grad(layer, both))(*args).jaxpr) == 3


TINY = dict(
    dim=32, n_heads=2, n_kv_heads=1, ffn_dim=64, vocab=32, seq_len=T,
    batch_size=2, n_train=8, n_val=4, compute_dtype="float32",
)


@pytest.mark.parametrize("remat", [True, False])
def test_model_says_what_its_remat_keeps(remat):
    # the key of the knob this replaced is read by nothing
    model = Llama(dict(TINY, n_layers=1, remat=remat,
                       remat_save=("attn_out",)))
    assert model.remat_saves == (FLASH_RESIDUALS if remat else ())


@pytest.mark.parametrize(
    "over,per_layer",
    [({}, 3), ({"remat": False}, 3), ({"saves": ()}, 4),
     ({"n_experts": 4, "moe_top_k": 2, "capacity_factor": None}, 3)],
    ids=["remat", "no_remat", "policy_bypassed", "moe"],
)
def test_train_step_runs_three_flash_kernels_a_layer(
    monkeypatch, over, per_layer
):
    """The model's own train step, traced with the kernel path taken
    as on the chip (nothing is lowered, so no kernel compiles)."""
    over = dict(over)
    saves = over.pop("saves", None)
    n_layers = 2
    model = Llama(dict(TINY, n_layers=n_layers, **over))
    if saves is not None:
        model.remat_saves = saves       # full remat: what the parent ran
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    x, y = model.put_batch(model.data.train_batch(0))
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    jaxpr = jax.make_jaxpr(model.train_step_fn)(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(model.current_lr),
    )
    assert _count(jaxpr.jaxpr) == per_layer * n_layers


@pytest.mark.parametrize("remat", [True, False])
def test_worker_summary_names_what_remat_keeps(remat):
    from theanompi_tpu.workers import bsp_worker

    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama",
        config=dict(TINY, n_layers=1, n_epochs=1, remat=remat, seed=3),
        verbose=False,
    )
    assert res["remat_saves"] == (list(FLASH_RESIDUALS) if remat else [])


def test_worker_summary_of_a_model_without_layer_remat():
    from theanompi_tpu.workers import bsp_worker

    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.wresnet",
        modelclass="WResNet",
        config={"batch_size": 4, "depth": 10, "widen": 1, "n_train": 4,
                "n_val": 4, "seed": 7, "n_epochs": 1},
        verbose=False,
    )
    assert res["remat_saves"] == []
