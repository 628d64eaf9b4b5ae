"""Model-zoo golden single-step tests (SURVEY §4c): every ImageNet
model builds, compiles, and completes one BSP train step + one val step
with a finite, plausible loss on the virtual mesh.  Small crop keeps
CPU runtime sane; architecture is unchanged."""

import numpy as np
import pytest

pytestmark = pytest.mark.slow

from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.utils import Recorder

ZOO = [
    ("theanompi_tpu.models.alex_net", "AlexNet", {}),
    ("theanompi_tpu.models.vgg16", "VGG16", {}),
    ("theanompi_tpu.models.googlenet", "GoogLeNet", {}),
    ("theanompi_tpu.models.resnet50", "ResNet50", {}),
]

TINY = {
    "batch_size": 1,
    "crop": 96,
    "n_train": 8,
    "n_val": 4,
    "lr": 0.01,
}


@pytest.mark.parametrize("modelfile,modelclass,extra", ZOO)
def test_zoo_single_step(devices8, modelfile, modelclass, extra):
    import importlib

    mesh = make_mesh(data=2, devices=devices8[:2])
    Model = getattr(importlib.import_module(modelfile), modelclass)
    model = Model({**TINY, **extra})
    model.build_model(n_replicas=2)
    model.compile_iter_fns(mesh=mesh)

    rec = Recorder(verbose=False)
    model.train_iter(0, rec)
    assert rec.n_iter == 1
    loss = rec.train_losses[-1]
    # 1000-way softmax: initial loss ~ ln(1000) = 6.9
    assert np.isfinite(loss) and 2.0 < loss < 20.0

    vloss, verr, verr5 = model.val_iter(0, rec)
    assert np.isfinite(vloss)
    assert 0.0 <= verr <= 1.0 and 0.0 <= verr5 <= verr + 1e-6


def test_stage1_width_pad_is_exact():
    """``stage1_width=128`` with the 64-wide params zero-embedded into
    the padded tree computes EXACTLY the standard network — the
    correctness half of the retired channel-padding lever
    (the A/B before PR 1
    measured −15.7%, so the knob survives as a measured record, and
    this test keeps its equivalence claim honest)."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.resnet50 import ResNet50

    cfg = {**TINY, "batch_size": 2, "compute_dtype": "float32"}
    m64 = ResNet50(cfg)
    m64.build_model()
    m128 = ResNet50({**cfg, "stage1_width": 128})
    m128.build_model()

    def embed(orig, pad):
        if orig.shape == pad.shape:
            return orig
        z = jnp.zeros_like(pad)
        return z.at[tuple(slice(0, d) for d in orig.shape)].set(orig)

    params = jax.tree.map(embed, m64.params, m128.params)
    state = jax.tree.map(embed, m64.net_state, m128.net_state)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 96, 96, 3)),
        jnp.float32,
    )
    y64, _ = m64.net.apply(m64.params, m64.net_state, x, train=False)
    y128, _ = m128.net.apply(params, state, x, train=False)
    np.testing.assert_allclose(
        np.asarray(y64), np.asarray(y128), atol=2e-4, rtol=2e-4
    )


def test_alexnet_learns(devices8):
    """A few steps on synthetic data must reduce AlexNet's loss."""
    from theanompi_tpu.models.alex_net import AlexNet

    mesh = make_mesh(data=4, devices=devices8[:4])
    model = AlexNet({**TINY, "batch_size": 2, "n_train": 32, "lr": 0.02})
    model.build_model(n_replicas=4)
    model.compile_iter_fns(mesh=mesh)
    rec = Recorder(verbose=False)
    for epoch in range(3):  # 12 steps over the 4-batch synthetic set
        for i in range(model.data.n_batch_train):
            model.train_iter(i, rec)
    assert np.mean(rec.train_losses[-4:]) < rec.train_losses[0]


def test_googlenet_aux_heads(devices8):
    """Train mode returns (main, aux1, aux2) and the loss is
    main + 0.3*(aux1 + aux2); eval mode returns main logits only."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.googlenet import GoogLeNet
    from theanompi_tpu.ops.layers import softmax_cross_entropy

    model = GoogLeNet(TINY)
    model.build_model(n_replicas=1)
    x = jnp.zeros((2, 96, 96, 3))
    y = jnp.asarray([3, 7])
    rng = jax.random.PRNGKey(0)

    out_t, _ = model.net.apply(
        model.params, model.net_state, x, train=True, rng=rng
    )
    assert isinstance(out_t, tuple) and len(out_t) == 3
    main, a1, a2 = out_t
    assert main.shape == a1.shape == a2.shape == (2, 1000)

    want = (
        softmax_cross_entropy(main, y)
        + 0.3 * softmax_cross_entropy(a1, y)
        + 0.3 * softmax_cross_entropy(a2, y)
    )
    got = model.compute_loss(out_t, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    out_e, _ = model.net.apply(model.params, model.net_state, x, train=False)
    assert not isinstance(out_e, tuple)
    np.testing.assert_allclose(
        float(model.compute_loss(out_e, y)),
        float(softmax_cross_entropy(out_e, y)),
        rtol=1e-6,
    )


def test_fused_inception_matches_unfused():
    """The fused-1x1 Inception (one wide conv + split) is the SAME
    function as the four-branch module: copy the fused conv's weight
    columns into the three separate convs and compare outputs."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.googlenet import _FusedInception, _inception

    c1, c3r, c3, c5r, c5, cp = 8, 12, 16, 4, 8, 8
    fused = _FusedInception(c1, c3r, c3, c5r, c5, cp)
    plain = _inception(c1, c3r, c3, c5r, c5, cp)
    in_shape = (10, 10, 6)
    key = jax.random.PRNGKey(3)
    pf, sf, out_f = fused.init(key, in_shape)
    pp_, sp_, out_p = plain.init(key, in_shape)
    assert out_f == out_p

    # transplant fused weights into the four-branch structure:
    # Concat params = [branch1, seq(3x3r,3x3), seq(5x5r,5x5), seq(pool,proj)]
    # where each _conv is Sequential([Conv, Activation]) -> [conv, {}]
    wf, bf = pf["first"]["w"], pf["first"]["b"]
    pp_[0][0]["w"] = wf[..., :c1]
    pp_[0][0]["b"] = bf[:c1]
    pp_[1][0][0]["w"] = wf[..., c1:c1 + c3r]
    pp_[1][0][0]["b"] = bf[c1:c1 + c3r]
    pp_[1][1][0] = pf["b3"][0]
    pp_[2][0][0]["w"] = wf[..., c1 + c3r:]
    pp_[2][0][0]["b"] = bf[c1 + c3r:]
    pp_[2][1][0] = pf["b5"][0]
    pp_[3][1][0] = pf["pproj"][0]

    x = jax.random.normal(jax.random.PRNGKey(4), (2, *in_shape))
    yf, _ = fused.apply(pf, sf, x)
    yp, _ = plain.apply(pp_, sp_, x)
    np.testing.assert_allclose(
        np.asarray(yf), np.asarray(yp), atol=1e-5
    )
