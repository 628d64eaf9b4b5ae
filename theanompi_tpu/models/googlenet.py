"""GoogLeNet (Inception v1) on ImageNet, with auxiliary classifiers.

Reference: ``theanompi/models/googlenet.py`` — ``GoogLeNet`` (Szegedy
et al. 2014) with the two auxiliary softmax heads weighted 0.3 in the
training loss; in BASELINE.json's 8-worker BSP config.

The network is a custom ``Layer`` (not a plain ``Sequential``) because
the aux heads branch off inception4a and inception4d; in train mode it
returns ``(main_logits, aux1_logits, aux2_logits)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from theanompi_tpu.models.base import ClassifierModel
from theanompi_tpu.models.data.imagenet import CROP, ImageNetData, N_CLASSES
from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops import (
    FC,
    LRN,
    Activation,
    Concat,
    Conv,
    Dropout,
    Flatten,
    GlobalAvgPool,
    Pool,
    Sequential,
    initializers,
)
from theanompi_tpu.ops.layers import Layer, softmax_cross_entropy


def _conv(ch, k, stride=1, pad="SAME"):
    return Sequential([
        Conv(ch, k, stride=stride, pad=pad, w_init=initializers.he()),
        Activation("relu"),
    ])


def _inception(c1, c3r, c3, c5r, c5, cp):
    """Inception module: 1x1 / 3x3(reduced) / 5x5(reduced) / pool-proj."""
    return Concat([
        _conv(c1, 1),
        Sequential([_conv(c3r, 1), _conv(c3, 3)]),
        Sequential([_conv(c5r, 1), _conv(c5, 5)]),
        Sequential([Pool(3, 1, mode="max", pad="SAME"), _conv(cp, 1)]),
    ])


class _FusedInception(Layer):
    """Inception module with the three 1x1 convs that read the SAME
    input (branch-1, 3x3-reduce, 5x5-reduce) fused into ONE 1x1 conv,
    split after the shared relu — identical math (relu is elementwise,
    he() init depends only on the shared fan-in), better MXU geometry:
    the separate convs fill 128-wide output-lane tiles at e.g.
    64/96/16 channels (the 16-wide 5x5-reduce uses 12.5% of its
    tile), the fused conv at c1+c3r+c5r.  The pool-proj branch reads
    the pooled input and cannot join.  Equivalence to the unfused
    module is asserted by
    ``test_model_zoo.py::test_fused_inception_matches_unfused``."""

    def __init__(self, c1, c3r, c3, c5r, c5, cp):
        self.sizes = (c1, c3r, c5r)
        self.first = Conv(
            c1 + c3r + c5r, 1, w_init=initializers.he()
        )
        self.b3 = _conv(c3, 3)
        self.b5 = _conv(c5, 5)
        self.pool = Pool(3, 1, mode="max", pad="SAME")
        self.pproj = _conv(cp, 1)

    def init(self, key, in_shape):
        k1, k3, k5, kp = jax.random.split(key, 4)
        c1, c3r, c5r = self.sizes
        p1, s1, sh1 = self.first.init(k1, in_shape)
        p3, s3, sh3 = self.b3.init(k3, sh1[:2] + (c3r,))
        p5, s5, sh5 = self.b5.init(k5, sh1[:2] + (c5r,))
        pp, sp_, shp = self.pproj.init(kp, in_shape)
        out = (in_shape[0], in_shape[1], c1 + sh3[2] + sh5[2] + shp[2])
        return (
            {"first": p1, "b3": p3, "b5": p5, "pproj": pp},
            {"first": s1, "b3": s3, "b5": s5, "pproj": sp_},
            out,
        )

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, 3) if rng is not None else [None] * 3
        )
        c1, c3r, c5r = self.sizes
        h, s1 = self.first.apply(
            params["first"], state["first"], x, train=train, rng=rngs[0]
        )
        h = jax.nn.relu(h)
        y3, s3 = self.b3.apply(
            params["b3"], state["b3"], h[..., c1:c1 + c3r],
            train=train, rng=rngs[1],
        )
        y5, s5 = self.b5.apply(
            params["b5"], state["b5"], h[..., c1 + c3r:],
            train=train, rng=rngs[2],
        )
        hp, _ = self.pool.apply({}, {}, x, train=train)
        yp, sp_ = self.pproj.apply(
            params["pproj"], state["pproj"], hp, train=train, rng=None
        )
        new_state = {"first": s1, "b3": s3, "b5": s5, "pproj": sp_}
        return (
            jnp.concatenate([h[..., :c1], y3, y5, yp], axis=-1),
            new_state,
        )


def _aux_head():
    """Auxiliary classifier: avgpool 5/3 -> 1x1 conv 128 -> FC1024 -> FC."""
    return Sequential([
        Pool(5, 3, mode="avg"),
        _conv(128, 1),
        Flatten(),
        FC(1024, w_init=initializers.he()),
        Activation("relu"),
        Dropout(0.7),
        FC(N_CLASSES, w_init=initializers.normal(0.01)),
    ])


class _GoogLeNetNet(Layer):
    """Trunk with two aux branch points; returns a 3-tuple in train mode."""

    def __init__(self, fused: bool = True):
        inc = _FusedInception if fused else _inception
        self.stem = Sequential([
            _conv(64, 7, stride=2),
            Pool(3, 2, pad="SAME"),
            LRN(),
            _conv(64, 1),
            _conv(192, 3),
            LRN(),
            Pool(3, 2, pad="SAME"),
            inc(64, 96, 128, 16, 32, 32),     # 3a
            inc(128, 128, 192, 32, 96, 64),   # 3b
            Pool(3, 2, pad="SAME"),
            inc(192, 96, 208, 16, 48, 64),    # 4a
        ])
        self.mid = Sequential([
            inc(160, 112, 224, 24, 64, 64),   # 4b
            inc(128, 128, 256, 24, 64, 64),   # 4c
            inc(112, 144, 288, 32, 64, 64),   # 4d
        ])
        self.tail = Sequential([
            inc(256, 160, 320, 32, 128, 128),  # 4e
            Pool(3, 2, pad="SAME"),
            inc(256, 160, 320, 32, 128, 128),  # 5a
            inc(384, 192, 384, 48, 128, 128),  # 5b
            GlobalAvgPool(),
            Dropout(0.4),
            FC(N_CLASSES, w_init=initializers.normal(0.01)),
        ])
        self.aux1 = _aux_head()
        self.aux2 = _aux_head()

    def init(self, key, in_shape):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        p_stem, s_stem, sh1 = self.stem.init(k1, in_shape)
        p_aux1, s_aux1, _ = self.aux1.init(k4, sh1)
        p_mid, s_mid, sh2 = self.mid.init(k2, sh1)
        p_aux2, s_aux2, _ = self.aux2.init(k5, sh2)
        p_tail, s_tail, out = self.tail.init(k3, sh2)
        params = {"stem": p_stem, "mid": p_mid, "tail": p_tail,
                  "aux1": p_aux1, "aux2": p_aux2}
        state = {"stem": s_stem, "mid": s_mid, "tail": s_tail,
                 "aux1": s_aux1, "aux2": s_aux2}
        return params, state, out

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, 5) if rng is not None else [None] * 5
        )
        h1, s_stem = self.stem.apply(
            params["stem"], state["stem"], x, train=train, rng=rngs[0]
        )
        h2, s_mid = self.mid.apply(
            params["mid"], state["mid"], h1, train=train, rng=rngs[1]
        )
        main, s_tail = self.tail.apply(
            params["tail"], state["tail"], h2, train=train, rng=rngs[2]
        )
        new_state = {"stem": s_stem, "mid": s_mid, "tail": s_tail,
                     "aux1": state["aux1"], "aux2": state["aux2"]}
        if not train:
            return main, new_state
        a1, s_aux1 = self.aux1.apply(
            params["aux1"], state["aux1"], h1, train=train, rng=rngs[3]
        )
        a2, s_aux2 = self.aux2.apply(
            params["aux2"], state["aux2"], h2, train=train, rng=rngs[4]
        )
        new_state["aux1"] = s_aux1
        new_state["aux2"] = s_aux2
        return (main, a1, a2), new_state


class GoogLeNet(ClassifierModel):
    """``fused_inception`` (default True) selects the fused-1x1
    Inception modules — same math, different param-tree structure, so
    checkpoints taken under one setting must be restored under the
    same setting (``fused_inception: false`` resumes pre-fusion
    checkpoints)."""

    AUX_WEIGHT = 0.3

    def __init__(self, config: dict | None = None):
        config = dict(config or {})
        config.setdefault("batch_size", 32)
        config.setdefault("lr", 0.01)
        config.setdefault("weight_decay", 2e-4)
        config.setdefault("n_epochs", 60)
        config.setdefault("lr_schedule", "step")
        config.setdefault("lr_step_every", 8)
        config.setdefault("lr_step_gamma", 0.96)
        super().__init__(config)

    def build_model(self, n_replicas: int = 1) -> None:
        self.net = _GoogLeNetNet(
            fused=bool(self.config.get("fused_inception", True))
        )
        crop = int(self.config.get("crop", CROP))
        self.input_shape = (crop, crop, 3)
        with setup_phase("data"):
            self.data = ImageNetData(
                batch_size=self.config.get("batch_size", 32),
                n_replicas=n_replicas,
                crop=crop,
                seed=self.seed,
                n_train=self.config.get("n_train"),
                n_val=self.config.get("n_val"),
            )
        self._init_params()

    def load(self, directory, recorder=None):
        """Checkpoint restore with a structure guard: the param tree
        depends on ``fused_inception`` (fused modules hold one merged
        1x1 weight where unfused hold three), so a mismatch surfaces
        here as a missing/mis-shaped leaf — name the knob instead of
        leaving the user to diagnose the raw tree error."""
        try:
            return super().load(directory, recorder)
        except (KeyError, ValueError) as e:
            raise RuntimeError(
                f"checkpoint restore failed: {e}\n"
                f"GoogLeNet's param-tree structure depends on the "
                f"'fused_inception' config knob (currently "
                f"{bool(self.config.get('fused_inception', True))}); a "
                f"checkpoint saved under the other setting must be "
                f"restored with that same setting."
            ) from e

    # aux-classifier loss (train mode returns a 3-tuple)
    def primary_logits(self, out):
        return out[0] if isinstance(out, tuple) else out

    def compute_loss(self, out, y):
        if isinstance(out, tuple):
            main, a1, a2 = out
            return (
                softmax_cross_entropy(main, y)
                + self.AUX_WEIGHT * softmax_cross_entropy(a1, y)
                + self.AUX_WEIGHT * softmax_cross_entropy(a2, y)
            )
        return softmax_cross_entropy(out, y)
