"""ResNet-50 on ImageNet — the north-star benchmark model.

Reference: ``theanompi/models/resnet50.py`` (+ Lasagne variant) —
``ResNet50`` (He et al. 2015); BASELINE.json's primary metric is
"ResNet-50 images/sec/chip" with >=90% linear BSP scaling on v5e-64.

v1.5 variant (stride on the 3x3, not the 1x1 — the throughput-standard
used by every modern ResNet-50 benchmark).  TPU-first: NHWC, bf16
compute, BN in fp32, he init, zero-init of the last BN scale in each
block (standard large-batch trick).
"""

from __future__ import annotations

import jax

from theanompi_tpu.models.base import ClassifierModel
from theanompi_tpu.models.data.imagenet import CROP, ImageNetData, N_CLASSES
from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops import (
    BN,
    FC,
    Activation,
    Conv,
    GlobalAvgPool,
    Pool,
    Sequential,
    initializers,
)
from theanompi_tpu.ops.layers import Layer

# (blocks, channels) per stage
_STAGES = [(3, 64), (4, 128), (6, 256), (3, 512)]
_EXPANSION = 4


class Bottleneck(Layer):
    """1x1 -> 3x3(stride) -> 1x1 bottleneck with projection shortcut.

    ``out_ch`` decouples the block's OUTPUT width from the internal
    width (default ``ch * 4``): the ``stage1_width`` experiment pads
    stage-1's internal 64-channel convs to a wider MXU-filling width
    while the residual stream stays 256 wide — with the pad slices
    zero-initialized the function is exactly the 64-wide one
    (asserted by ``test_model_zoo.py::test_stage1_width_pad_is_exact``;
    the on-chip A/B measured −15.7%, so the knob is a measured
    retirement record, not a recommended setting)."""

    def __init__(self, ch: int, stride: int = 1, out_ch: int | None = None):
        self.ch = ch
        self.out_ch = out_ch if out_ch is not None else ch * _EXPANSION
        self.stride = stride
        self.conv1 = Conv(ch, 1, bias=False)
        self.bn1 = BN()
        self.conv2 = Conv(ch, 3, stride=stride, pad=1, bias=False)
        self.bn2 = BN()
        self.conv3 = Conv(self.out_ch, 1, bias=False)
        self.bn3 = BN()
        self.proj: Conv | None = None
        self.bn_proj: BN | None = None

    def init(self, key, in_shape):
        keys = jax.random.split(key, 8)
        p, s = {}, {}
        p["conv1"], _, sh = self.conv1.init(keys[0], in_shape)
        p["bn1"], s["bn1"], _ = self.bn1.init(keys[1], sh)
        p["conv2"], _, sh = self.conv2.init(keys[2], sh)
        p["bn2"], s["bn2"], _ = self.bn2.init(keys[3], sh)
        p["conv3"], _, out = self.conv3.init(keys[4], sh)
        p["bn3"], s["bn3"], _ = self.bn3.init(keys[5], out)
        # zero-init final BN scale: block starts as identity
        p["bn3"] = dict(p["bn3"], scale=p["bn3"]["scale"] * 0.0)
        if self.stride != 1 or in_shape[-1] != out[-1]:
            self.proj = Conv(
                self.out_ch, 1, stride=self.stride, bias=False
            )
            self.bn_proj = BN()
            p["proj"], _, _ = self.proj.init(keys[6], in_shape)
            p["bn_proj"], s["bn_proj"], _ = self.bn_proj.init(keys[7], out)
        return p, s, out

    def apply(self, params, state, x, *, train=False, rng=None):
        s = {}
        h, _ = self.conv1.apply(params["conv1"], {}, x)
        h, s["bn1"] = self.bn1.apply(params["bn1"], state["bn1"], h, train=train)
        h = jax.nn.relu(h)
        h, _ = self.conv2.apply(params["conv2"], {}, h)
        h, s["bn2"] = self.bn2.apply(params["bn2"], state["bn2"], h, train=train)
        h = jax.nn.relu(h)
        h, _ = self.conv3.apply(params["conv3"], {}, h)
        h, s["bn3"] = self.bn3.apply(params["bn3"], state["bn3"], h, train=train)
        if self.proj is not None:
            sc, _ = self.proj.apply(params["proj"], {}, x)
            sc, s["bn_proj"] = self.bn_proj.apply(
                params["bn_proj"], state["bn_proj"], sc, train=train
            )
        else:
            sc = x
        return jax.nn.relu(h + sc), s


class ResNet50(ClassifierModel):
    def __init__(self, config: dict | None = None):
        config = dict(config or {})
        config.setdefault("batch_size", 128)
        config.setdefault("lr", 0.1)
        config.setdefault("weight_decay", 1e-4)
        config.setdefault("momentum", 0.9)
        config.setdefault("n_epochs", 90)
        config.setdefault("lr_schedule", {30: 0.01, 60: 1e-3, 80: 1e-4})
        super().__init__(config)

    def build_model(self, n_replicas: int = 1) -> None:
        # stem rides the space-to-depth transform by default: the
        # 7x7/s2 C=3 conv starves the MXU (~14% of the step on 2.4% of
        # the FLOPs, measured fwd+bwd on v5e); the transform is exact
        # and checkpoint-compatible (ops/layers.py Conv s2d)
        # stage1_width > 64 pads the MXU-underfilled 64-channel convs
        # (stem + stage-1 internals) to a lane-filling width; the
        # residual stream stays 256 so every other stage is untouched.
        # With pad_stage1_params-style zero pads this computes exactly
        # the standard network (test_model_zoo asserts it).
        s1w = int(self.config.get("stage1_width", 64))
        layers: list[Layer] = [
            Conv(s1w, 7, stride=2, pad=3, bias=False,
                 w_init=initializers.he(),
                 s2d=bool(self.config.get("stem_s2d", True))),
            BN(),
            Activation("relu"),
            Pool(3, 2, pad="SAME"),
        ]
        for stage, (blocks, ch) in enumerate(_STAGES):
            for b in range(blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                if stage == 0:
                    layers.append(
                        Bottleneck(s1w, stride, out_ch=ch * _EXPANSION)
                    )
                else:
                    layers.append(Bottleneck(ch, stride))
        layers += [GlobalAvgPool(), FC(N_CLASSES, w_init=initializers.normal(0.01))]
        self.net = Sequential(layers)
        crop = int(self.config.get("crop", CROP))
        self.input_shape = (crop, crop, 3)
        with setup_phase("data"):
            self.data = ImageNetData(
                batch_size=self.config.get("batch_size", 128),
                n_replicas=n_replicas,
                crop=crop,
                seed=self.seed,
                n_train=self.config.get("n_train"),
                n_val=self.config.get("n_val"),
            )
        self._init_params()
