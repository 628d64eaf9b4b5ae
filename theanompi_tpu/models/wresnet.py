"""Wide Residual Network on CIFAR-10.

Reference: ``theanompi/models/wresnet.py`` — ``WResNet`` (Zagoruyko &
Komodakis 2016) on CIFAR-10, the reference's small self-contained
benchmark model (named in BASELINE.json's model list).

WRN-d-k: depth d = 6n+4 with pre-activation residual blocks, widths
(16k, 32k, 64k) over three stages with strides (1, 2, 2).  Default
WRN-16-4 — small enough for convergence smoke tests, structured enough
to exercise BN/residual paths.  TPU-first: NHWC, bf16 compute, all
convs MXU-shaped.
"""

from __future__ import annotations

import jax

from theanompi_tpu.models.base import ClassifierModel
from theanompi_tpu.models.data.cifar10 import Cifar10Data, N_CLASSES, SHAPE
from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops import BN, FC, Activation, Conv, GlobalAvgPool, Sequential, initializers
from theanompi_tpu.ops.layers import Layer


class PreactBlock(Layer):
    """BN-ReLU-Conv pre-activation residual block (WRN style)."""

    def __init__(self, out_ch: int, stride: int = 1):
        self.out_ch = out_ch
        self.stride = stride
        self.bn1 = BN()
        self.conv1 = Conv(out_ch, 3, stride=stride, pad="SAME", bias=False)
        self.bn2 = BN()
        self.conv2 = Conv(out_ch, 3, stride=1, pad="SAME", bias=False)
        self.shortcut: Conv | None = None  # set in init if shape changes

    def init(self, key, in_shape):
        c_in = in_shape[-1]
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        p_bn1, s_bn1, _ = self.bn1.init(k1, in_shape)
        p_c1, _, shape1 = self.conv1.init(k2, in_shape)
        p_bn2, s_bn2, _ = self.bn2.init(k3, shape1)
        p_c2, _, out_shape = self.conv2.init(k4, shape1)
        params = {"bn1": p_bn1, "conv1": p_c1, "bn2": p_bn2, "conv2": p_c2}
        state = {"bn1": s_bn1, "bn2": s_bn2}
        if self.stride != 1 or c_in != self.out_ch:
            self.shortcut = Conv(
                self.out_ch, 1, stride=self.stride, pad="SAME", bias=False
            )
            p_sc, _, _ = self.shortcut.init(k5, in_shape)
            params["shortcut"] = p_sc
        return params, state, out_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        h, s_bn1 = self.bn1.apply(params["bn1"], state["bn1"], x, train=train)
        h = jax.nn.relu(h)
        # preact shortcut: branch from the *activated* input when
        # projecting, from raw x otherwise (standard WRN wiring)
        if self.shortcut is not None:
            sc, _ = self.shortcut.apply(params["shortcut"], {}, h)
        else:
            sc = x
        h, _ = self.conv1.apply(params["conv1"], {}, h)
        h, s_bn2 = self.bn2.apply(params["bn2"], state["bn2"], h, train=train)
        h = jax.nn.relu(h)
        h, _ = self.conv2.apply(params["conv2"], {}, h)
        return h + sc, {"bn1": s_bn1, "bn2": s_bn2}


class WResNet(ClassifierModel):
    """WRN-{depth}-{widen} CIFAR-10 classifier under the model contract."""

    def __init__(self, config: dict | None = None):
        config = dict(config or {})
        config.setdefault("lr", 0.1)
        config.setdefault("weight_decay", 5e-4)
        config.setdefault("n_epochs", 60)
        config.setdefault("lr_schedule", {20: 0.02, 40: 0.004, 50: 0.0008})
        super().__init__(config)
        self.depth = int(config.get("depth", 16))
        self.widen = int(config.get("widen", 4))
        assert (self.depth - 4) % 6 == 0, "WRN depth must be 6n+4"

    def build_model(self, n_replicas: int = 1) -> None:
        n = (self.depth - 4) // 6
        k = self.widen
        layers: list[Layer] = [
            Conv(16, 3, pad="SAME", bias=False, w_init=initializers.he())
        ]
        for stage, (width, stride) in enumerate(
            [(16 * k, 1), (32 * k, 2), (64 * k, 2)]
        ):
            for b in range(n):
                layers.append(PreactBlock(width, stride if b == 0 else 1))
        layers += [BN(), Activation("relu"), GlobalAvgPool(), FC(N_CLASSES)]
        self.net = Sequential(layers)
        self.input_shape = SHAPE
        with setup_phase("data"):
            self.data = Cifar10Data(
                batch_size=self.config.get("batch_size", 128),
                n_replicas=n_replicas,
                seed=self.seed,
                n_train=self.config.get("n_train"),
                n_val=self.config.get("n_val"),
                # convergence drills: flip a fraction of returned labels
                # so the plateau sits off the floor (applies on both the
                # synthetic and real-CIFAR paths)
                label_noise=float(self.config.get("label_noise", 0.0)),
            )
        self._init_params()
