"""Plain reference of ResNet-50 v1.5 in training mode: forward and
loss in float32 ``jax.numpy``/``lax``, no import from
``theanompi_tpu``.

He et al. 2015 with the v1.5 change (the stride of a down-sampling
block sits on its 3x3, not its first 1x1), as torchvision's
``resnet50`` and the TensorFlow official model build it: 7x7/2 stem of
64, 3x3/2 max pool, stages of (3, 4, 6, 3) bottlenecks of width (64,
128, 256, 512) x 4, a projection shortcut wherever shape changes,
global average pool, 1000-way classifier.  Batch normalisation uses
THIS batch's statistics (biased variance, eps 1e-5): a training step
is what the cells run.  One departure, noted: the max pool pads as
TensorFlow's ``SAME`` does (one row and column at the bottom and
right), where torchvision pads one on every side; the program under
test follows TensorFlow.

Weights are the program's parameter tree, read by position: a list of
``[stem conv, stem bn, -, -, 16 bottlenecks, -, fc]`` with
``{"w"}`` (HWIO), ``{"scale", "offset"}`` and, per bottleneck,
``conv1 bn1 conv2 bn2 conv3 bn3`` and ``proj bn_proj`` where present.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, jnp.asarray(w, jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * p["scale"] + p["offset"]


def _bottleneck(p, x, stride):
    h = jax.nn.relu(_bn(_conv(x, p["conv1"]["w"], 1, 0), p["bn1"]))
    h = jax.nn.relu(_bn(_conv(h, p["conv2"]["w"], stride, 1), p["bn2"]))
    h = _bn(_conv(h, p["conv3"]["w"], 1, 0), p["bn3"])
    if "proj" in p:
        x = _bn(_conv(x, p["proj"]["w"], stride, 0), p["bn_proj"])
    return jax.nn.relu(h + x)


def logits(params, images):
    """images [N, H, W, 3] float32 -> logits [N, classes]."""
    x = jnp.asarray(images, jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, params[0]["w"], 2, 3), params[1]))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    i = 4
    for stage, (blocks, _) in enumerate(STAGES):
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            x = _bottleneck(params[i], x, stride)
            i += 1
    x = jnp.mean(x, (1, 2))
    fc = params[i + 1]
    return x @ jnp.asarray(fc["w"], jnp.float32) + fc["b"]


def loss(params, images, labels):
    """Mean softmax cross-entropy of one replica's batch."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(params, images), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))
