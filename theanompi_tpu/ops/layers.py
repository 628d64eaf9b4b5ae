"""Functional layer library (the reference's ``layers2.py``, TPU-first).

Reference: ``theanompi/models/layers2.py`` — class-based layers holding
Theano shared variables (``Conv`` via cuDNN ``dnn_conv``, ``Pool``,
``LRN``, ``BN``, ``FC``, ``Dropout``, ``Softmax``).  Rebuilt as
init/apply pairs over pytrees:

- ``layer.init(key, in_shape)`` → ``(params, state, out_shape)``
- ``layer.apply(params, state, x, train=..., rng=...)`` → ``(y, state)``

TPU-first choices: NHWC layout (XLA:TPU's preferred conv layout),
fp32 master params with a configurable ``compute_dtype`` (bf16 feeds
the MXU at full rate), ``lax.conv_general_dilated`` /
``lax.reduce_window`` so XLA tiles everything onto the systolic array.
``state`` carries BN running statistics (the reference kept them as
extra shared variables).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import initializers

PyTree = Any


def _split(key, n):
    return jax.random.split(key, n) if n > 1 else [key]


class Layer:
    """Base layer: stateless module descriptor; params live in pytrees."""

    name: str = "layer"

    def init(self, key, in_shape):
        """→ (params, state, out_shape).  Shapes exclude the batch dim."""
        return {}, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}()"


class Activation(Layer):
    """Elementwise nonlinearity (relu/tanh/...); fused into neighbors by XLA."""

    def __init__(self, fn: Callable | str = "relu"):
        self.fn = getattr(jax.nn, fn) if isinstance(fn, str) else fn

    def init(self, key, in_shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.fn(x), state


def _s2d_applicable(x_shape, k: int, b: int, p0: int) -> bool:
    """The transform is exact only when the spatial dims fold evenly
    and the strided output equals H/b (true for the ResNet stem)."""
    _, h, w, _ = x_shape
    out_h = (h + 2 * p0 - k) // b + 1
    return h % b == 0 and w % b == 0 and out_h == h // b and w // b == (
        (w + 2 * p0 - k) // b + 1
    )


def _s2d_conv(x, w, b: int, p0: int):
    """Stride-``b`` conv with pad ``p0`` as a unit-stride conv on the
    space-to-depth input.

    Derivation: y[p] = sum_i x[b*p + i - p0] w[i].  Writing
    i - p0 = b*I + di (di in [0,b)), the padded kernel tap index is
    m = (i - p0) - b*I_min with I_min = floor(-p0/b), i.e. a front
    zero-pad of f = (-p0) % b; blocks (I) become 2-D taps and (di, c)
    become channels, matching the input's (di, dj, c) channel fold.
    """
    kh, kw, c, o = w.shape
    f = (-p0) % b
    k_pad = -(-(f + kh) // b) * b
    t = k_pad // b                       # transformed kernel taps
    wp = jnp.pad(w, ((f, k_pad - f - kh), (f, k_pad - f - kw),
                     (0, 0), (0, 0)))
    w2 = wp.reshape(t, b, t, b, c, o).transpose(0, 2, 1, 3, 4, 5)
    w2 = w2.reshape(t, t, b * b * c, o)
    n, h, wd, _ = x.shape
    x2 = x.reshape(n, h // b, b, wd // b, b, c)
    x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // b, wd // b,
                                                b * b * c)
    left = -(-p0 // b)                   # ceil(p0/b) = -I_min
    right = t - 1 - left
    return lax.conv_general_dilated(
        x2, w2, (1, 1), [(left, right), (left, right)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


class Conv(Layer):
    """2-D convolution, NHWC / HWIO (reference: cuDNN ``dnn_conv``).

    ``pad`` is 'SAME', 'VALID', or an int of symmetric padding.

    ``s2d=True`` computes the EXACT same convolution through a
    space-to-depth transform: the input folds ``stride x stride``
    pixel blocks into channels and the kernel is zero-padded/
    re-indexed to match, turning a strided conv on few channels (the
    classic C=3 network stem, which starves the MXU) into a unit-
    stride conv on ``stride^2 * C`` channels.  Measured on v5e: the
    ResNet-50 7x7/s2 stem fwd+bwd is ~14% of the train step on 2.4%
    of the FLOPs; the transform recovers most of it.  Weights keep
    the ORIGINAL [kh, kw, C, O] shape (checkpoints unaffected); the
    re-indexing is a tiny per-step reshape XLA folds away."""

    def __init__(
        self,
        out_ch: int,
        kernel: int | tuple[int, int],
        stride: int | tuple[int, int] = 1,
        pad: str | int = "SAME",
        *,
        w_init=initializers.he(),
        b_init=initializers.zeros,
        bias: bool = True,
        groups: int = 1,
        s2d: bool = False,
    ):
        self.out_ch = out_ch
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else kernel
        self.stride = (stride, stride) if isinstance(stride, int) else stride
        self.pad = pad
        self.w_init = initializers.get(w_init)
        self.b_init = initializers.get(b_init)
        self.bias = bias
        self.groups = groups
        self.s2d = s2d
        if s2d:
            if (
                not isinstance(pad, int)
                or self.kernel[0] != self.kernel[1]
                or self.stride[0] != self.stride[1]
                or self.stride[0] < 2
                or groups != 1
            ):
                raise ValueError(
                    "s2d needs a square kernel, symmetric stride >= 2, "
                    "integer padding, and groups == 1"
                )

    def init(self, key, in_shape):
        h, w, c = in_shape
        kh, kw = self.kernel
        wkey, bkey = _split(key, 2)
        params = {
            "w": self.w_init(wkey, (kh, kw, c // self.groups, self.out_ch))
        }
        if self.bias:
            params["b"] = self.b_init(bkey, (self.out_ch,))
        pad = self.pad
        if isinstance(pad, int):
            out_h = (h + 2 * pad - kh) // self.stride[0] + 1
            out_w = (w + 2 * pad - kw) // self.stride[1] + 1
        elif pad == "SAME":
            out_h = -(-h // self.stride[0])
            out_w = -(-w // self.stride[1])
        else:  # VALID
            out_h = (h - kh) // self.stride[0] + 1
            out_w = (w - kw) // self.stride[1] + 1
        return params, {}, (out_h, out_w, self.out_ch)

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("blk_conv"):
            if self.s2d and _s2d_applicable(x.shape, self.kernel[0],
                                            self.stride[0], self.pad):
                y = _s2d_conv(x, params["w"].astype(x.dtype),
                              self.stride[0], self.pad)
            else:
                pad = self.pad
                if isinstance(pad, int):
                    pad = [(pad, pad), (pad, pad)]
                y = lax.conv_general_dilated(
                    x,
                    params["w"].astype(x.dtype),
                    window_strides=self.stride,
                    padding=pad,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=self.groups,
                )
            if self.bias:
                y = y + params["b"].astype(y.dtype)
            return y, state


def _pool_explicit_pad(shape, size, stride, pad):
    """Explicit (top, bottom), (left, right) padding matching
    lax.reduce_window's 'SAME'/'VALID' conventions (the library's
    own convention resolver, sliced to the spatial dims)."""
    pads = lax.padtype_to_pads(
        shape, (1, *size, 1), (1, *stride, 1), pad
    )
    return pads[1], pads[2]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def maxpool_tiesplit(x, size, stride, pad):
    """Max pooling whose backward is scatter-free.

    Forward: IDENTICAL to ``lax.reduce_window``-max.  Backward: for
    each window offset, ``eq = (x[shifted] == y)`` marks the
    attaining elements and ``dy/cnt`` routes to them — gradient mass
    is conserved exactly; on TIES it is split equally among the
    attaining elements where XLA's ``select_and_scatter`` gives
    everything to the first in window order (ties are the only
    semantic difference; the equal split is the symmetric
    subgradient).

    **Measured result: NOT the default.**  GoogLeNet's pools profile
    at ~59% of its train step, which motivated this; but three
    formulations all LOST to select_and_scatter on v5e (b128 focused
    bench, select_and_scatter = 4471-4487 img/s across same-code
    captures): scatter-style dilated-
    pad accumulation 1138 (every add materialized an input-sized fp32
    array), dilated gather stencil 2539 (upsampled share/y arrays
    materialized at input size), and this phase-decomposed gather
    3224 — its ~7 window-grid passes (cnt, share, per-phase gather,
    interleave transpose) out-read the scatter's near-bandwidth
    single pass.  select_and_scatter on this hardware generation is
    simply not the serial bottleneck it is reputed to be.  Kept
    opt-in (``TM_POOL_BWD=tiesplit``) as the measured record of the
    experiment and for backends where the scatter IS serial.
    """
    return _maxpool_ts_fwd(x, size, stride, pad)[0]


def _maxpool_ts_fwd(x, size, stride, pad):
    y = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, *size, 1), (1, *stride, 1), pad
    )
    return y, (x, y)


def _maxpool_ts_bwd(size, stride, pad, res, dy):
    # PHASE-DECOMPOSED GATHER: every intermediate lives on the
    # window grid (1/s^2 of the input) and dx is assembled by one
    # reshape-interleave.  Two rejected formulations, both measured
    # on v5e: scatter-style accumulation (k*k dilated pads summed)
    # ran 3x SLOWER than select_and_scatter (every add materialized
    # an input-sized fp32 array), and a dilated gather stencil 4x
    # slower (the upsampled share/y arrays materialized at input
    # size).  Here, for each of the s*s input phases, the windows
    # covering a pixel are a small static set of window-grid shifts
    # (ceil(k/s)^2 of them), so the whole backward is k^2-ish
    # window-grid-sized fused elementwise passes.
    x, y = res
    kh, kw = size
    sh, sw = stride
    n, h, w, c = x.shape
    oh, ow = y.shape[1], y.shape[2]
    (pt, pb), (pl, pr) = _pool_explicit_pad(x.shape, size, stride, pad)
    # pad so every phase has the same grid size (extra sliced off)
    hp = -(-(h + pt + pb) // sh) * sh
    wp = -(-(w + pl + pr) // sw) * sw
    ph, pw = hp // sh, wp // sw
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(
        x, ((0, 0), (pt, hp - h - pt), (pl, wp - w - pl), (0, 0)),
        constant_values=neg,
    )

    def at_offset(oi, oj):
        """x values each window sees at offset (oi, oj): a strided
        slice of the padded input, shaped like y."""
        return lax.slice(
            xp,
            (0, oi, oj, 0),
            (n, oi + (oh - 1) * sh + 1, oj + (ow - 1) * sw + 1, c),
            (1, sh, sw, 1),
        )

    # tie counts <= k*k are exact in bf16, and keeping every array in
    # the compute dtype halves the bandwidth of a purely
    # bandwidth-bound pass (fp32 intermediates measured ~2x slower)
    cdt = x.dtype
    cnt = jnp.zeros(y.shape, cdt)
    for oi in range(kh):
        for oj in range(kw):
            cnt = cnt + (at_offset(oi, oj) == y).astype(cdt)
    # every SAME/VALID window contains >= 1 real element, so the max
    # is always attained; the guard only protects degenerate configs
    share = (dy.astype(cdt) / jnp.maximum(cnt, jnp.asarray(1, cdt)))

    # window-grid arrays padded so any (q2 - d) shift is a slice:
    # low by the max back-shift, high to cover ph > oh phases
    di_max, dj_max = (kh - 1) // sh, (kw - 1) // sw
    hi_h = max(ph - oh, 0) + di_max
    hi_w = max(pw - ow, 0) + dj_max
    share_p = jnp.pad(
        share, ((0, 0), (di_max, hi_h), (dj_max, hi_w), (0, 0))
    )
    y_p = jnp.pad(
        y, ((0, 0), (di_max, hi_h), (dj_max, hi_w), (0, 0)),
        constant_values=neg,
    )

    phases = []
    for pi in range(sh):
        for pj in range(sw):
            # phase pixels sit at xp[(q2*sh + pi, r2*sw + pj)]
            xph = lax.slice(
                xp, (0, pi, pj, 0), (n, hp, wp, c), (1, sh, sw, 1)
            )
            acc = jnp.zeros((n, ph, pw, c), jnp.float32)
            # windows covering this phase: origins (q2 - d)*s with
            # d*s <= k-1-p  (window offset o = p + d*s < k)
            for di in range((kh - 1 - pi) // sh + 1):
                for dj in range((kw - 1 - pj) // sw + 1):
                    sl = (
                        slice(None),
                        slice(di_max - di, di_max - di + ph),
                        slice(dj_max - dj, dj_max - dj + pw),
                        slice(None),
                    )
                    acc = acc + (
                        share_p[sl] * (xph == y_p[sl])
                    ).astype(jnp.float32)
            phases.append(acc.astype(x.dtype))

    # interleave phases back: [sh*sw, n, ph, pw, c] ->
    # [n, ph, sh, pw, sw, c] -> [n, hp, wp, c]
    dxp = (
        jnp.stack(phases)
        .reshape(sh, sw, n, ph, pw, c)
        .transpose(2, 3, 0, 4, 1, 5)
        .reshape(n, hp, wp, c)
    )
    dx = dxp[:, pt:pt + h, pl:pl + w, :]
    return (dx.astype(x.dtype),)


maxpool_tiesplit.defvjp(_maxpool_ts_fwd, _maxpool_ts_bwd)


class Pool(Layer):
    """Max/avg pooling via ``lax.reduce_window`` (reference: ``Pool``).

    ``bwd="tiesplit"`` swaps the max-pool backward for the
    scatter-free tie-split formulation (``maxpool_tiesplit``) —
    measured SLOWER than select_and_scatter on v5e, see its
    docstring; default stays exact.  ``TM_POOL_BWD`` supplies the
    construction-time default only — it is captured when the layer is
    BUILT, so flipping the env after a model is jitted has no effect,
    and two pools in one process can differ via the constructor."""

    def __init__(
        self,
        size: int | tuple[int, int] = 2,
        stride: int | tuple[int, int] | None = None,
        mode: str = "max",
        pad: str = "VALID",
        bwd: str | None = None,
    ):
        self.bwd = (
            bwd if bwd is not None else os.environ.get("TM_POOL_BWD", "")
        )
        # disable-style spellings select the default backward: a
        # leftover ``TM_POOL_BWD=0`` / ``off`` / ``default`` from an
        # A/B run must not fail model construction (ADVICE r5)
        if self.bwd.strip().lower() in (
            "", "0", "off", "default", "none", "false",
        ):
            self.bwd = ""
        if self.bwd not in ("", "tiesplit"):
            raise ValueError(
                f"unknown Pool bwd {self.bwd!r} (expected 'tiesplit' or "
                f"a disable value: ''/'0'/'off'/'default'/'none')"
            )
        self.size = (size, size) if isinstance(size, int) else size
        stride = stride if stride is not None else size
        self.stride = (stride, stride) if isinstance(stride, int) else stride
        assert mode in ("max", "avg")
        self.mode = mode
        self.pad = pad

    def init(self, key, in_shape):
        h, w, c = in_shape
        if self.pad == "SAME":
            out_h = -(-h // self.stride[0])
            out_w = -(-w // self.stride[1])
        else:
            out_h = (h - self.size[0]) // self.stride[0] + 1
            out_w = (w - self.size[1]) // self.stride[1] + 1
        return {}, {}, (out_h, out_w, c)

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("blk_pool"):
            dims = (1, *self.size, 1)
            strides = (1, *self.stride, 1)
            if self.mode == "max":
                if self.bwd == "tiesplit":
                    return (
                        maxpool_tiesplit(x, self.size, self.stride, self.pad),
                        state,
                    )
                y = lax.reduce_window(
                    x, -jnp.inf, lax.max, dims, strides, self.pad
                )
            else:
                summed = lax.reduce_window(
                    x, 0.0, lax.add, dims, strides, self.pad
                )
                y = summed / (self.size[0] * self.size[1])
            return y, state


class LRN(Layer):
    """Local response normalization across channels (AlexNet-era).

    Reference: ``layers2.LRN`` (cuDNN LRN).  y = x / (k + a/n * sum x^2)^b
    over a window of ``n`` adjacent channels.
    """

    def __init__(self, n: int = 5, k: float = 2.0, alpha: float = 1e-4, beta: float = 0.75):
        self.n, self.k, self.alpha, self.beta = n, k, alpha, beta

    def apply(self, params, state, x, *, train=False, rng=None):
        half = self.n // 2
        sq = jnp.square(x.astype(jnp.float32))
        # channel window sum as ONE windowed reduction: the old
        # pad-then-5-slice form gave the fp32 square FIVE consumers,
        # which made XLA materialize a full fp32 copy of the conv
        # output next to the bf16 one (profiled on v5e: LRN fwd+bwd
        # was ~30% of the AlexNet step, dominated by those reads);
        # reduce_window reads the squared input once and lowers to a
        # single fused sweep.
        dims = (1,) * (x.ndim - 1) + (self.n,)
        win = lax.reduce_window(
            sq, 0.0, lax.add, dims, (1,) * x.ndim,
            [(0, 0)] * (x.ndim - 1) + [(half, half)],
        )
        denom = jnp.power(self.k + (self.alpha / self.n) * win, -self.beta)
        return (x.astype(jnp.float32) * denom).astype(x.dtype), state


def _bn_stats(xf, axes):
    """One-pass batch statistics: E[x] and E[x^2] reduce together, so
    XLA emits a SINGLE fused read of the activation instead of the
    sequential mean -> var(x - mean) pair (jnp.var depends on the
    mean, forcing a second full pass).  BN stat reductions are ~1/3
    of a ResNet-50 train step on v5e (profiled).

    Conditioning (ADVICE r3, measured): the E[x^2]-E[x]^2 form loses
    precision when |mean| >> std — ~50% relative variance error at
    mean/std = 600 in fp32 (test_layers documents the envelope; tight
    at mean/std <= ~30).  Every BN in this zoo normalizes post-conv /
    post-mean-subtract activations, where mean/std is O(1).  Shifted
    variants were BENCHED AND REJECTED: probing one element per
    channel as the shift cost 6% of the ResNet-50 step — slicing an
    fp32 view materialized a full fp32 copy of the conv output
    (profiled as (f32,bf16) double-output conv fusions), and even a
    bf16-sliced probe still broke the producer's fusion schedule
    (2659 -> 2490 img/s).  If you add a BN over raw un-normalized
    data, standardize the input (as the data pipeline already does)
    rather than re-deriving the shift."""
    n = math.prod(xf.shape[a] for a in axes)
    s1 = jnp.sum(xf, axes)
    s2 = jnp.sum(xf * xf, axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    return mean, var, n


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, scale, offset, axes, eps):
    """Train-mode BN core with a hand-written one-pass backward.

    Autodiff of the stats+normalize graph leaves XLA with FOUR
    backward reductions over the full activation (d_scale, d_offset,
    d_mean, d_var) scheduled behind a chain of sequential dependencies
    (var depends on mean), which on v5e materialized as ~20% of the
    ResNet-50 step in two-pass reduction reads (measured before
    PR 1).  The custom backward needs only TWO
    channel reductions — sum(dy) and sum(dy*x_hat) — computed
    adjacently so XLA multi-output-fuses them into ONE read of dy,
    then one elementwise pass for dx.  Math is the standard BN
    backward (Ioffe & Szegedy 2015, eqs. in appendix):
      dx = (scale*r) * (dy - mean(dy) - x_hat * mean(dy*x_hat))
    Residuals save x in its ORIGINAL dtype (bf16 on the MXU path) so
    activation memory does not double, and ``y`` is returned in
    x.dtype FROM INSIDE the custom_vjp so the incoming cotangent is
    bf16 too — with the cast outside, the upstream backward fusions
    had to materialize a full fp32 dy (102 MB/layer at the
    56x56x256 stages, profiled as the (f32,bf16) double-output
    fusions, r4); fp32 math happens in-register inside the fused
    passes either way."""
    xf = x.astype(jnp.float32)
    mean, var, _ = _bn_stats(xf, axes)
    r = lax.rsqrt(var + eps)
    y = (xf - mean) * r * scale + offset
    return y.astype(x.dtype), mean, var


def _bn_train_fwd(x, scale, offset, axes, eps):
    xf = x.astype(jnp.float32)
    mean, var, _ = _bn_stats(xf, axes)
    r = lax.rsqrt(var + eps)
    y = (xf - mean) * r * scale + offset
    return (y.astype(x.dtype), mean, var), (x, mean, r, scale)


def _bn_train_bwd(axes, eps, res, cts):
    dy, dmean_ct, dvar_ct = cts
    x, mean, r, scale = res
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)   # in-register upcast, fused
    n = math.prod(xf.shape[a] for a in axes)
    xhat = (xf - mean) * r
    # the two backward reductions, adjacent -> one fused read of dy
    s_dy = jnp.sum(dyf, axes)
    s_dyx = jnp.sum(dyf * xhat, axes)
    dx = (scale * r) * (dyf - s_dy / n - xhat * (s_dyx / n))
    # cotangents of the mean/var outputs (the running-stat EMA path).
    # The train loss never reads the new running stats, so these are
    # structural zeros folded into the same elementwise pass — kept
    # for correctness of any exotic caller that does differentiate
    # through the stats.  (var's clamp-at-0 subgradient is taken as
    # the unclamped branch; the clamp only binds at var==0.)
    dx = dx + dmean_ct / n + dvar_ct * (2.0 / n) * (xf - mean)
    return dx.astype(x.dtype), s_dyx, s_dy


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


class BN(Layer):
    """Batch normalization with running statistics (reference: ``BN``).

    Running mean/var live in ``state`` (the reference used extra shared
    variables updated inside the Theano function).
    """

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5, axis=None):
        self.momentum = momentum
        self.eps = eps
        self.axis = axis  # axes to reduce over; default: all but channel

    def init(self, key, in_shape):
        c = in_shape[-1]
        params = {"scale": jnp.ones((c,)), "offset": jnp.zeros((c,))}
        state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        return params, state, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        # around the custom_vjp call, so that its backward rule's
        # instructions carry the name too
        with jax.named_scope("blk_bn"):
            axes = (self.axis if self.axis is not None
                    else tuple(range(x.ndim - 1)))
            if isinstance(axes, int):  # bare-int axis stays valid (jnp did)
                axes = (axes,)
            # normalize negatives: the probe index in _bn_stats matches
            # positions positionally, and axes are a static jit constant
            axes = tuple(a % x.ndim for a in axes)
            if train:
                # y comes back already in x.dtype (see _bn_train: keeping
                # the cast inside the vjp keeps the cotangent bf16)
                y, mean, var = _bn_train(
                    x, params["scale"], params["offset"], axes, self.eps
                )
                m = self.momentum
                state = {
                    "mean": m * state["mean"] + (1 - m) * mean,
                    "var": m * state["var"] + (1 - m) * var,
                }
                return y, state
            xf = x.astype(jnp.float32)
            mean, var = state["mean"], state["var"]
            y = (xf - mean) * lax.rsqrt(var + self.eps)
            y = y * params["scale"] + params["offset"]
            return y.astype(x.dtype), state


class FC(Layer):
    """Fully connected layer (reference: ``FC``) — one MXU matmul."""

    def __init__(
        self,
        out_dim: int,
        *,
        w_init=initializers.he(),
        b_init=initializers.zeros,
        bias: bool = True,
    ):
        self.out_dim = out_dim
        self.w_init = initializers.get(w_init)
        self.b_init = initializers.get(b_init)
        self.bias = bias

    def init(self, key, in_shape):
        (d,) = in_shape
        wkey, bkey = _split(key, 2)
        params = {"w": self.w_init(wkey, (d, self.out_dim))}
        if self.bias:
            params["b"] = self.b_init(bkey, (self.out_dim,))
        return params, {}, (self.out_dim,)

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("blk_head"):
            y = x @ params["w"].astype(x.dtype)
            if self.bias:
                y = y + params["b"].astype(y.dtype)
            return y, state


class Dropout(Layer):
    """Inverted dropout (reference: ``Dropout``); identity at eval."""

    def __init__(self, rate: float = 0.5):
        self.rate = rate

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout needs rng when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0).astype(x.dtype), state


class Concat(Layer):
    """Parallel branches concatenated on the channel axis (Inception)."""

    def __init__(self, branches: Sequence["Layer"]):
        self.branches = list(branches)

    def init(self, key, in_shape):
        keys = jax.random.split(key, len(self.branches))
        params, state, shapes = [], [], []
        for k, b in zip(keys, self.branches):
            p, s, sh = b.init(k, in_shape)
            params.append(p)
            state.append(s)
            shapes.append(sh)
        h, w = shapes[0][:2]
        assert all(sh[:2] == (h, w) for sh in shapes), (
            f"branch spatial shapes differ: {shapes}"
        )
        out = (h, w, sum(sh[2] for sh in shapes))
        return params, state, out

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, len(self.branches))
            if rng is not None
            else [None] * len(self.branches)
        )
        ys, new_state = [], []
        for b, p, s, r in zip(self.branches, params, state, rngs):
            y, s2 = b.apply(p, s, x, train=train, rng=r)
            ys.append(y)
            new_state.append(s2)
        return jnp.concatenate(ys, axis=-1), new_state


class GlobalAvgPool(Layer):
    """Spatial global average pool: NHWC -> NC."""

    def init(self, key, in_shape):
        return {}, {}, (in_shape[-1],)

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("blk_pool"):
            return jnp.mean(x, axis=(1, 2)), state


class Flatten(Layer):
    def init(self, key, in_shape):
        return {}, {}, (math.prod(in_shape),)

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


class Sequential(Layer):
    """Layer composition with shape inference (reference composed layers
    manually in each model's ``build_model``)."""

    def __init__(self, layers: Sequence[Layer]):
        self.layers = list(layers)

    def init(self, key, in_shape):
        keys = jax.random.split(key, max(len(self.layers), 1))
        params, state = [], []
        shape = in_shape
        for k, layer in zip(keys, self.layers):
            p, s, shape = layer.init(k, shape)
            params.append(p)
            state.append(s)
        return params, state, shape

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, max(len(self.layers), 1))
            if rng is not None
            else [None] * len(self.layers)
        )
        new_state = []
        for layer, p, s, r in zip(self.layers, params, state, rngs):
            x, s = layer.apply(p, s, x, train=train, rng=r)
            new_state.append(s)
        return x, new_state


# ---------------------------------------------------------------------------
# Gated-MLP activation (the dense decoder block of ``models/llama.py``)
# ---------------------------------------------------------------------------

# ``jax.ad_checkpoint.checkpoint_name``s of ``swiglu``'s two operands,
# the dense MLP's gate and up projection outputs (``Llama._layer``): a
# ``jax.checkpoint`` whose policy saves them replays neither product
MLP_RESIDUALS = ("mlp_gate", "mlp_up")


def _silu_mul(g, u):
    return jax.nn.silu(g) * u


@jax.custom_vjp
def swiglu(g, u):
    """``silu(g) * u``, whose backward hands its consumers ARRAYS.

    Forward: exactly ``jax.nn.silu(g) * u``.  Backward: autodiff of
    that same expression, so the same values in the same dtype — but
    returned through ``lax.optimization_barrier``, under the
    ``mlp_act_grad`` scope.  Without the barrier XLA:TPU clones the
    elementwise gradient (an ``exponential``, two ``divide``s, from
    three [tokens, ffn] arrays) into the OPERAND of each product that
    reads it — the ``w_gate``/``w_up`` weight gradients and the two
    input-gradient products — where it is re-evaluated once per
    visit of an operand tile.  With it ``(dg, du)`` are computed once
    a layer, on the v5e as the epilogue of the product that makes
    ``dh``, and the four products read plain arrays: Mistral's
    [4096, 14336] ``w_gate`` gradient with its Adam update 14.98 ->
    8.94 ms, the step 296.2 -> 275.3 (PERF.md §6, PR 27).  The
    forward rule has no barrier on purpose: materialising ``h`` for
    ``w_down``'s gradient was measured and lost 2 ms a step.
    """
    return _silu_mul(g, u)


def _swiglu_fwd(g, u):
    return _silu_mul(g, u), (g, u)


def _swiglu_bwd(res, dh):
    with jax.named_scope("mlp_act_grad"):
        return lax.optimization_barrier(jax.vjp(_silu_mul, *res)[1](dh))


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


# ---------------------------------------------------------------------------
# Losses / metrics (reference: Softmax layer + negative_log_likelihood
# + errors() inside layers2/models)
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits, labels) -> jnp.ndarray:
    """Mean softmax cross-entropy; labels are int class ids."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def accuracy(logits, labels, k: int = 1) -> jnp.ndarray:
    """Top-k accuracy (reference reported top-1/top-5 errors).

    ``k`` is clamped to the class count so top-5 reporting stays valid
    on few-class heads (e.g. IMDB's 2)."""
    k = min(k, logits.shape[-1])
    if k == 1:
        return jnp.mean(jnp.argmax(logits, -1) == labels)
    topk = jax.lax.top_k(logits, k)[1]
    return jnp.mean(jnp.any(topk == labels[:, None], axis=-1))
