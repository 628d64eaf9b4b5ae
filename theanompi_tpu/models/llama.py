"""Llama-family decoder transformer with 5-D parallelism
(DP x TP x SP x PP x EP).

New-framework scope: the reference is DP-only (SURVEY §2.2); the
BASELINE Llama-3-8B stretch config requires tensor parallelism and
sequence parallelism, which shape this model's design:

- **DP** over the ``data`` mesh axis — batch sharded, grads averaged.
- **TP** over ``model`` — Megatron-style: QKV/gate/up column-parallel,
  o/down row-parallel (+psum); vocab sharded through embedding, LM
  head, and the sharded softmax loss (``parallel/tp.py``) so full
  logits never materialize.
- **SP** over ``seq`` — activations sharded on sequence; attention is
  either ``parallel/ring_attention`` (ppermute KV ring, the default)
  or ``parallel/ulysses`` (head all-to-all), selected by the
  ``sp_mode`` config knob.
- **PP** over ``pipe`` — GPipe microbatching via
  ``parallel/pp.pipeline_apply``: decoder layers stacked on a
  pipe-sharded leading dim (each stage holds ``n_layers/pp``
  consecutive layers), embed replicated, head masked to the last
  stage.  Knobs: ``pp``, ``pp_microbatches``.
- **EP** over ``expert`` — with ``n_experts > 0`` every block's FFN
  becomes a top-k MoE (``parallel/moe.py``); ``ep`` shards the expert
  weights over the ``expert`` mesh axis, whose ranks are ALSO
  data-parallel replicas (the batch shards over ``(expert, data)``
  jointly), with routed tokens exchanged by ``all_to_all``.  Expert
  grads average over ``data`` and scale by ``1/ep`` (the all_to_all
  transpose already accumulated the ep group's token cotangents at
  each owner); everything else averages over ``(expert, data)`` —
  both through the configured wire strategy.  Knobs: ``n_experts,
  moe_top_k, capacity_factor, ep, moe_aux_coef, moe_z_coef,
  moe_renormalize``.  ``capacity_factor: null`` is the DROPLESS path
  (every pick computed by grouped products over expert-sorted rows;
  ``ep == 1``), a number the capacity buffers that drop.

The WHOLE train step — embed, L layers, loss, backward, optimizer —
is ONE vma-checked ``shard_map`` under ``jit``: XLA overlaps the TP
psums and ring hops with compute.  ``check_vma=True`` is load-bearing:
it makes autodiff insert the exactly-right collective transposes
(psum↔pvary), so gradients of sharded AND replicated params come back
correct for any mesh layout with no manual grad reduction (verified by
the layout-invariance tests).  Per-layer ``jax.checkpoint`` (remat)
bounds activation memory for long sequences; it keeps the layer's
input and the flash kernel's two outputs and, for as many of the last
layer calls as the device's memory holds, the dense MLP's gate and up
products, then grouped-query attention's q, k, v and the attention
block's output, then a dropless expert layer's sorted rows with their
gate and up products — two-product experts' one
(``Llama.remat_keep_calls``).  Params are initialized
*under jit with sharded out_shardings*, so the full 8B-scale parameter
set never materializes on one device.

Architecture per Llama-3: RMSNorm, RoPE, grouped-query attention,
SwiGLU MLP, untied LM head (tied under ``tie_word_embeddings``);
``qk_norm`` adds an RMSNorm over the whole
projected width of q and of k, before RoPE.  q, k and v leave their
products in the attention kernels' ``[B, H, T, hd]`` layout and RoPE is
one pass over the whole row (``_gqa_qkv``, ``rope``): no activation is
re-laid or lane-sliced between the block's norm and the kernels.  The
model satisfies the same worker contract as every zoo member, so
``BSP().init(modelfile='theanompi_tpu.models.llama',
modelclass='Llama')`` trains it.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.models.base import TMModel
from theanompi_tpu.models.data.lm_synthetic import MarkovLMData
from theanompi_tpu.obs.memory import device_bytes_limit
from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops.attention import (
    FLASH_RESIDUALS,
    flash_attention,
    flash_tiles_summary,
)
from theanompi_tpu.ops.grouped_matmul import TILE_PLAN_RESIDUAL
from theanompi_tpu.ops.layers import MLP_RESIDUALS, swiglu
from theanompi_tpu.ops import ssd
from theanompi_tpu.ops import optimizers as opt_lib
from theanompi_tpu.parallel import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    ExchangePlan,
    dp_replicas,
    last_stage_value,
    make_mesh,
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)
from theanompi_tpu.parallel.moe import (
    MOE_RESIDUALS,
    held_rows_bound,
    moe_ffn,
    select_bias_step,
    shared_expert,
)
from theanompi_tpu.parallel.ring_attention import ring_attention
from theanompi_tpu.parallel.ulysses import ulysses_attention
from theanompi_tpu.parallel import tp as tp_lib
from theanompi_tpu.utils import Recorder

PyTree = Any

# device memory ``Llama.remat_keep_calls`` leaves free beside its
# estimate of the step's peak: the estimate counts no executable (0.1
# to 1.0 GB in the benchmark's cells), no staged data, no copy of a
# weight in compute dtype and no gap between the allocator's buffers
# (PERF.md, PR 36, has the chip-less compiles it was fixed from)
REMAT_RESERVE_BYTES = 1 << 30

# ``jax.ad_checkpoint.checkpoint_name``s of what a grouped-query
# attention block's backward reads and its replay would rebuild: q, k
# and v as the flash backward takes them, but for the K/V repeat
# (``Llama._gqa_qkv``), and the block's output, the FFN half's input
# (``Llama._layer``).  A ``jax.checkpoint`` whose policy saves them
# replays none of the four products (wq, wk, wv, wo)
ATTN_RESIDUALS = ("attn_q", "attn_k", "attn_v", "attn_block_out")


# -- pure model math (runs on LOCAL shards inside shard_map) ----------------

def rms_norm(x, w, eps=1e-5, sharded_width=None, axes=-1):
    """RMSNorm over the last dimension.  ``sharded_width``: the
    normalised channels are the local shard of ``sharded_width``
    column-sharded over the model axis, and the statistic is over all
    of them (QK-norm: the whole projection, not a head or a shard).
    ``axes``: where those channels lie when not in the last dimension
    alone — ``(1, 3)`` for a projection in the attention kernels'
    ``[B, H_loc, T, hd]`` layout, ``w`` then shaped to broadcast
    (``[H_loc, 1, hd]``): the same float32 expression as on the flat
    ``[B, T, H_loc * hd]`` row, the squares summed in place."""
    xf = x.astype(jnp.float32)
    if sharded_width is None:
        ms = jnp.mean(xf * xf, axis=axes, keepdims=True)
    else:
        ms = lax.psum(
            jnp.sum(xf * xf, axis=axes, keepdims=True), MODEL_AXIS
        ) / sharded_width
    scale = lax.rsqrt(ms + eps)
    return (xf * scale).astype(x.dtype) * w.astype(x.dtype)


def rope(x, pos, theta=10000.0, inv_freq=None, factor=1.0, nope=0):
    """Rotary embedding. x: [B, H, T, D], pos: [T] global positions.
    ``inv_freq`` ``[(D - nope) / 2]``: the pairs' frequencies where
    they are not ``theta ** (-2i / (D - nope))`` (a scaled table:
    ``rope_table``); ``factor`` multiplies cos and sin (YaRN's
    attention factor); the first ``nope`` channels stay as they are
    (cos 1 / sin 0: latent attention's q, ``rope_tail``).

    ONE pass over the whole row, ``x * cos + (x @ swap) * sin`` in
    float32 (``_rotate``): each pair ``(x1, x2)`` becomes ``(x1 cos -
    x2 sin, x2 cos + x1 sin)`` with no stride-2 lane slice, no stack
    and no reshape of the activation; on the chip the sliced form
    took 4.7 ms of a ``[2, 20, 8192, 256]`` row where this pass takes
    1.9 with its product (PERF.md §6, PRs 38 and 45)."""
    r = x.shape[-1] - nope
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]    # [T, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    # a pair shares its angle
    cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
    if nope:
        cos = jnp.pad(cos, ((0, 0), (nope, 0)), constant_values=1.0)
        sin = jnp.pad(sin, ((0, 0), (nope, 0)))
    return _rotate(x, cos, sin)


@jax.custom_vjp
def _rotate(x, cos, sin):
    """``x [B, H, T, D]`` with each channel pair turned by its angle,
    ``cos`` / ``sin`` ``float32[T, D]`` (a pair's two entries equal):
    ``x * cos + swap(x) * sin`` with ``swap`` the signed exchange of
    each pair, ``(x1, x2) -> (-x2, x1)``: a constant ``[D, D]``
    product on the matrix unit (one input times +-1: exact in ``x``'s
    dtype; a pair at cos 1 / sin 0 passes as it is).  Backward: a
    rotation's transpose is the rotation by the negative angle — the
    same pass over the gradient, rounded once, where autodiff's form
    rounds ``dy * sin`` before the swap; the tables get no gradient
    (positions are not learnt)."""
    d = x.shape[-1]
    swap = np.zeros((d, d), np.float32)
    even = np.arange(0, d, 2)
    swap[even + 1, even] = -1.0               # (x @ swap)[2i] = -x[2i+1]
    swap[even, even + 1] = 1.0                # (x @ swap)[2i+1] = x[2i]
    swapped = jnp.matmul(
        x, jnp.asarray(swap, x.dtype), precision=lax.Precision.HIGHEST
    )
    return (
        x.astype(jnp.float32) * cos + swapped.astype(jnp.float32) * sin
    ).astype(x.dtype)


def _rotate_fwd(x, cos, sin):
    return _rotate(x, cos, sin), (cos, sin)


def _rotate_bwd(res, dy):
    cos, sin = res
    return _rotate(dy, cos, -sin), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rope_table(params: dict, head_dim: int):
    """``(inv_freq float32[r / 2], factor)`` of one entry of a
    published ``rope_parameters``, over the ``r = head_dim *
    partial_rotary_factor`` channels of a head that rotate (all of
    them where the entry names no factor; the table is that of a head
    of ``r`` channels, and ``rope`` passes the other ``head_dim - r``
    as they are): ``rope_type`` ``"default"`` is ``f_i = rope_theta **
    (-2i / r)`` and factor 1; ``"yarn"``
    (Peng et al. 2023, as the Hugging Face rotary utilities compute
    it) blends each pair between ``f_i`` and ``f_i / factor`` by how
    many turns it makes over ``original_max_position_embeddings``::

        d(n) = r ln(original / (2 pi n)) / (2 ln rope_theta)
        lo, hi = floor(d(beta_fast)), ceil(d(beta_slow))
        r_i = clip((i - lo) / (hi - lo), 0, 1)
        w_i = (f_i / factor) r_i + f_i (1 - r_i)

    with cos and sin times ``attention_factor`` (``0.1 ln(factor) +
    1`` where none is given).  Static, at every length."""
    kind = params.get("rope_type", "default")
    theta = float(params["rope_theta"])
    head_dim = int(head_dim * float(params.get("partial_rotary_factor", 1.0)))
    half = head_dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) * 2 / head_dim)
    if kind == "default":
        return f.astype(np.float32), 1.0
    if kind != "yarn":
        raise NotImplementedError(
            f"rope_type {kind!r}: the rotary tables here are 'default' "
            f"and 'yarn'"
        )
    scale = float(params["factor"])
    original = float(params["original_max_position_embeddings"])

    def turns_dim(n):
        return head_dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta)
        )

    lo = max(math.floor(turns_dim(float(params.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(turns_dim(float(params.get("beta_slow", 1)))),
             head_dim - 1)
    ramp = np.clip(
        (np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0
    )
    factor = params.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(scale) + 1.0
    return (
        ((f / scale) * ramp + f * (1 - ramp)).astype(np.float32),
        float(factor),
    )


def rope_at(x, pos, theta=10000.0):
    """Rotary embedding at PER-ROW positions (the KV-cache decode
    path, where every slot sits at its own sequence position).
    x: [S, H, D], pos: [S].  Implemented as a vmap of ``rope`` so
    there is ONE copy of the rotation math — a token rotated here
    matches the same token rotated by the training forward at the
    same position bit-for-bit by construction."""
    return jax.vmap(
        lambda xs, p: rope(xs[None, :, None, :], p[None], theta)[
            0, :, 0, :
        ]
    )(x, pos)


def _heads(x, n, d):
    """[B, T, n*d] -> [B, n, T, d]"""
    b, t, _ = x.shape
    return x.reshape(b, t, n, d).transpose(0, 2, 1, 3)


def _unheads(x):
    """[B, n, T, d] -> [B, T, n*d]"""
    b, n, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n * d)


def rope_tail(x, pos, theta, nope):
    """``rope`` on the channels of ``x [B, H, T, D]`` from ``nope``
    on, the first ``nope`` as they are (latent attention's q: a
    no-position part and a rotary part in one row): the same one pass,
    WITHOUT a slice at ``nope`` and a concatenation back."""
    return rope(x, pos, theta, nope=nope)


class Llama(TMModel):
    """Contract-conforming Llama-style causal LM.

    Config knobs: ``dim, n_layers, n_heads, n_kv_heads, ffn_dim,
    vocab, seq_len, batch_size, lr, tp, sp, remat, compute_dtype``.
    ``tp``/``sp`` set the model/seq mesh axis sizes; remaining devices
    form the data axis.  Architecture switches: ``qk_norm`` (bool,
    off: learned RMSNorm of the full q and k projections); with
    ``n_experts > 0`` the FFN is a mixture of experts of width
    ``ffn_dim`` each: ``moe_top_k``, ``moe_renormalize`` (bool, on:
    the picked gates rescaled to sum to one), ``capacity_factor`` (a
    number: capacity buffers that drop; ``null``: dropless),
    ``moe_aux_coef``, ``moe_z_coef``, ``ep``.  ``rope_theta`` (1e4)
    and ``norm_eps`` (1e-5) reach every rotation and every RMSNorm;
    ``sandwich_norm`` (bool, off) adds an RMSNorm on each branch's
    OUTPUT, before the residual sum.  ``ut_steps`` R > 1 makes a
    LOOPED decoder: the one stack of layers runs R times over the same
    parameters, the final norm closes every pass, its output is both
    that pass's exit and the next pass's input, and the training loss
    weighs the R exits' cross-entropies a token by a learned exit
    distribution less ``exit_beta`` times its entropy
    (``_exit_loss``).  ``attention: "mla"`` is latent attention
    (``q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
    v_head_dim``; ``_mla_qkv``): q, k and v each leave a product in
    the attention kernels' layout (``tp.col_parallel_heads``) — v and
    k over column cuts of the weight ``wkv_b``, k's with an identity
    block that carries the one rotary key into every head, q rotated
    in one pass over its whole row (``rope_tail``) — so no activation
    is sliced, broadcast or concatenated; ``first_k_dense`` leading
    layers of an expert model are dense SwiGLUs of ``dense_ffn_dim``;
    ``moe_scoring: "sigmoid"`` with ``moe_route_scale`` and
    ``moe_bias_rate`` is the router whose selection bias is state
    (``net_state``); ``moe_shared_experts`` adds a dense expert every
    token goes through; ``moe_experts_held`` holds experts ``[0,
    held)`` alone (one expert-parallel rank's share by itself);
    ``mtp_depth: 1`` adds a multi-token-prediction module
    (``mtp_coef``; ``_mtp_hidden``, ``_mtp_loss``).  ``head_dim``
    where it is not ``dim // n_heads``; ``layer_types`` (the
    published list: ``"full_attention"`` / ``"sliding_attention"`` a
    layer, the first ``n_layers`` entries), ``sliding_window`` (a
    query of a sliding layer sees itself and the ``sliding_window -
    1`` keys before it) and ``rope_parameters`` (the published dict:
    a rotary table a kind, ``rope_table``) describe the attention
    layer by layer (``attn_kinds``, ``window_of``).  These compose
    with ``tp`` and data parallelism and are refused under ``pp``,
    ``sp`` and ``ut_steps > 1``.  ``layer_types`` may also name a
    MIXER kind a layer, ``"mamba"`` / ``"attention"``
    (``mixer_kinds``): a mamba layer runs a Mamba-2 state-space mixer
    in attention's place (``mamba_n_heads, mamba_d_head,
    mamba_d_state, mamba_n_groups, mamba_d_conv, mamba_expand,
    mamba_chunk_size``; ``_mamba_block``, ``ops/ssd.py``), under data
    parallelism alone.  ``position_embedding_type: "nope"``: q and k
    are not rotated; ``embedding_multiplier``, ``residual_multiplier``,
    ``attention_multiplier`` (the scores' scale in place of ``head_dim
    ** -0.5``) and ``logits_scaling`` (divides the logits) are the
    hybrid decoders' four scalars; ``tie_word_embeddings``: ONE matrix
    is embedding and head (``_head_weight``).  ``n_heads_per_layer``
    (the published ``num_attention_heads_per_layer``, its first
    ``n_layers`` entries): query heads a LAYER over the one
    ``n_kv_heads`` — the leaves ``wq``, ``wo`` and the gate's follow
    it, the projections read it off the leaf; ``attention_gate:
    "per-head"``: a leaf ``w_attn_gate [D, H_l]`` a layer and one
    sigmoid a token and query head, from the block's normed input, on
    the kernels' output before ``wo`` (``_attn_gate``, scope
    ``attn_gate``; its mean a call rides out with the loss,
    ``obs/gate.py``); an entry of ``rope_parameters`` may give a
    ``partial_rotary_factor``: that share of a head's channels rotates
    (``rope_table``, ``rotary_channels``); ``moe_route_scale`` also
    scales the softmax router's renormalised picks.  These three make
    the stack one described layer by layer (``attn_per_layer``): they
    compose with ``tp`` and data parallelism and are refused where
    ``layer_types`` is.  ``validate: false``: the run holds no
    validation set.  ``layer_types`` as a STRING (the published
    ``hybrid_override_pattern``, its first ``n_layers`` characters)
    describes the stack BLOCK BY BLOCK, each ONE sublayer with its one
    norm and its one residual add: ``M`` a Mamba-2 mixer, ``*``
    attention, ``E`` an expert layer (``block_pattern``; two mixers
    may follow each other; the leaves a block holds say which half it
    is, ``_layer``); an expert layer may stand in a stack with mamba
    layers or blocks, the selection bias beside the scans' counters.
    ``hidden_act: "relu2"``: the routed and the shared experts are
    ``relu(x W_up) ** 2 W_down``, two products and no gate leaf;
    ``moe_latent_dim``: the routed experts live in a latent — the
    router reads the block's normed input at ``dim``, the experts its
    projection (leaves ``w_lat_down``, ``w_lat_up``; scope
    ``moe_latent``); ``moe_shared_dim``: the shared expert's width
    where it is no multiple of ``ffn_dim``.  A HEAD SHARE:
    ``n_heads_held`` / ``n_kv_heads_held`` beside ``n_heads`` /
    ``n_kv_heads`` and ``mamba_heads_held`` / ``mamba_groups_held``
    beside ``mamba_n_heads`` / ``mamba_n_groups`` (the published
    counts) make the leaves hold one rank's heads, with their whole
    B/C groups, by itself, as ``moe_experts_held`` does for the
    experts: the partial output product is the rank's part of the
    block's result (``head_share``; refused under ``tp > 1``).
    """

    def __init__(self, config: dict | None = None):
        c = dict(config or {})
        self.config = c
        self.dim = int(c.get("dim", 256))
        self.n_layers = int(c.get("n_layers", 4))
        self.n_heads = int(c.get("n_heads", 8))
        self.n_kv_heads = int(c.get("n_kv_heads", self.n_heads))
        self.ffn_dim = int(c.get("ffn_dim", self.dim * 4))
        self.vocab = int(c.get("vocab", 256))
        self.seq_len = int(c.get("seq_len", 256))
        # a configuration value where the published one is not dim /
        # n_heads (wq [dim, n_heads * head_dim], wo back to dim)
        self.head_dim = int(c.get("head_dim", self.dim // self.n_heads))
        # a HEAD SHARE: of the published ``n_heads`` / ``n_kv_heads``
        # the leaves hold ``n_heads_held`` / ``n_kv_heads_held``, what
        # one of the ranks that share a layer's heads holds, by itself
        # (as ``moe_experts_held`` is beside ``n_experts``): the
        # partial ``wo`` product is that rank's part of the block's
        # result, and nothing stands in for the other ranks'
        self.head_share = {}
        if c.get("n_heads_held") is not None:
            held = int(c["n_heads_held"])
            kv_held = int(c.get("n_kv_heads_held", 0))
            ranks = self.n_heads // max(held, 1)
            if (held < 1 or held * ranks != self.n_heads
                    or kv_held != max(1, self.n_kv_heads // ranks)
                    or max(ranks, self.n_kv_heads)
                    % min(ranks, self.n_kv_heads)):
                raise ValueError(
                    f"n_heads_held {held} / n_kv_heads_held {kv_held}: a "
                    f"head share is one of R ranks' whole heads, R = "
                    f"n_heads / n_heads_held dividing n_heads "
                    f"{self.n_heads}, with max(1, n_kv_heads / R) of the "
                    f"{self.n_kv_heads} key/value heads (a key/value head "
                    f"may lie on several ranks, never in parts)"
                )
            self.head_share.update(
                n_heads=(held, self.n_heads),
                n_kv_heads=(kv_held, self.n_kv_heads),
            )
            self.n_heads, self.n_kv_heads = held, kv_held
        # "gqa": wq/wk/wv/wo at one head dim.  "mla": latent attention
        # (``_mla_qkv``): q and k/v each through a low-rank
        # down-projection with its own RMSNorm; a head's q and k are a
        # no-position part and a rotary part, and the rotary part of k
        # is ONE vector a token, shared by all heads
        self.attention = str(c.get("attention", "gqa"))
        assert self.attention in ("gqa", "mla"), self.attention
        if self.attention == "mla":
            self.q_lora_rank = int(c["q_lora_rank"])
            self.kv_lora_rank = int(c["kv_lora_rank"])
            self.qk_nope_head_dim = int(c["qk_nope_head_dim"])
            self.qk_rope_head_dim = int(c["qk_rope_head_dim"])
            self.v_head_dim = int(c["v_head_dim"])
            # what the attention kernels see: q and k rows
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
            if self.head_dim != self.v_head_dim:
                raise NotImplementedError(
                    f"latent attention with q/k rows of {self.head_dim} "
                    f"and value rows of {self.v_head_dim}: the flash "
                    f"kernels take one head dim for q, k and v"
                )
        self.tp = int(c.get("tp", 1))
        self.sp = int(c.get("sp", 1))
        self.pp = int(c.get("pp", 1))
        # MoE knobs: n_experts=0 keeps the dense SwiGLU FFN
        self.n_experts = int(c.get("n_experts", 0))
        self.moe_top_k = int(c.get("moe_top_k", 2))
        # a number sizes capacity buffers that drop; None is dropless
        cf = c.get("capacity_factor", 1.25)
        self.capacity_factor = None if cf is None else float(cf)
        self.moe_renormalize = bool(c.get("moe_renormalize", True))
        self.qk_norm = bool(c.get("qk_norm", False))
        self.rope_theta = float(c.get("rope_theta", 10000.0))
        self.norm_eps = float(c.get("norm_eps", 1e-5))
        self.sandwich_norm = bool(c.get("sandwich_norm", False))
        # passes over the one stack of layers; > 1 is a looped decoder
        self.ut_steps = int(c.get("ut_steps", 1))
        self.exit_beta = float(c.get("exit_beta", 0.0))
        self.ep = int(c.get("ep", 1))
        self.moe_aux_coef = float(c.get("moe_aux_coef", 0.01))
        self.moe_z_coef = float(c.get("moe_z_coef", 0.0))
        # the router's form (``parallel/moe.router_topk``): "softmax",
        # or "sigmoid" scores picked under a selection bias that is
        # STATE (``net_state["moe_bias"]``, one row an expert layer
        # call): no gradient, moved ``moe_bias_rate`` a step toward
        # balance after the optimizer, outside it and the exchange
        self.moe_scoring = str(c.get("moe_scoring", "softmax"))
        assert self.moe_scoring in ("softmax", "sigmoid"), self.moe_scoring
        self.moe_route_scale = float(c.get("moe_route_scale", 1.0))
        self.moe_bias_rate = float(c.get("moe_bias_rate", 0.0))
        self.moe_select_bias = bool(
            self.n_experts and self.moe_scoring == "sigmoid"
            and self.moe_bias_rate
        )
        # experts [0, held) of the n_experts routed over are here: one
        # expert-parallel rank's share of every expert layer, by itself
        held = c.get("moe_experts_held")
        self.moe_experts_held = None if held is None else int(held)
        # shared experts: a dense SwiGLU of that many expert widths
        # that every token goes through, beside the routed ones
        self.moe_shared_experts = int(c.get("moe_shared_experts", 0))
        # the FFNs' form: "silu" is the SwiGLU (gate, up and down
        # products); "relu2" (the published ``mlp_hidden_act``) is
        # ``relu(x W_up) ** 2 W_down``, TWO products and no gate leaf,
        # for the routed experts and the shared one
        self.hidden_act = str(c.get("hidden_act", "silu"))
        if self.hidden_act not in ("silu", "relu2"):
            raise ValueError(
                f"hidden_act {self.hidden_act!r}: 'silu' (SwiGLU) or "
                f"'relu2' (relu(.)^2, two products)"
            )
        # the shared expert's width where it is not a whole number of
        # expert widths' worth (``moe_shared_experts * ffn_dim``)
        self.moe_shared_dim = int(c.get(
            "moe_shared_dim", self.moe_shared_experts * self.ffn_dim))
        # experts in a LATENT: the router reads the block's normed
        # input at ``dim``, the routed experts its projection to
        # ``moe_latent_dim`` (their leaves are that wide) and their
        # sum goes back up to ``dim``; the shared expert stays at
        # ``dim`` (``moe.moe_ffn``'s ``latent``)
        latent = c.get("moe_latent_dim")
        self.moe_latent_dim = None if latent is None else int(latent)
        # the first layers of an expert model that are dense SwiGLUs
        # of ``dense_ffn_dim`` (an expert's width is ``ffn_dim``)
        self.first_k_dense = int(c.get("first_k_dense", 0))
        self.dense_ffn_dim = int(c.get("dense_ffn_dim", self.ffn_dim))
        # the FFN kind of every layer, "moe" or "dense"
        self.layer_kinds = tuple(
            "moe" if self.n_experts and i >= self.first_k_dense else "dense"
            for i in range(self.n_layers)
        )
        # the attention kind of every layer, beside ``layer_kinds``
        # (the FFN's): the published ``layer_types`` — its first
        # ``n_layers`` entries, for a stack cut in depth —
        # ``"full_attention"`` or ``"sliding_attention"`` (a query sees
        # itself and the ``sliding_window - 1`` keys before it); each
        # kind rotates by its own entry of ``rope_parameters`` where
        # that is given (``rope_table``), else by ``rope_theta``
        types = c.get("layer_types")
        # a STRING (the published ``hybrid_override_pattern``, its
        # first ``n_layers`` characters) describes the stack BLOCK BY
        # BLOCK, each ONE sublayer with its one norm and its one
        # residual add — ``M`` a Mamba-2 mixer, ``*`` attention, ``E``
        # an expert layer — so a "layer" of the tuples below is a
        # block, and the half it does not have is ``"none"``
        self.block_pattern = (
            types[:self.n_layers] if isinstance(types, str) else None)
        # ``"attention"`` (the hybrid decoders' name for a full layer)
        # is ``"full_attention"``; ``"mamba"`` is no attention at all
        # but a state-space MIXER (``mixer_kinds``, ``ops/ssd.py``)
        self.attn_kinds = (
            ("full_attention",) * self.n_layers if types is None
            else tuple(
                "full_attention" if t == "attention" else str(t)
                for t in types[:self.n_layers]
            )
        )
        if self.block_pattern is not None:
            unknown = set(self.block_pattern) - set("M*E-")
            if (unknown or self.first_k_dense
                    or len(self.block_pattern) != self.n_layers
                    or ("E" in self.block_pattern) != bool(self.n_experts)):
                raise ValueError(
                    f"layer_types as a string names each of the "
                    f"{self.n_layers} BLOCKS 'M' (a mamba mixer), '*' "
                    f"(attention), 'E' (an expert layer: n_experts > 0, "
                    f"and only then) or '-' (a dense FFN), and stands in "
                    f"first_k_dense's place too; got "
                    f"{self.block_pattern!r} (unknown {sorted(unknown)}), "
                    f"n_experts {self.n_experts}, first_k_dense "
                    f"{self.first_k_dense}"
                )
            if "-" in self.block_pattern or c.get("mtp_depth"):
                raise NotImplementedError(
                    "a block pattern (layer_types as a string: blocks of "
                    "ONE sublayer) does not yet run a dense FFN block "
                    "('-': the blocks run are 'M', '*' and 'E') nor "
                    "compose with a multi-token-prediction module, whose "
                    "block is a mixer AND an FFN (pattern "
                    f"{self.block_pattern!r}, mtp_depth "
                    f"{c.get('mtp_depth')})"
                )
            self.attn_kinds = tuple(
                {"M": "mamba", "*": "full_attention", "E": "none"}[b]
                for b in self.block_pattern
            )
            self.layer_kinds = tuple(
                "moe" if b == "E" else "none" for b in self.block_pattern)
        # the mixer of every layer, "attention" or "mamba" ("none": a
        # block that is an FFN alone)
        self.mixer_kinds = tuple(
            {"mamba": "mamba", "none": "none"}.get(k, "attention")
            for k in self.attn_kinds
        )
        self.has_mamba = "mamba" in self.mixer_kinds
        window = c.get("sliding_window")
        self.sliding_window = None if window is None else int(window)
        self.rope_parameters = c.get("rope_parameters")
        # query heads a LAYER (the published
        # ``num_attention_heads_per_layer``, its first ``n_layers``
        # entries, as ``layer_types``) over the one ``n_kv_heads``;
        # None: ``n_heads`` in every layer
        per_layer = c.get("n_heads_per_layer")
        self.heads_per_layer = (
            (self.n_heads,) * self.n_layers if per_layer is None
            else tuple(int(h) for h in per_layer[:self.n_layers])
        )
        # a sigmoid gate a query head on the kernels' output, before
        # ``wo`` (``_attn_gate``; the published ``gating: "per-head"``)
        gate = c.get("attention_gate")
        if gate not in (None, False, "per-head", "per_head"):
            raise ValueError(
                f"attention_gate {gate!r}: the gate here is 'per-head' "
                f"(one sigmoid a query head) or none"
            )
        self.attention_gate = bool(gate)
        # the stack's attention is described layer by layer (as the
        # published decoders with two kinds of layer do), not by
        # ``rope_theta`` and the one causal call alone
        self.attn_per_layer = not (
            types is None and window is None and self.rope_parameters is None
            and per_layer is None and not self.attention_gate
        )
        unknown = set(self.attn_kinds) - {
            "full_attention", "sliding_attention", "mamba",
            # (an expert BLOCK's place in a pattern, never a list's)
            *(("none",) if self.block_pattern is not None else ()),
        }
        if unknown or len(self.attn_kinds) != self.n_layers:
            raise ValueError(
                f"layer_types names each of the {self.n_layers} layers "
                f"'full_attention' (or 'attention'), 'sliding_attention' "
                f"or 'mamba'; got {len(self.attn_kinds)} entries, unknown "
                f"{sorted(unknown)}"
            )
        if "sliding_attention" in self.attn_kinds and not self.sliding_window:
            raise ValueError(
                "layer_types has 'sliding_attention' layers: give "
                "sliding_window, the keys a query sees (itself included)"
            )
        if self.has_mamba:
            # the Mamba-2 mixer's sizes (``ops/ssd.py``'s names), from
            # the published keys
            self._mamba = dict(
                n_heads=int(c["mamba_n_heads"]),
                head_dim=int(c["mamba_d_head"]),
                d_state=int(c["mamba_d_state"]),
                n_groups=int(c.get("mamba_n_groups", 1)),
            )
            self.mamba_d_conv = int(c.get("mamba_d_conv", 4))
            self.mamba_chunk_size = int(c.get("mamba_chunk_size", 256))
            inner = ssd.mamba_sizes(**self._mamba)[0]
            expand = int(c.get("mamba_expand", 2))
            assert inner == expand * self.dim, (
                f"mamba_n_heads x mamba_d_head = {inner} is not "
                f"mamba_expand {expand} x dim {self.dim}: the PUBLISHED "
                f"counts tie the scan's channels to the width; a head "
                f"share (mamba_heads_held, mamba_groups_held) may hold "
                f"fewer heads and groups of the same sizes, nothing else"
            )
            if c.get("mamba_heads_held") is not None:
                # the mixer's head share: one rank's state heads and
                # its WHOLE B/C groups, so the gated norm's groups
                # (``inner / n_groups`` channels) lie on a rank each
                # and a share needs no statistic of another's
                held = int(c["mamba_heads_held"])
                groups_held = int(c.get("mamba_groups_held", 0))
                heads, groups = (
                    self._mamba["n_heads"], self._mamba["n_groups"])
                if (held < 1 or heads % held or groups_held < 1
                        or groups_held * heads != groups * held):
                    raise ValueError(
                        f"mamba_heads_held {held} / mamba_groups_held "
                        f"{groups_held}: a mixer's head share is one of R "
                        f"ranks' heads with their whole B/C groups, R "
                        f"dividing mamba_n_heads {heads} and "
                        f"mamba_n_groups {groups} alike"
                    )
                self.head_share.update(
                    mamba_n_heads=(held, heads),
                    mamba_n_groups=(groups_held, groups),
                )
                self._mamba.update(n_heads=held, n_groups=groups_held)
        # the hybrid decoders' scalars: ``embedding_multiplier`` on
        # the looked-up rows, ``residual_multiplier`` on each branch
        # before its residual add, ``attention_multiplier`` in place of
        # ``head_dim ** -0.5`` on the scores (None: that default),
        # ``logits_scaling`` DIVIDES the logits;
        # ``tie_word_embeddings``: ONE matrix is embedding and head;
        # ``position_embedding_type`` "nope": q and k are not rotated
        self.embedding_multiplier = float(c.get("embedding_multiplier", 1.0))
        self.residual_multiplier = float(c.get("residual_multiplier", 1.0))
        scale = c.get("attention_multiplier")
        self.attention_multiplier = None if scale is None else float(scale)
        self.logits_scaling = float(c.get("logits_scaling", 1.0))
        self.tie_word_embeddings = bool(c.get("tie_word_embeddings", False))
        self.position_embedding_type = str(
            c.get("position_embedding_type", "rope"))
        assert self.position_embedding_type in ("rope", "nope"), (
            self.position_embedding_type
        )
        # (inv_freq, factor) a kind: (None, 1.0) is ``rope_theta``'s
        self._rope_tables = {
            kind: (
                (None, 1.0) if self.rope_parameters is None
                else rope_table(self.rope_parameters[kind], self.head_dim)
            )
            for kind in set(self.attn_kinds) - {"mamba", "none"}
        }
        # the run summary's ``"rotary_channels"``: how many of a head's
        # channels a kind rotates (its entry's ``partial_rotary_factor``)
        self.rotary_channels = {
            kind: self.head_dim if inv_freq is None else 2 * len(inv_freq)
            for kind, (inv_freq, _) in sorted(self._rope_tables.items())
        }
        # multi-token prediction: ``mtp_depth`` (0 or 1) more blocks
        # of the last layer's kind after the stack, which predict the
        # token after next from the stack's output and the next
        # token's embedding through the model's own head; their loss
        # weighs ``mtp_coef``
        self.mtp_depth = int(c.get("mtp_depth", 0))
        self.mtp_coef = float(c.get("mtp_coef", 0.3))
        assert self.mtp_depth in (0, 1), self.mtp_depth
        # token-sharding axes for MoE aux-moment globalization; set
        # for real in compile_iter_fns — initialized here so tracing
        # _forward before compile agrees with loss_and_err's fallback
        self._dp_axes = (DATA_AXIS,)
        batch = int(c.get("batch_size", 8))
        # default microbatch count: 2 per stage halves the GPipe bubble
        # vs M=S, when the local batch allows it
        default_m = 2 * self.pp if batch % (2 * self.pp) == 0 else self.pp
        self.pp_microbatches = int(
            c.get("pp_microbatches", default_m) if self.pp > 1 else 1
        )
        self.sp_mode = str(c.get("sp_mode", "ring"))
        # last-stage-only head, cost-shared (VERDICT r2 item 6): when
        # the per-device token count divides by pp, the head/unembed
        # runs on 1/pp of the tokens per stage instead of being
        # replicated-and-masked; ragged cases keep the masked path
        self._pp_scatter = bool(c.get("pp_head_scatter", True)) and (
            self.pp > 1
            and (batch * (self.seq_len // self.sp)) % self.pp == 0
        )
        self.remat = bool(c.get("remat", True))
        # what the per-layer remat keeps from the forward pass
        # (``_forward``; the run summary's "remat_saves"): the flash
        # kernel's two outputs and, for a dropless expert layer, the
        # tile plan of its grouped products (a few KB)
        saves = FLASH_RESIDUALS
        if "moe" in self.layer_kinds and self.capacity_factor is None:
            saves += (TILE_PLAN_RESIDUAL,)
        self.remat_saves = saves if self.remat else ()
        # how many of the LAST dense layer calls also keep
        # ``MLP_RESIDUALS``, how many of the last grouped-query
        # attention calls ``ATTN_RESIDUALS`` and how many of the last
        # dropless expert calls ``MOE_RESIDUALS`` ("remat_kept_calls",
        # "remat_kept_attn_calls", "remat_kept_moe_calls" of the
        # summary): ``compile_iter_fns`` sets them from the shapes and
        # the device's memory
        self.remat_kept_calls = 0
        self.remat_kept_attn_calls = 0
        self.remat_kept_moe_calls = 0
        self.compute_dtype = jnp.dtype(c.get("compute_dtype", "bfloat16"))
        self.seed = int(c.get("seed", 42))
        self.n_epochs = int(c.get("n_epochs", 5))
        self.epoch = 0
        self.current_lr = float(c.get("lr", 3e-3))
        self.opt_name = str(c.get("optimizer", "adam"))
        self.optimizer = opt_lib.get(
            self.opt_name, weight_decay=float(c.get("weight_decay", 0.0))
        )

        assert (self.attention == "mla" or "head_dim" in c
                or self.dim % self.n_heads == 0)
        assert self.n_heads % self.n_kv_heads == 0, (
            "n_heads must be a multiple of n_kv_heads (GQA groups)"
        )
        assert self.n_heads % self.tp == 0, "n_heads must divide by tp"
        assert self.n_kv_heads % self.tp == 0, "n_kv_heads must divide by tp"
        # (a multiple of n_kv_heads divides by tp as n_kv_heads does)
        assert len(self.heads_per_layer) == self.n_layers and all(
            h % self.n_kv_heads == 0 for h in self.heads_per_layer
        ), (
            f"n_heads_per_layer names each of the {self.n_layers} layers "
            f"a multiple of n_kv_heads {self.n_kv_heads}; got "
            f"{self.heads_per_layer}"
        )
        assert self.vocab % self.tp == 0, "vocab must divide by tp"
        assert self.ffn_dim % self.tp == 0, "ffn_dim must divide by tp"
        assert self.dense_ffn_dim % self.tp == 0, (
            "dense_ffn_dim must divide by tp"
        )
        assert 0 <= self.first_k_dense <= self.n_layers
        assert self.seq_len % self.sp == 0, "seq_len must divide by sp"
        assert self.n_layers % self.pp == 0, "n_layers must divide by pp"
        if self.n_experts:
            assert self.n_experts % self.ep == 0, (
                f"n_experts {self.n_experts} must divide by ep {self.ep}"
            )
            assert 0 < self.moe_top_k <= self.n_experts
            if self.capacity_factor is None and self.ep > 1:
                raise NotImplementedError(
                    "dropless MoE (capacity_factor: null) does not yet "
                    "compose with expert parallelism (ep > 1): the "
                    "sorted rows of an expert would cross chips in a "
                    "ragged all-to-all; give a capacity_factor or ep=1"
                )
            if self.moe_experts_held is not None and (
                self.capacity_factor is not None or self.ep > 1
                or not 0 < self.moe_experts_held <= self.n_experts
            ):
                raise NotImplementedError(
                    "moe_experts_held (one expert-parallel rank's share "
                    "by itself) is the dropless path's: give "
                    "capacity_factor null, ep 1 and 1..n_experts held"
                )
        else:
            assert self.ep == 1, "ep > 1 requires n_experts > 0"
            assert not (self.first_k_dense or self.moe_shared_experts
                        or self.moe_experts_held or self.moe_latent_dim), (
                "first_k_dense, moe_shared_experts, moe_experts_held and "
                "moe_latent_dim need n_experts > 0"
            )
        assert self.ut_steps >= 1, self.ut_steps
        mixed = [
            name for name, on in (
                ("attention: mla", self.attention == "mla"),
                ("first_k_dense", len(set(self.layer_kinds)) > 1),
                ("mtp_depth", self.mtp_depth),
                ("a selection bias", self.moe_select_bias),
                ("layer_types (an attention kind, a window, a rotary "
                 "table or a head count per layer, an attention gate)",
                 self.attn_per_layer),
                ("a multiplier (embedding_multiplier, residual_multiplier, "
                 "attention_multiplier, logits_scaling)",
                 (self.embedding_multiplier, self.residual_multiplier,
                  self.logits_scaling) != (1.0, 1.0, 1.0)
                 or self.attention_multiplier is not None),
                ("tie_word_embeddings", self.tie_word_embeddings),
                ("position_embedding_type: nope",
                 self.position_embedding_type == "nope"),
            ) if on
        ]
        if self.has_mamba and (
            self.tp > 1 or self.attention == "mla"
            or "sliding_attention" in self.attn_kinds or self.mtp_depth
        ):
            raise NotImplementedError(
                "a mamba layer or block (layer_types) does not yet "
                "compose with tensor parallelism, latent "
                "attention, sliding-window layers or a "
                f"multi-token-prediction module (tp {self.tp}, attention "
                f"{self.attention}, kinds {sorted(set(self.attn_kinds))}, "
                f"mtp_depth {self.mtp_depth}): the scan's heads and its "
                "state are not sharded over the model axis (a head share, "
                "mamba_heads_held, runs by itself), and the hybrid stack "
                "has been run with grouped-query full attention alone; "
                "use tp=1, attention gqa, no sliding_attention layer and "
                "mtp_depth 0"
            )
        if self.hidden_act == "relu2" and "dense" in self.layer_kinds:
            raise NotImplementedError(
                "hidden_act: relu2 (two products, no gate leaf) is the "
                "routed and the shared experts': a dense FFN (n_experts "
                f"{self.n_experts}, first_k_dense {self.first_k_dense}) "
                "keeps its SwiGLU, whose remat names a gate and an up "
                "product"
            )
        if self.head_share and (
            self.tp > 1 or self.attention == "mla" or per_layer is not None
        ):
            raise NotImplementedError(
                "a head share (n_heads_held, mamba_heads_held: one "
                "rank's heads by itself) does not yet compose with tensor "
                "parallelism, latent attention or n_heads_per_layer (tp "
                f"{self.tp}, attention {self.attention}): the share "
                "stands in the model axis' place, and the held counts "
                "are one pair for the stack; use tp=1, attention gqa and "
                "one n_heads"
            )
        if self.position_embedding_type == "nope" and self.attention == "mla":
            raise NotImplementedError(
                "position_embedding_type: nope is grouped-query "
                "attention's: latent attention (attention: mla) rotates a "
                "part of every head by construction"
            )
        if self.logits_scaling != 1.0 and self.mtp_depth:
            raise NotImplementedError(
                "logits_scaling does not yet compose with a multi-token-"
                "prediction module (mtp_depth): the module's exit goes "
                "through the shared head unscaled"
            )
        if self.attn_per_layer and self.attention == "mla":
            raise NotImplementedError(
                "attention: mla does not yet compose with layer_types, "
                "sliding_window, rope_parameters, n_heads_per_layer or "
                "attention_gate: latent attention's projections "
                "(_mla_qkv) rotate by the one rope_theta, hold n_heads "
                "in every layer and call the kernels without a window "
                "or a gate"
            )
        if mixed and (self.pp > 1 or self.sp > 1 or self.ut_steps > 1):
            # pp stacks the layers' leaves on one leading dimension
            # (one kind of layer) and runs the head apart from them;
            # sp shards the positions the MTP labels shift over and
            # has only been run with the one attention path (the ring
            # and the all-to-all know no window and one rotary table)
            raise NotImplementedError(
                f"{', '.join(mixed)} does not yet compose with "
                f"pipeline parallelism, sequence parallelism or a "
                f"looped stack (pp {self.pp}, sp {self.sp}, ut_steps "
                f"{self.ut_steps}): use pp=1, sp=1, ut_steps=1"
            )
        if self.ut_steps > 1 and self.pp > 1:
            raise NotImplementedError(
                "a looped decoder (ut_steps > 1) does not yet compose "
                "with pipeline parallelism (pp > 1): every pass would "
                "send the last stage's output back to the first, R "
                "trips of the pipe a microbatch; use pp=1"
            )
        if self.pp > 1:
            assert batch % self.pp_microbatches == 0, (
                f"local batch {batch} must divide into "
                f"{self.pp_microbatches} microbatches"
            )
        assert self.sp_mode in ("ring", "ulysses"), self.sp_mode
        if self.sp_mode == "ulysses":
            h_loc = self.n_heads // self.tp
            hkv_loc = self.n_kv_heads // self.tp
            assert h_loc % self.sp == 0 and hkv_loc % self.sp == 0, (
                f"ulysses needs per-TP-shard heads divisible by sp: "
                f"H/tp={h_loc}, Hkv/tp={hkv_loc}, sp={self.sp}"
            )

        self.params: PyTree = None
        self.opt_state: PyTree = None
        self.net_state: PyTree = None
        self.mesh: Mesh | None = None
        self._train_step = None
        self._val_step = None
        self._train_scan = None
        self._scan_k = 0

    # -- parameter layout -------------------------------------------------

    def param_specs(self) -> PyTree:
        """PartitionSpec per leaf — the model's sharding contract.

        With ``pp > 1`` the per-layer trees are STACKED along a
        leading ``n_layers`` dimension sharded over the ``pipe`` axis,
        so each pipeline stage's device holds exactly its own
        ``n_layers/pp`` consecutive layers (contiguous mesh reshape =
        consecutive stages)."""
        if self.pp > 1:
            layers = {
                k: P(PIPE_AXIS, *s)
                for k, s in self._layer_specs(self.layer_kinds[0]).items()
            }
        else:
            layers = [
                self._layer_specs(kind, mixer)
                for kind, mixer in zip(self.layer_kinds, self.mixer_kinds)
            ]
        specs = {
            "embed": P(MODEL_AXIS, None),        # vocab-sharded rows
            "layers": layers,
            "final_norm": P(None),
            "lm_head": P(None, MODEL_AXIS),      # vocab-sharded cols
        }
        if self.tie_word_embeddings:
            # the head IS the embedding, transposed (``_head_weight``)
            del specs["lm_head"]
        if self.ut_steps > 1:
            # the exit gate acts on the full width: replicated
            specs.update({"exit_gate_w": P(None, None), "exit_gate_b": P(None)})
        if self.mtp_depth:
            # the two norms and the projection act on the full width
            specs["mtp"] = {
                "enorm": P(None), "hnorm": P(None),
                "eh_proj": P(None, None),
                "block": self._layer_specs(self.layer_kinds[-1]),
                "head_norm": P(None),
            }
        return specs

    def _layer_specs(self, kind: str, mixer: str = "attention") -> dict:
        """PartitionSpec per leaf of one layer of FFN ``kind`` and
        ``mixer`` kind; ``"none"`` (a block of a ``layer_types``
        pattern): the half the block does not have, with its norm."""
        layer = {
            norm: P(None) for norm, half in (
                ("attn_norm", mixer), ("mlp_norm", kind))
            if half != "none"
        }
        if mixer == "mamba":
            # refused under tp > 1: every leaf whole on every device
            layer.update({
                "ssm_in": P(None, None), "ssm_conv_w": P(None, None),
                "ssm_conv_b": P(None), "ssm_dt_bias": P(None),
                "ssm_a_log": P(None), "ssm_d": P(None),
                "ssm_norm": P(None), "ssm_out": P(None, None),
            })
        elif mixer == "attention" and self.attention == "mla":
            # heads over the model axis (the up-projections' columns,
            # the output projection's rows); the two down-projections
            # and their norms act on the full width: replicated
            layer.update({
                "wq_a": P(None, None), "q_a_norm": P(None),
                "wq_b": P(None, MODEL_AXIS),
                "wkv_a": P(None, None), "kv_a_norm": P(None),
                "wkv_b": P(None, MODEL_AXIS),
                "wo": P(MODEL_AXIS, None),
            })
        elif mixer == "attention":
            layer.update({
                "wq": P(None, MODEL_AXIS),
                "wk": P(None, MODEL_AXIS),
                "wv": P(None, MODEL_AXIS),
                "wo": P(MODEL_AXIS, None),
            })
        if self.attention_gate and mixer == "attention":
            # a column a query head: sharded as wq's heads
            layer["w_attn_gate"] = P(None, MODEL_AXIS)
        if self.qk_norm and mixer == "attention":
            # over the whole projected width: sharded as its columns
            layer.update({"q_norm": P(MODEL_AXIS), "k_norm": P(MODEL_AXIS)})
        if self.sandwich_norm:
            # over the full width of each branch's (psum'd) output
            layer.update({
                name: P(None) for name, half in (
                    ("attn_out_norm", mixer), ("mlp_out_norm", kind))
                if half != "none"
            })
        if kind == "moe":
            # experts sharded over the expert axis, FFN dim over model
            layer.update({
                "router": P(None, None),
                "we_gate": P(EXPERT_AXIS, None, MODEL_AXIS),
                "we_up": P(EXPERT_AXIS, None, MODEL_AXIS),
                "we_down": P(EXPERT_AXIS, MODEL_AXIS, None),
            })
            if self.moe_latent_dim:
                # the two latent projections act on the full width
                layer.update({
                    "w_lat_down": P(None, None), "w_lat_up": P(None, None),
                })
            if self.moe_shared_experts:
                layer.update({
                    "ws_gate": P(None, MODEL_AXIS),
                    "ws_up": P(None, MODEL_AXIS),
                    "ws_down": P(MODEL_AXIS, None),
                })
            if self.hidden_act == "relu2":  # two products: no gate leaf
                layer.pop("we_gate")
                layer.pop("ws_gate", None)
        elif kind == "dense":
            layer.update({
                "w_gate": P(None, MODEL_AXIS),
                "w_up": P(None, MODEL_AXIS),
                "w_down": P(MODEL_AXIS, None),
            })
        return layer

    def _init_full_params(self, key) -> PyTree:
        """Full (unsharded) init; device_put with NamedShardings slices
        it onto the mesh."""
        d, f, v = self.dim, self.ffn_dim, self.vocab
        hd = self.head_dim

        def dense(key, shape, scale=None):
            scale = scale or (2.0 / (shape[0] + shape[-1])) ** 0.5
            return scale * jax.random.normal(key, shape, jnp.float32)

        keys = iter(jax.random.split(key, 4 + 9 * self.n_layers))
        # leaves the first decoders did not have (latent attention, a
        # shared expert, the MTP module) draw from a stream of their
        # own: the older leaves keep their values under any knob
        more = iter(jax.random.split(
            jax.random.fold_in(key, 1), 8 * (self.n_layers + 1) + 1
        ))

        def attention(h, keys):
            if self.attention == "mla":
                hq = self.n_heads * self.head_dim
                hkv = self.n_heads * (
                    self.qk_nope_head_dim + self.v_head_dim
                )
                return {
                    "wq_a": dense(next(more), (d, self.q_lora_rank)),
                    "q_a_norm": jnp.ones((self.q_lora_rank,)),
                    "wq_b": dense(next(more), (self.q_lora_rank, hq)),
                    "wkv_a": dense(next(more), (
                        d, self.kv_lora_rank + self.qk_rope_head_dim
                    )),
                    "kv_a_norm": jnp.ones((self.kv_lora_rank,)),
                    "wkv_b": dense(next(more), (self.kv_lora_rank, hkv)),
                    "wo": dense(
                        next(more), (self.n_heads * self.v_head_dim, d)
                    ),
                }
            return {
                "wq": dense(next(keys), (d, h * hd)),
                "wk": dense(next(keys), (d, self.n_kv_heads * hd)),
                "wv": dense(next(keys), (d, self.n_kv_heads * hd)),
                "wo": dense(next(keys), (h * hd, d)),
                **({"w_attn_gate": dense(next(more), (d, h))}
                   if self.attention_gate else {}),
            }

        def mamba(i):
            return ssd.mamba_init(
                # a stream of its own, a key a layer (fold_in 1 and 2
                # are ``more``'s and the MTP block's)
                jax.random.fold_in(jax.random.fold_in(key, 3), i), d,
                d_conv=self.mamba_d_conv, dense=dense, **self._mamba,
            )

        def one_layer(kind, keys, mixer="attention", i=0):
            h = self.heads_per_layer[i]     # (the MTP block: the last's)
            # (a block of a ``layer_types`` pattern holds ONE half and
            # its norm)
            lp = {
                **({} if mixer == "none" else {"attn_norm": jnp.ones((d,))}),
                **({} if mixer == "none" else
                   mamba(i) if mixer == "mamba" else attention(h, keys)),
                **({} if kind == "none" else {"mlp_norm": jnp.ones((d,))}),
            }
            if self.attention == "mla" or mixer == "mamba":
                for _ in range(4):
                    next(keys)  # keep key budget aligned (9 per layer)
            if self.qk_norm and mixer == "attention":
                lp["q_norm"] = jnp.ones((h * hd,))
                lp["k_norm"] = jnp.ones((self.n_kv_heads * hd,))
            if self.sandwich_norm:
                if mixer != "none":
                    lp["attn_out_norm"] = jnp.ones((d,))
                if kind != "none":
                    lp["mlp_out_norm"] = jnp.ones((d,))
            if kind == "moe":
                e = self.n_experts
                # the leaves hold the experts that are here
                eh = e if self.moe_experts_held is None else (
                    self.moe_experts_held
                )
                # what the routed experts read: the width, or the latent
                de = self.moe_latent_dim or d
                # per-expert fan-in/out scales (the generic shape-based
                # scale would key on E instead of D/F for 3-D tensors)
                lp.update({
                    "router": dense(next(keys), (d, e)),
                    "we_gate": dense(
                        next(keys), (eh, de, f), (2.0 / (de + f)) ** 0.5
                    ),
                    "we_up": dense(
                        next(keys), (eh, de, f), (2.0 / (de + f)) ** 0.5
                    ),
                    "we_down": dense(
                        next(keys), (eh, f, de), (2.0 / (f + de)) ** 0.5
                    ),
                })
                next(keys)  # keep key budget aligned (9 per layer)
                if self.moe_latent_dim:
                    # (a stream of its own, as the mixers' is)
                    k_dn, k_up = jax.random.split(
                        jax.random.fold_in(jax.random.fold_in(key, 4), i))
                    lp.update({
                        "w_lat_down": dense(k_dn, (d, de)),
                        "w_lat_up": dense(k_up, (de, d)),
                    })
                if self.moe_shared_experts:
                    fs = self.moe_shared_dim
                    lp.update({
                        "ws_gate": dense(next(more), (d, fs)),
                        "ws_up": dense(next(more), (d, fs)),
                        "ws_down": dense(next(more), (fs, d)),
                    })
                if self.hidden_act == "relu2":      # no gate leaf
                    lp.pop("we_gate")
                    lp.pop("ws_gate", None)
            elif kind == "dense":
                fd = self.dense_ffn_dim
                lp.update({
                    "w_gate": dense(next(keys), (d, fd)),
                    "w_up": dense(next(keys), (d, fd)),
                    "w_down": dense(next(keys), (fd, d)),
                })
                for _ in range(2):
                    next(keys)  # keep key budget aligned (9 per layer)
            return lp

        layers = [
            one_layer(kind, keys, mixer, i) for i, (kind, mixer) in
            enumerate(zip(self.layer_kinds, self.mixer_kinds))
        ]
        if self.pp > 1:
            # stack the SAME per-layer draws (pp is a layout choice,
            # not a math choice: init must match the pp=1 model)
            layers = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
        params = {
            "embed": 0.02 * jax.random.normal(next(keys), (v, d), jnp.float32),
            "layers": layers,
            "final_norm": jnp.ones((d,)),
            "lm_head": dense(next(keys), (d, v)),
        }
        if self.tie_word_embeddings:
            del params["lm_head"]
        if self.ut_steps > 1:
            # a gate near zero: every pass but the last keeps about
            # half of the mass that reaches it
            params["exit_gate_w"] = 0.02 * jax.random.normal(
                next(keys), (d, 1), jnp.float32
            )
            params["exit_gate_b"] = jnp.zeros((1,))
        if self.mtp_depth:
            params["mtp"] = {
                "enorm": jnp.ones((d,)), "hnorm": jnp.ones((d,)),
                "eh_proj": dense(next(more), (2 * d, d)),
                "block": one_layer(
                    self.layer_kinds[-1],
                    iter(jax.random.split(jax.random.fold_in(key, 2), 9)),
                    i=-1,
                ),
                "head_norm": jnp.ones((d,)),
            }
        return params

    def _init_net_state(self) -> PyTree:
        """What a step reads and moves that is neither a parameter nor
        optimizer state: the routers' selection bias, a row ``[E]`` an
        expert layer call (the stack's, then the MTP block's), zeros
        at the start.  ``None`` for a model without one."""
        if not self.moe_select_bias:
            return None
        return {"moe_bias": jnp.zeros(
            (self.moe_calls, self.n_experts), jnp.float32
        )}

    @property
    def moe_calls(self) -> int:
        """Expert layer calls a step runs: each gives one row of
        moments, of routing counters and of selection bias."""
        kinds = self.layer_kinds * self.ut_steps
        if self.mtp_depth:
            kinds += self.layer_kinds[-1:]
        return kinds.count("moe")

    # -- forward (local shards) -------------------------------------------

    def flash_tiles(self) -> dict:
        """The run summary's ``"flash_tiles"``: for each flash kernel
        the tiles ``ops.attention._flash_tiles`` chose for this
        model's attention shape and the share of visited score tiles
        that take the masked body; ``{}`` where attention takes the
        dense path (off the TPU, or a length no block tiles).  A model
        described layer by layer (``attn_per_layer``) gives one such
        summary an attention kind, under the kind's name."""
        # ring attention hands the kernels one shard's length a hop
        t = self.seq_len // (self.sp if self.sp_mode == "ring" else 1)

        def tiles(kind):
            return flash_tiles_summary(
                t, t, self.head_dim, self.compute_dtype, causal=True,
                window=self.window_of(kind),
            )

        if not self.attn_per_layer:
            return tiles("full_attention")
        # a model described layer by layer: a summary a kind
        return {kind: tiles(kind)
                for kind in sorted(set(self.attn_kinds) - {"mamba", "none"})}

    def window_of(self, kind: str) -> int | None:
        """The keys a query of a layer of ``kind`` sees, itself
        included; None: all before it."""
        return self.sliding_window if kind == "sliding_attention" else None

    @property
    def attention_kinds(self) -> dict:
        """The run summary's ``"attention_kinds"``: layers of each
        (a mamba layer is no attention: ``mixer_kinds_count``)."""
        return {k: self.attn_kinds.count(k)
                for k in sorted(set(self.attn_kinds) - {"mamba", "none"})}

    @property
    def mixer_kinds_count(self) -> dict:
        """The run summary's ``"mixer_kinds"``: layers of each."""
        return {k: self.mixer_kinds.count(k)
                for k in sorted(set(self.mixer_kinds) - {"none"})}

    @property
    def block_kinds_count(self) -> dict | None:
        """The run summary's ``"block_kinds"``: blocks of each
        character of a ``layer_types`` pattern; None for a stack of whole
        layers."""
        if self.block_pattern is None:
            return None
        return {b: self.block_pattern.count(b)
                for b in sorted(set(self.block_pattern))}

    @property
    def ssd_chunk(self) -> int | None:
        """The run summary's ``"ssd_chunk"``: positions a chunk of
        the state-space scan; None without a mamba layer."""
        return self.mamba_chunk_size if self.has_mamba else None

    def ssd_kernel(self) -> dict:
        """The run summary's ``"ssd_kernel"``: the tiles the scan's
        Pallas kernels take for this model's shapes
        (``ssd.scan_kernel_tiles``) — ``{}`` where XLA's form runs
        (off the TPU, a shape no tile divides) or without a mamba
        layer.  Static, from shapes and the device."""
        if not self.has_mamba:
            return {}
        m = self._mamba
        tiles = ssd.scan_kernel_tiles(
            self.seq_len, self.mamba_chunk_size, m["head_dim"], m["d_state"],
            m["n_heads"] // m["n_groups"], m["n_heads"],
        )
        return {} if tiles is None else tiles._asdict()

    # -- what the per-layer remat keeps -----------------------------------

    @property
    def remat_calls(self) -> int:
        """Layer calls a step runs under ``jax.checkpoint``: a looped
        decoder's are (pass, layer)."""
        return self.n_layers * self.ut_steps if self.remat else 0

    @property
    def _local_tokens(self) -> int:
        """Tokens of a step one device holds: ``B_loc x T_loc``."""
        return int(self.config.get("batch_size", 8)) * (
            self.seq_len // self.sp
        )

    @property
    def remat_kept_bytes_per_call(self) -> int:
        """Bytes of ``MLP_RESIDUALS`` one DENSE layer call keeps on a
        device: two ``[B_loc, T_loc, dense_ffn_dim / tp]`` in compute
        dtype; 0 for a model of expert layers alone, which name
        neither."""
        if "dense" not in self.layer_kinds:
            return 0
        return (
            2 * self._local_tokens * (self.dense_ffn_dim // self.tp)
            * self.compute_dtype.itemsize
        )

    @property
    def _gqa_layers(self) -> tuple[bool, ...]:
        """For every layer, whether its mixer is grouped-query
        attention: neither a mamba layer nor latent attention names
        ``ATTN_RESIDUALS``."""
        return tuple(
            self.attention != "mla" and mixer == "attention"
            for mixer in self.mixer_kinds
        )

    def remat_kept_attn_bytes_of(self, layer: int) -> int:
        """Bytes of ``ATTN_RESIDUALS`` the grouped-query attention
        call of ``layer`` keeps on a device, in compute dtype: q
        ``[B_loc, H_loc, T_loc, hd]`` at THAT layer's heads
        (``heads_per_layer``), k and v ``[B_loc, Hkv_loc, T_loc, hd]``
        (before the repeat) and the block's output ``[B_loc, T_loc,
        D]``; 0 for a layer without such a call (latent attention, a
        mamba layer), which names none of them."""
        if not self._gqa_layers[layer]:
            return 0
        heads = (self.heads_per_layer[layer] + 2 * self.n_kv_heads) // self.tp
        return (
            self._local_tokens * (heads * self.head_dim + self.dim)
            * self.compute_dtype.itemsize
        )

    @property
    def _gqa_call_bytes(self) -> list[int]:
        """``remat_kept_attn_bytes_of`` every grouped-query attention
        call of a step, in call order (a looped stack's passes one
        after the other)."""
        return [
            self.remat_kept_attn_bytes_of(i)
            for i, gqa in enumerate(self._gqa_layers) if gqa
        ] * self.ut_steps

    @property
    def remat_kept_attn_bytes_per_call(self) -> int:
        """The most a grouped-query attention call keeps (every
        call's, where the layers have one head count); 0 for a model
        without such a call."""
        return max(self._gqa_call_bytes, default=0)

    @property
    def remat_kept_moe_bytes_per_call(self) -> int:
        """Bytes of ``MOE_RESIDUALS`` one DROPLESS expert layer call
        keeps on a device: the ``R`` sorted rows the layer lays out at
        a time (``moe.held_rows_bound``: every pick, or under a held
        range twice its balanced share) times a row ``[D]`` and its
        gate and up products ``[ffn_dim / tp]`` in compute dtype, and
        the sort's two ``int32[k N]``; 0 for a model without such a
        layer (none, or the capacity path, which names nothing)."""
        if "moe" not in self.layer_kinds or self.capacity_factor is not None:
            return 0
        picks = self.moe_top_k * self._local_tokens
        rows = held_rows_bound(picks, self.moe_experts_held, self.n_experts)
        # a row as the experts read it (``moe_latent_dim`` wide under a
        # latent) and its products before the down projection: gate
        # and up, or under ``hidden_act: relu2`` the ONE
        products = 1 if self.hidden_act == "relu2" else 2
        return (
            rows * ((self.moe_latent_dim or self.dim)
                    + products * self.ffn_dim // self.tp)
            * self.compute_dtype.itemsize + 2 * picks * 4
        )

    def _kept_bytes(self, n_mlp: int, n_attn: int, n_moe: int) -> int:
        """Bytes the last ``n_mlp`` dense, ``n_attn`` grouped-query
        attention and ``n_moe`` dropless expert calls keep on a
        device."""
        calls = self._gqa_call_bytes
        return (
            n_mlp * self.remat_kept_bytes_per_call
            + sum(calls[len(calls) - n_attn:])
            + n_moe * self.remat_kept_moe_bytes_per_call
        )

    @property
    def remat_kept_bytes(self) -> int:
        """Bytes of ``MLP_RESIDUALS``, ``ATTN_RESIDUALS`` and
        ``MOE_RESIDUALS`` the kept calls hold on a device."""
        return self._kept_bytes(
            self.remat_kept_calls, self.remat_kept_attn_calls,
            self.remat_kept_moe_calls,
        )

    def _local_params(self, axis_sizes) -> tuple[int, int]:
        """(elements, bytes) of the parameters ONE device holds under
        ``param_specs`` on a mesh of ``axis_sizes``.  Shape-only eval,
        no compute."""
        shapes = jax.eval_shape(
            self._init_full_params, jax.random.PRNGKey(0)
        )
        specs = jax.tree.leaves(
            self.param_specs(), is_leaf=lambda s: isinstance(s, P)
        )
        elems = nbytes = 0
        for leaf, spec in zip(jax.tree.leaves(shapes), specs):
            dims = list(leaf.shape)
            for i, ax in enumerate(tuple(spec)):
                if ax is None:
                    continue
                for a in ax if isinstance(ax, (tuple, list)) else (ax,):
                    dims[i] //= axis_sizes[a]
            elems += math.prod(dims)
            nbytes += math.prod(dims) * leaf.dtype.itemsize
        return elems, nbytes

    def step_peak_terms(self) -> dict[str, int]:
        """Bytes one device holds at the train step's peak when no
        call keeps ``MLP_RESIDUALS``, ``ATTN_RESIDUALS`` or
        ``MOE_RESIDUALS``, from shapes alone, term by term:
        ``params_grads_opt`` — every local parameter's master,
        gradient and optimizer state; ``call_inputs`` and
        ``flash_outputs`` — what each layer call keeps for its replay
        (its input; the flash kernel's output and logsumexp);
        ``head`` — the head's live set: one set of local
        logits and their gradient in compute dtype
        (``tp.dense_unembed_xent``, or one loop body of
        ``tp.exits_unembed_xent`` with the exits' stack and its
        gradient beside it; a streamed head holds a chunk of each).
        Their sum is ``step_peak_estimate``; the run's memory account
        gives them out (``keep_account``)."""
        isz = self.compute_dtype.itemsize
        batch = int(self.config.get("batch_size", 8))
        t_loc = self.seq_len // self.sp
        n_tok = batch * t_loc
        _, param_bytes = self._local_params(
            {MODEL_AXIS: self.tp, PIPE_AXIS: self.pp, EXPERT_AXIS: self.ep}
        )
        opt_copies = {"adam": 2, "sgd": 0}.get(self.opt_name, 1)
        # every call keeps its input; an attention call the flash
        # kernel's two outputs beside it (at its layer's heads), a
        # mamba call nothing more
        kept_input = n_tok * self.dim * isz
        stack_heads = sum(
            h for h, mixer in zip(self.heads_per_layer, self.mixer_kinds)
            if mixer == "attention"
        ) * self.ut_steps // self.pp
        kept_heads = (
            stack_heads + self.mtp_depth * self.heads_per_layer[-1]
        ) // self.tp
        kept_flash = (
            n_tok * kept_heads * self.head_dim * isz
            + batch * kept_heads * t_loc * 4
        )
        head = 2 * n_tok * (
            self.vocab // self.tp // self._xent_chunks()
        ) * isz
        exits = self.ut_steps if self.ut_steps > 1 else 1 + self.mtp_depth
        if exits > 1:
            head += 2 * exits * n_tok * self.dim * isz
        calls = self.n_layers * self.ut_steps // self.pp + self.mtp_depth
        return {
            "params_grads_opt": param_bytes * (2 + opt_copies),
            "call_inputs": calls * kept_input,
            "flash_outputs": kept_flash,
            "head": head,
        }

    def step_peak_estimate(self) -> int:
        """The sum of ``step_peak_terms``.  The benchmark's three
        transformer cells read within 0.45 GiB of it on the chip
        (``tests/test_flash_remat.py``)."""
        return sum(self.step_peak_terms().values())

    def remat_keep_calls(
        self, bytes_limit: int | None
    ) -> tuple[int, int, int]:
        """``(n_mlp, n_attn, n_moe)``: how many of the last DENSE layer
        calls keep ``MLP_RESIDUALS``, how many of the last
        grouped-query attention calls keep ``ATTN_RESIDUALS`` and how
        many of the stack's last dropless expert calls keep
        ``MOE_RESIDUALS``.  The room is what lies between the step's
        estimated peak and the device's ``bytes_limit`` less
        ``REMAT_RESERVE_BYTES``; the MLP's copies fill it first, as
        many calls as fit, attention's take what they leave and the
        expert layer's what both leave (so a device keeps every call
        it kept before the next set was counted).  ``(0, 0, 0)``
        without a limit (the CPU), without remat and on the pipeline
        path, whose stage function keeps the plain policy; a model
        without a dense layer keeps no MLP call, one without
        grouped-query attention (latent attention, mamba layers alone)
        no attention call, one without a dropless expert layer no
        expert call."""
        if not (self.remat and bytes_limit) or self.pp > 1:
            return 0, 0, 0
        free = max(
            bytes_limit - REMAT_RESERVE_BYTES - self.step_peak_estimate(), 0
        )
        mlp_bytes = self.remat_kept_bytes_per_call
        n_mlp = min(
            free // mlp_bytes,
            self.ut_steps * self.layer_kinds.count("dense"),
        ) if mlp_bytes else 0
        free -= n_mlp * mlp_bytes
        # the LAST calls first (the first the backward reaches), each
        # at its own layer's heads, while they fit
        n_attn = 0
        for attn_bytes in reversed(self._gqa_call_bytes):
            if attn_bytes > free:
                break
            free -= attn_bytes
            n_attn += 1
        moe_bytes = self.remat_kept_moe_bytes_per_call
        n_moe = min(
            free // moe_bytes, self.ut_steps * self.layer_kinds.count("moe")
        ) if moe_bytes else 0
        return n_mlp, n_attn, n_moe

    def keep_account(self, bytes_limit: int | None) -> dict | None:
        """What ``remat_keep_calls`` decides from and leaves at
        ``bytes_limit``, for the run's memory account
        (``obs/memory.py``): the limit, ``reserve_bytes``, the
        estimate's ``terms``, ``kept_bytes`` (``remat_kept_bytes`` at
        the rule's counts), ``unkept_bytes`` (the residual sets of
        every eligible call it did not keep, each at its own bytes),
        ``free_bytes`` (what the third count left) and the
        ``eligible`` and ``kept`` calls a kind.  None where the rule
        does not run: no limit, no remat, the pipeline path."""
        if not (self.remat and bytes_limit) or self.pp > 1:
            return None
        terms = self.step_peak_terms()
        kinds = ("mlp", "attn", "moe")
        kept = self.remat_keep_calls(bytes_limit)
        eligible = (
            self.ut_steps * self.layer_kinds.count("dense")
            if self.remat_kept_bytes_per_call else 0,
            len(self._gqa_call_bytes),
            self.ut_steps * self.layer_kinds.count("moe")
            if self.remat_kept_moe_bytes_per_call else 0,
        )
        kept_bytes = self._kept_bytes(*kept)
        room = max(
            bytes_limit - REMAT_RESERVE_BYTES - sum(terms.values()), 0
        )
        return {
            "bytes_limit": int(bytes_limit),
            "reserve_bytes": REMAT_RESERVE_BYTES,
            "terms": terms,
            "kept_bytes": kept_bytes,
            "unkept_bytes": self._kept_bytes(*eligible) - kept_bytes,
            "free_bytes": room - kept_bytes,
            "eligible": dict(zip(kinds, eligible)),
            "kept": dict(zip(kinds, kept)),
        }

    def _mla_qkv(self, p, xn, pos):
        """Latent attention's projections, ``xn [B, T, D]`` -> ``q, k,
        v [B, H_loc, T, head_dim]`` for the attention kernels, under
        the scope ``mla_proj``: q through ``wq_a``, an RMSNorm and
        ``wq_b``; k's no-position part and v through ``wkv_a``, an
        RMSNorm and ``wkv_b``; RoPE on the last ``qk_rope_head_dim``
        of a head's q and on the ONE rotary key vector a token, which
        ``wkv_a`` gives beside the latent and every head shares.

        Every operand leaves as the output of a product
        (``tp.col_parallel_heads``), q through one more pass
        (``rope_tail``): what is cut and joined is the WEIGHT
        ``wkv_b`` (``[rank, H_loc, nope + v]``: 18 MB at the
        published widths), not the 168-294 MB activations.  v is the
        latent times the weight's value columns.  k is
        ``[latent | rotary key] [B, T, rank +
        rope]`` times ``[[W_nope, 0], [0, I]]``: rows ``[0, rank)``
        carry the weight's no-position columns into each head's first
        ``nope`` channels, rows ``[rank, rank + rope)`` an identity
        into each head's last ``rope`` — one input times 1,
        accumulated in float32, so the rotary part equals a broadcast
        bit for bit; the matrix unit does the broadcast over the heads
        and the join, and the backward's transposed product returns
        the rotary key's gradient already summed over the heads."""
        eps, theta = self.norm_eps, self.rope_theta
        h_loc = self.n_heads // self.tp
        nope, rank = self.qk_nope_head_dim, self.kv_lora_rank
        rope_dim = self.qk_rope_head_dim
        with jax.named_scope("mla_proj"):
            cq = rms_norm(xn @ p["wq_a"].astype(xn.dtype), p["q_a_norm"], eps)
            q = rope_tail(
                tp_lib.col_parallel_heads(cq, p["wq_b"], h_loc),
                pos, theta, nope,
            )
            ckv = xn @ p["wkv_a"].astype(xn.dtype)
            k_rope = rope(ckv[:, None, :, rank:], pos, theta)[:, 0]
            ckv = rms_norm(ckv[..., :rank], p["kv_a_norm"], eps)
            w = p["wkv_b"].reshape(rank, h_loc, nope + self.v_head_dim)
            w_k = jnp.concatenate([
                jnp.pad(w[..., :nope], ((0, 0), (0, 0), (0, rope_dim))),
                jnp.pad(
                    jnp.broadcast_to(
                        jnp.eye(rope_dim, dtype=w.dtype)[:, None],
                        (rope_dim, h_loc, rope_dim),
                    ),
                    ((0, 0), (0, 0), (nope, 0)),
                ),
            ])
            k = tp_lib.col_parallel_heads(
                jnp.concatenate([ckv, k_rope], axis=-1), w_k, h_loc
            )
            v = tp_lib.col_parallel_heads(ckv, w[..., nope:], h_loc)
            return q, k, v

    def _layer(self, p, x, pos, select_bias=None, *,
               attn_kind="full_attention"):
        """One decoder block on local shards: x [B, T_loc, D]; an
        expert block where ``p`` holds a router (``select_bias``: its
        row ``[E]`` of the selection bias, if the model has one);
        ``attn_kind`` (static): the layer's entry of ``attn_kinds``;
        ``"mamba"`` runs the state-space mixer in attention's place
        (``_mamba_block``) and gives the scan's two counters last,
        ``(x, stats)`` or ``(x, mom, stats)``.  A gated attention call
        (``attention_gate``) gives its gate's counter last
        (``_attn_gate``): ``(x, open)``, ``(x, mom, open)``.  The
        halves a layer has are those whose norm ``p`` holds: a block
        of a pattern is a mixer alone (no ``mlp_norm``) or an
        expert layer alone (no ``attn_norm``, ``attn_kind`` "none").

        With MoE enabled returns ``(x, mom)`` where ``mom`` is the
        fp32 [2E+2] vector of this layer's aux-loss MOMENTS
        (pick fractions f, mean router probs p, z-loss) and its count
        of dropped picks — kept linear so microbatch splits average
        exactly; ``_aux_from_moments`` forms the losses.  Dense blocks
        return just ``x``."""
        cdtype = self.compute_dtype
        eps = self.norm_eps
        # the block names of the step program (``blk_*``, PERF.md §3):
        # metadata only; ``benchmark/layer_metrics/_blocks.py`` joins
        # them with a trace's device time
        # what the mixer half gives beside ``x``, last of the results
        tail = ()
        if attn_kind == "mamba":
            x, stats = self._mamba_block(p, x)
            tail = (stats,)
        elif "attn_norm" in p:
            x, gate_open = self._attn_block(p, x, pos, attn_kind)
            if gate_open is not None:
                tail = (gate_open,)
        # (a block of a ``layer_types`` pattern is ONE half: a mixer without
        # ``mlp_norm``, an expert layer without ``attn_norm``)
        if "mlp_norm" not in p:
            return (x, *tail) if tail else x
        if "router" not in p:
            x = self._dense_ffn(p, x)
            return (x, *tail) if tail else x
        with jax.named_scope("blk_ffn"):
            xn = rms_norm(x, p["mlp_norm"], eps)
            y, aux = moe_ffn(
                xn, p["router"], p.get("we_gate"), p["we_up"], p["we_down"],
                n_experts=self.n_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.capacity_factor,
                expert_axis=EXPERT_AXIS,
                model_axis=MODEL_AXIS,
                # aux losses globalize over the token-sharding axes
                # (layout-invariant; set in compile_iter_fns)
                batch_axes=(*self._dp_axes, SEQ_AXIS),
                renormalize=self.moe_renormalize,
                scoring=self.moe_scoring,
                select_bias=select_bias,
                route_scale=self.moe_route_scale,
                held=self.moe_experts_held,
                **({"latent": (p["w_lat_down"], p["w_lat_up"])}
                   if "w_lat_down" in p else {}),
            )
            mom = jnp.concatenate(
                [aux["f"], aux["p"], aux["z"][None],
                 aux["dropped"][None]]
            ).astype(jnp.float32)
            y = y.astype(cdtype)
            if "ws_up" in p:
                y = y + shared_expert(
                    xn, p.get("ws_gate"), p["ws_up"], p["ws_down"],
                    MODEL_AXIS,
                ).astype(cdtype)
            if self.sandwich_norm:
                y = rms_norm(y, p["mlp_out_norm"], eps)
            return x + self._branch(y), mom, *tail

    def _attn_block(self, p, x, pos, attn_kind):
        """A layer's attention on the residual stream ``x``, from
        ``attn_norm`` to the residual add (``blk_attn``).  Returns
        ``(x, the gate's counter or None)``."""
        cdtype = self.compute_dtype
        hd = self.head_dim
        eps = self.norm_eps
        gate_open = None
        with jax.named_scope("blk_attn"):
            xn = rms_norm(x, p["attn_norm"], eps)
            if self.attention == "mla":
                q, k, v = self._mla_qkv(p, xn, pos)
                o = flash_attention(
                    q, k, v, causal=True, sm_scale=hd ** -0.5
                )
            else:
                o = self._gqa(p, xn, pos, attn_kind)
                if self.attention_gate:
                    with self._kind_scope(attn_kind):
                        o, gate_open = self._attn_gate(p, xn, o)
            a = tp_lib.row_parallel(_unheads(o), p["wo"]).astype(cdtype)
            if self.sandwich_norm:
                a = rms_norm(a, p["attn_out_norm"], eps)
            x = x + self._branch(a)
            if self.attention != "mla":
                # the FFN half's input, named beside q, k and v
                # (``_gqa_qkv``): a call that keeps them replays
                # neither ``wo`` nor the three projections
                x = checkpoint_name(x, ATTN_RESIDUALS[3])
        return x, gate_open

    def _branch(self, y):
        """A block's branch as it enters the residual sum: times
        ``residual_multiplier`` where the model has one."""
        if self.residual_multiplier == 1.0:
            return y
        return (self.residual_multiplier * y).astype(self.compute_dtype)

    def _dense_ffn(self, p, x):
        """A layer's dense SwiGLU on the residual stream ``x``, from
        ``mlp_norm`` to the residual add (``blk_ffn``)."""
        cdtype = self.compute_dtype
        eps = self.norm_eps
        with jax.named_scope("blk_ffn"):
            xn = rms_norm(x, p["mlp_norm"], eps)
            # named for the layer's remat (``_forward``), which keeps
            # the two products of the layer calls the memory holds
            g = checkpoint_name(
                tp_lib.col_parallel(xn, p["w_gate"]), MLP_RESIDUALS[0]
            )
            u = checkpoint_name(
                tp_lib.col_parallel(xn, p["w_up"]), MLP_RESIDUALS[1]
            )
            h = swiglu(g, u)
            y = tp_lib.row_parallel(h, p["w_down"]).astype(cdtype)
            if self.sandwich_norm:
                y = rms_norm(y, p["mlp_out_norm"], eps)
            return x + self._branch(y)

    def _mamba_block(self, p, x):
        """A mamba layer's mixer on the residual stream ``x [B, T,
        D]``, from ``attn_norm`` (the published ``input_layernorm``)
        to the residual add, under ``blk_ssm``; the maths is
        ``ops/ssd.py``'s.  Returns ``(x, float32[2])``: the scan's
        counters (``ssd.ssd_scan``)."""
        cdtype = self.compute_dtype
        with jax.named_scope("blk_ssm"):
            xn = rms_norm(x, p["attn_norm"], self.norm_eps)
            m, stats = ssd.mamba_mixer(
                p, xn, chunk=self.mamba_chunk_size, eps=self.norm_eps,
                **self._mamba,
            )
            return x + self._branch(m.astype(cdtype)), stats

    def _gqa(self, p, xn, pos, kind="full_attention"):
        """Grouped-query attention of ``xn [B, T_loc, D]`` (inside
        ``_layer``'s ``blk_attn``): the three projections, QK-norm,
        RoPE, the kernel or the sequence-parallel ring;
        ``[B, H_loc, T_loc, hd]``.  ``kind``: the layer's attention
        kind — its rotary table and, for ``"sliding_attention"``, the
        window; a model described layer by layer (``attn_per_layer``)
        runs each kind under a scope of its own, ``attn_full`` /
        ``attn_sliding``."""
        if self.attn_per_layer:
            with self._kind_scope(kind):
                return self._gqa_kind(p, xn, pos, kind)
        return self._gqa_kind(p, xn, pos, kind)

    @staticmethod
    def _kind_scope(kind):
        """The scope of an attention kind's layers in a model
        described layer by layer."""
        if kind == "sliding_attention":
            return jax.named_scope("attn_sliding")
        return jax.named_scope("attn_full")

    def _gqa_kind(self, p, xn, pos, kind):
        q, k, v = self._gqa_qkv(p, xn, pos, kind)
        if self.sp == 1:
            # no sequence sharding: skip the ring/all_to_all
            # machinery and hit the fused kernel (reference math
            # off-TPU) directly
            return flash_attention(
                q, k, v, causal=True, window=self.window_of(kind),
                **({} if self.attention_multiplier is None
                   else {"sm_scale": self.attention_multiplier}),
            )
        attn = (
            ring_attention if self.sp_mode == "ring"
            else ulysses_attention
        )
        rep = self.n_heads // self.n_kv_heads
        return attn(q, k, v, SEQ_AXIS, causal=True, kv_rep=rep)

    def _attn_gate(self, p, xn, o):
        """The attention gate (``attention_gate``; the published
        ``gating: "per-head"``, the headwise form of Qiu et al.,
        arXiv:2505.06708) on the kernels' output ``o [B, H_loc, T,
        hd]``, under the scope ``attn_gate``: ``g = sigmoid(xn
        w_attn_gate)``, one number a token and query head from the
        block's normed input, the product accumulated and the sigmoid
        taken in float32; ``o`` times ``g`` over a head's channels,
        before ``wo``.  Returns ``(o gated, the gate's counter)``: the
        mean of ``g`` over this device's tokens and heads, no gradient
        (``obs/gate.py``; 0.5 at a seed's weights — a mean at 0 is a
        block switched off, at 1 a gate that gates nothing)."""
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(jnp.einsum(
                "btd,dh->bht", xn, p["w_attn_gate"].astype(xn.dtype),
                preferred_element_type=jnp.float32,
            ))
            o = (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)
            return o, lax.stop_gradient(jnp.mean(g))

    def _gqa_qkv(self, p, xn, pos, kind):
        """Grouped-query attention's projections, ``xn [B, T, D]`` ->
        ``q [B, H_loc, T, hd]``, ``k`` and ``v`` for the attention
        kernels (``sp == 1``: the key/value heads repeated to
        ``H_loc``) or the sequence-parallel ring (``[B, Hkv_loc, T,
        hd]``: KV stays compact on the wire), under the scope
        ``gqa_proj``.  Between ``attn_norm`` and the kernels no
        activation is re-laid or lane-sliced: each product writes the
        kernels' layout (``tp.col_parallel_heads``), QK-norm's
        statistic is taken over that layout (``rms_norm(.., axes=(1,
        3))``), the
        rotation by the kind's table is one pass over the whole row
        (``rope``).  q, k and v carry the first three names of
        ``ATTN_RESIDUALS``."""
        eps = self.norm_eps
        hd = self.head_dim
        # the LAYER's query heads here: its leaf's local columns
        h_loc = p["wq"].shape[1] // hd
        hkv_loc = self.n_kv_heads // self.tp
        with jax.named_scope("gqa_proj"):
            q = tp_lib.col_parallel_heads(xn, p["wq"], h_loc)
            k = tp_lib.col_parallel_heads(xn, p["wk"], hkv_loc)
            v = checkpoint_name(
                tp_lib.col_parallel_heads(xn, p["wv"], hkv_loc),
                ATTN_RESIDUALS[2],
            )
            # q and k are named for the layer's remat (``_forward``)
            # where the backward reads them: the products' outputs
            # under QK-norm (its backward reads those; the norm and
            # the rotation, two elementwise passes, are replayed),
            # else what the kernels take, after the rotation; k and v
            # always before the repeat
            if self.qk_norm:
                q = checkpoint_name(q, ATTN_RESIDUALS[0])
                k = checkpoint_name(k, ATTN_RESIDUALS[1])
                q = rms_norm(q, p["q_norm"].reshape(h_loc, 1, hd), eps,
                             h_loc * self.tp * hd, axes=(1, 3))
                k = rms_norm(k, p["k_norm"].reshape(hkv_loc, 1, hd), eps,
                             self.n_kv_heads * hd, axes=(1, 3))
            if self.position_embedding_type == "rope":
                inv_freq, factor = self._rope_tables[kind]
                # a table narrower than the head (a kind's
                # ``partial_rotary_factor``): the channels before it
                # pass as they are
                nope = hd - self.rotary_channels[kind]
                q = rope(q, pos, self.rope_theta, inv_freq, factor, nope)
                k = rope(k, pos, self.rope_theta, inv_freq, factor, nope)
            if not self.qk_norm:
                q = checkpoint_name(q, ATTN_RESIDUALS[0])
                k = checkpoint_name(k, ATTN_RESIDUALS[1])
            if self.sp == 1 and h_loc != hkv_loc:
                k = jnp.repeat(k, h_loc // hkv_loc, axis=1)
                v = jnp.repeat(v, h_loc // hkv_loc, axis=1)
            return q, k, v

    def _head_weight(self, params):
        """The head's ``[D, V/tp]``: the embedding transposed where
        ``tie_word_embeddings`` (ONE leaf, whose gradient is then the
        lookup's scatter-add plus the head's ``dW``)."""
        if self.tie_word_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _forward(self, params, ids, head=True, with_aux=False,
                 net_state=None, mtp_ids=None, with_ssm=False,
                 with_gate=False):
        """ids [B_loc, T_loc] -> local vocab-shard logits [.., V/tp].

        ``net_state``: the step's state beside the parameters
        (``_init_net_state``), read without a gradient.  ``mtp_ids``
        (with ``head=False``; the train loss path of a model with an
        MTP module): the next token at every position; the hidden
        states come back as TWO exits ``[2, B, T, D]``, the main
        model's and the MTP module's (``_mtp_hidden``), each closed
        by its own norm, for the one shared head.

        With ``pp > 1`` and the default scattered head, logits are a
        VALID 1/S TOKEN SLICE on every stage ([n_tok/S, V/tp]) —
        metrics must slice targets with ``_pp_targets`` (same
        geometry) and recombine through ``_pp_value`` (pipe-pmean).
        On the ragged fallback (``_pp_scatter`` False) logits are
        instead valid on the LAST stage only (other stages hold
        zeros-driven garbage) and ``_pp_value`` masks to it.

        ``with_aux=True`` (train loss path) additionally returns the
        MoE aux pair [lb, z], averaged over layers and pipe-broadcast
        (zeros when the model is dense), and the routing counters
        ``[L, E+1]`` of ``_routing_counters`` (None when dense).
        ``with_ssm=True`` (a stack with mamba layers): the scans'
        counters ``[L_mamba, 2]`` come back last, after the expert
        layers' pair where there is one (``obs/ssm.py``).
        ``with_gate=True`` (a model with an attention gate): what the
        other arguments ask for comes back FIRST of a pair, the gated
        calls' counters ``[calls]`` second (``obs/gate.py``)."""
        cdtype = self.compute_dtype
        t_loc = ids.shape[1]
        seq_idx = lax.axis_index(SEQ_AXIS)
        pos = seq_idx * t_loc + jnp.arange(t_loc)

        with jax.named_scope("blk_embed"):
            x = tp_lib.embed_lookup(ids, params["embed"], self.vocab)
            if self.embedding_multiplier != 1.0:
                x = self.embedding_multiplier * x
            x = x.astype(cdtype)

        def remat(fn, *names):
            # every call's replay skips the flash forward kernel: its
            # output and logsumexp (named in its forward rule,
            # ``ops/attention.py``) are kept, [B, H_loc, T, hd] and 4
            # bytes a row, so the backward runs the backward kernel only.
            # Where the kernel does not run (dense path;
            # ``ring_attention``, whose own vjp calls the kernels
            # unnamed) the names never occur.  A dropless expert
            # layer's tile plan (``parallel/moe.py``) is kept the same
            # way: built once a layer call.  The LAST
            # ``remat_kept_calls`` dense calls (the first the backward
            # reaches, so their copies live shortest) also keep the
            # dense MLP's gate and up products, the last
            # ``remat_kept_attn_calls`` grouped-query attention calls
            # q, k, v and the attention block's output
            # (``ATTN_RESIDUALS``), the last ``remat_kept_moe_calls``
            # dropless expert calls their sorted rows, those rows'
            # gate and up products and the sort's two results
            # (``MOE_RESIDUALS``).  Replayed in every call: the
            # norms, ``swiglu`` and the down projection, the K/V
            # repeat, QK-norm and the rotation after it, the router,
            # the expert masters' casts and ``silu(g) * u``; in a call
            # that keeps no more than ``remat_saves``, the
            # projections around the kernel, the MLP's two products
            # and the expert layer's gather and two products as well.
            return jax.checkpoint(
                fn,
                policy=jax.checkpoint_policies.save_only_these_names(*names),
            )

        kinds = set(self.attn_kinds)

        @functools.cache
        def layer_of(kind, extra):
            """The layer call of an attention kind whose remat keeps
            the names ``extra`` beside ``remat_saves``: one policy for
            each ``extra`` that occurs (plain; + the MLP's, attention's
            or the expert layer's names, as the call keeps them)."""
            # a model of one plain kind calls the method itself
            fn = self._layer if kinds == {"full_attention"} else (
                functools.partial(self._layer, attn_kind=kind)
            )
            return remat(fn, *self.remat_saves, *extra) if (
                self.remat) else fn

        # the last layer's kind: the MTP block's, and the pipeline's
        # (one kind of layer there)
        layer = layer_of(self.attn_kinds[-1], ())

        moe = "moe" in self.layer_kinds
        aux = jnp.zeros((2,), jnp.float32)
        routing = None
        exits = mtp_x = None
        # a row of selection bias an expert layer call, in call order
        # (None for a model without one)
        bias_rows = (
            iter(net_state["moe_bias"]) if self.moe_select_bias
            else itertools.repeat(None)
        )
        ssm = []        # a mamba layer's scan counters, in layer order
        gates = []      # a gated attention call's counter, in call order

        def take_gate(res):
            """A layer call's results less its gate's counter, which a
            gated attention call gives last (``_layer``)."""
            if not self.attention_gate:
                return res
            *res, gate_open = res
            gates.append(gate_open)
            return res[0] if len(res) == 1 else res

        if self.pp == 1:
            kept_mlp, kept_attn, kept_moe = self._kept_calls()

            def stack(x, first_call=0):
                moms = []
                for call, (p, kind) in enumerate(
                    zip(params["layers"], self.attn_kinds), first_call
                ):
                    fn = layer_of(
                        kind,
                        MLP_RESIDUALS * (call in kept_mlp)
                        + ATTN_RESIDUALS * (call in kept_attn)
                        + MOE_RESIDUALS * (call in kept_moe),
                    )
                    res = (fn(p, x, pos, next(bias_rows)) if "router" in p
                           else fn(p, x, pos))
                    if kind == "mamba":     # its scan's counters, last
                        *res, stats = res
                        ssm.append(stats)
                        res = res[0] if len(res) == 1 else res
                    elif kind != "none":
                        res = take_gate(res)
                    if "router" in p:
                        x, mom = res
                        moms.append(mom)
                    else:
                        x = res
                return x, (jnp.stack(moms) if moms else None)

            if self.ut_steps == 1:
                x, moms = stack(x)
                if mtp_ids is not None:
                    mtp_x, mom = self._mtp_hidden(
                        params, x, mtp_ids, pos,
                        layer if not self.attention_gate
                        else lambda *args: take_gate(layer(*args)),
                        bias_rows,
                    )
                    if mom is not None:
                        moms = jnp.concatenate([moms, mom[None]])
            else:
                # the looped decoder: R passes over the SAME leaves
                # (their gradients are sums over the passes), the
                # final norm closing each; the normed output is that
                # pass's exit and the next pass's input.  The passes
                # are unrolled, as the layers are: as a ``lax.scan``
                # the step kept 0.9 GiB more and ran 13 % slower at
                # the cell's sizes (PERF.md, PR 33).
                exits, moms = [], []
                with jax.named_scope("ut_stack"):
                    for step in range(self.ut_steps):
                        x, mom = stack(x, step * self.n_layers)
                        x = rms_norm(x, params["final_norm"], self.norm_eps)
                        exits.append(x)
                        moms.append(mom)
                exits = jnp.stack(exits)
                if moe:     # [R][L, ...] -> a row a layer call
                    moms = jnp.concatenate(moms)
            if moe:
                aux = self._aux_from_moments(moms)
                routing = self._routing_counters(moms)
        else:
            # GPipe over the pipe axis: the embed above is replicated
            # compute (only stage 0's copy feeds the chain — backward
            # through the stage-0 injection mask zeroes the rest) and
            # the blocks pipeline microbatch-wise.  The head below
            # runs, by default, on a 1/S token slice per stage (the
            # scatter block just after the pipeline; grads reassemble
            # through the psum/slice transposes).  On the ragged
            # fallback (_pp_scatter False) it instead runs on every
            # stage masked to the last by _pp_value, whose
            # where-transpose zeroes garbage-stage cotangents.
            l_loc = self.n_layers // self.pp

            stage0 = lax.axis_index(PIPE_AXIS) * l_loc

            def stage_fn(stage_params, payload):
                xm, am = (payload["x"], payload["aux"]) if moe else (
                    payload, None
                )
                for i in range(l_loc):
                    p = jax.tree.map(lambda a: a[i], stage_params)
                    if moe:
                        xm, mom = layer(p, xm, pos)
                        # this stage's global layer row: the moment
                        # rows travel WITH the microbatch, so the last
                        # stage's payload holds every layer's moments
                        am = lax.dynamic_update_slice(
                            am, mom[None, :], (stage0 + i, 0)
                        )
                    else:
                        xm = layer(p, xm, pos)
                return {"x": xm, "aux": am} if moe else xm

            xmb = split_microbatches(x, self.pp_microbatches)
            if moe:
                # per-layer aux MOMENTS ride the pipe alongside the
                # activation (kept linear so the microbatch mean below
                # is exact — the losses form after averaging)
                xmb = {
                    "x": xmb,
                    "aux": jnp.zeros(
                        (
                            self.pp_microbatches,
                            self.n_layers,
                            2 * self.n_experts + 2,
                        ),
                        jnp.float32,
                    ),
                }
            ys = pipeline_apply(stage_fn, params["layers"], xmb)
            if moe:
                # microbatch-mean of the per-layer moments (valid on
                # the last stage, broadcast), then form the losses —
                # exactly the pp=1 numbers, any microbatch count
                mom = last_stage_value(jnp.mean(ys["aux"], axis=0))
                aux = self._aux_from_moments(mom)
                # (a count summed, not averaged, over the microbatches)
                routing = self._routing_counters(mom).at[:, -1].multiply(
                    self.pp_microbatches
                )
                ys = ys["x"]
            x = merge_microbatches(ys)
            if self._pp_scatter:
                # LAST-STAGE-ONLY HEAD, cost-shared (VERDICT r2 item
                # 6): broadcast the last stage's (only valid)
                # activations over the pipe axis and hand each stage
                # 1/S of the tokens — head FLOPs become 1/S per
                # device instead of replicated-and-masked.  The
                # broadcast moves n_tok x D activation bytes over the
                # pipe axis, orders of magnitude below the
                # n_tok x D x V head FLOPs it stops duplicating;
                # targets/metrics slice with the SAME geometry
                # (_pp_slice_tokens) and recombine by pipe-pmean
                # (_pp_value).
                x = self._pp_slice_tokens(last_stage_value(x))

        if exits is None:
            with jax.named_scope("blk_head"):
                x = rms_norm(x, params["final_norm"], self.norm_eps)
                if self.logits_scaling != 1.0:
                    # the logits divided: the head is linear in its rows
                    x = (x / self.logits_scaling).astype(cdtype)
            if mtp_x is not None:
                exits = jnp.stack([x, mtp_x])
        if not head:
            # a looped decoder gives its R exits [R, B, T, D] (the
            # last of them is ``x``): the loss reads them all
            h = x if exits is None else exits
            out = (h, aux, routing) if with_aux else (h,)
            if with_ssm:
                out = (*out, jnp.stack(ssm))
            if len(out) == 1:
                out, = out
            return (out, jnp.stack(gates)) if with_gate else out
        # logits stay in compute dtype: the xent/metric reductions
        # upcast to fp32 INSIDE their fused reads (tp.py), so an
        # .astype(f32) here would only materialize a second, 2x-wide
        # copy of [N, V] in HBM (profiled at ~1 GB/step on the bench
        # proxy).  Same values either way — the matmul already ran in
        # compute dtype.
        with jax.named_scope("blk_head"):
            logits = tp_lib.col_parallel(x, self._head_weight(params))
        return (logits, aux, routing) if with_aux else logits

    def _kept_calls(self) -> tuple[frozenset, frozenset, frozenset]:
        """The layer calls whose remat also keeps ``MLP_RESIDUALS``,
        those whose remat also keeps ``ATTN_RESIDUALS`` and those
        whose remat also keeps ``MOE_RESIDUALS``: the last
        ``remat_kept_calls`` of the calls that ARE dense (an expert
        call names neither product), the last
        ``remat_kept_attn_calls`` of those that run grouped-query
        attention (latent attention and a mamba call name none) and
        the last ``remat_kept_moe_calls`` of the expert calls."""
        def last(n, names_them):
            calls = [
                i for i, ok in enumerate(names_them * self.ut_steps) if ok
            ]
            return frozenset(calls[len(calls) - n:])

        return (
            last(self.remat_kept_calls,
                 tuple(kind == "dense" for kind in self.layer_kinds)),
            last(self.remat_kept_attn_calls, self._gqa_layers),
            last(self.remat_kept_moe_calls,
                 tuple(kind == "moe" for kind in self.layer_kinds)),
        )

    def _mtp_hidden(self, params, x, next_ids, pos, layer, bias_rows):
        """The MTP module (depth 1) on the stack's output ``x [B, T,
        D]`` (BEFORE the final norm) and the next tokens ``next_ids
        [B, T]``, under the scope ``mtp``: the main model's embedding
        of the next token and ``x``, each through its own RMSNorm,
        side by side through ``eh_proj [2D, D]``; one more block of
        the last layer's kind with weights (and selection bias) of
        its own; the module's closing norm.  What comes back goes
        through the main model's head against the token AFTER next.
        Returns ``(hidden [B, T, D], the block's moments or None)``."""
        mp = params["mtp"]
        eps = self.norm_eps
        with jax.named_scope("mtp"):
            with jax.named_scope("blk_embed"):
                emb = tp_lib.embed_lookup(
                    next_ids, params["embed"], self.vocab
                ).astype(x.dtype)
            with jax.named_scope("blk_mtp_in"):
                both = jnp.concatenate([
                    rms_norm(emb, mp["enorm"], eps),
                    rms_norm(x, mp["hnorm"], eps),
                ], axis=-1)
                h = both @ mp["eh_proj"].astype(x.dtype)
            mom = None
            if "router" in mp["block"]:
                h, mom = layer(mp["block"], h, pos, next(bias_rows))
            else:
                h = layer(mp["block"], h, pos)
            with jax.named_scope("blk_head"):
                return rms_norm(h, mp["head_norm"], eps), mom

    def _mtp_loss(self, params, exits, y, head_xent=None):
        """The training loss of a model with an MTP module from its
        two exits ``[2, N, D]`` and the next tokens ``y [B, T]``: the
        main exit's mean cross-entropy against ``y`` plus ``mtp_coef``
        times the module's against the token after next (``y`` one
        place further on; a sequence's last position has none and
        weighs nothing, and the mean stays over all ``N``).  Both go
        through the one head as the looped decoder's exits do
        (``tp.exits_unembed_xent``, labels ``[2, N]``), or, with
        ``head_xent(h [N, D], labels [N]) -> (loss_vec, pred)`` given,
        through the streamed head one after the other.  Returns
        ``(loss, err)``: local token means, ``err`` the main exit's."""
        b, t = y.shape
        n = b * t
        yf = y.reshape(-1)
        labels = jnp.stack([
            yf, jnp.concatenate([y[:, 1:], y[:, -1:]], axis=1).reshape(-1),
        ])
        row_w = jnp.stack([
            jnp.full((n,), 1.0 / n, jnp.float32),
            jnp.tile(
                jnp.where(jnp.arange(t) < t - 1, self.mtp_coef / n, 0.0)
                .astype(jnp.float32), b,
            ),
        ])
        if head_xent is None:
            loss, _, pred = tp_lib.exits_unembed_xent(
                exits, self._head_weight(params), labels, row_w, self.vocab,
                MODEL_AXIS,
            )
            pred = pred[0]
        else:
            (main, pred), (after, _) = (
                head_xent(exits[i], labels[i]) for i in range(2)
            )
            loss = jnp.sum(row_w[0] * main) + jnp.sum(row_w[1] * after)
        err = jnp.mean((pred != yf).astype(jnp.float32))
        return loss, err

    def _exit_loss(self, params, exits, targets, head=None):
        """The looped decoder's training loss from its R exits
        ``[R, N, D]``: per token ``sum_t q_t * xent_t - beta * H(q)``,
        where ``lam_t = sigmoid(z_t . w_g + b_g)`` (t < R) is the
        share of the mass reaching exit t that leaves there, ``q_1 =
        lam_1``, ``q_t = lam_t * prod_{j<t}(1 - lam_j)`` and ``q_R``
        the rest.  The exit weights are known before any head runs,
        so the dense head (``head`` None) is GIVEN them and computes
        its gradients in its forward pass (``tp.exits_unembed_xent``):
        three head products an exit, one exit's logits alive at once,
        none kept for the backward and none replayed there; the gate's
        gradient comes back through the head's row weights.  Else
        ``head(z [N, D]) -> (loss_vec [N], pred [N])`` is the streamed
        head, which keeps no logits: the exits go through it one at a
        time (a ``lax.map``) and the exit weights reach it as the
        cotangent of its loss vector.

        Returns ``(loss, err, counters)``: the local token means of
        the loss and of the LAST exit's top-1 error, and the counters
        ``[2R + 1]`` = mean ``q_t``, mean ``xent_t``, mean exit step
        ``sum_t t * q_t`` (``obs/exits.py``)."""
        r = exits.shape[0]
        with jax.named_scope("ut_exit"):
            z = exits[:-1].astype(jnp.float32)
            a = (
                jnp.sum(z * params["exit_gate_w"][:, 0], axis=-1)
                + params["exit_gate_b"][0]
            )                                           # [R-1, N]
            stay = jnp.cumsum(jax.nn.log_sigmoid(-a), axis=0)
            log_q = jnp.concatenate([
                jax.nn.log_sigmoid(a)
                + jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]]),
                stay[-1:],
            ])                                          # [R, N]
            q = jnp.exp(log_q)
            entropy = -jnp.sum(q * log_q, axis=0)
            if head is None:
                # the token mean's 1 / N inside the row weights: the
                # softmax gradient is rounded to the compute dtype
                # once, after that product, as under autodiff's mean
                weighted, xent, pred = tp_lib.exits_unembed_xent(
                    exits, self._head_weight(params), targets,
                    q / exits.shape[1], self.vocab, MODEL_AXIS,
                )       # xent, pred: no gradient; counters only
            else:
                xent, pred = lax.map(head, exits)       # [R, N] each
                weighted = jnp.mean(jnp.sum(q * xent, axis=0))
            loss = weighted - self.exit_beta * jnp.mean(entropy)
            err = jnp.mean((pred[-1] != targets).astype(jnp.float32))
            mass = jnp.mean(q, axis=1)
            counters = lax.stop_gradient(jnp.concatenate([
                mass,
                jnp.mean(xent, axis=1),
                jnp.sum(mass * jnp.arange(1, r + 1))[None],
            ]))
        return loss, err, counters

    def _routing_counters(self, moms):
        """[L, 2E+2] per-layer moments -> the step's routing counters
        ``[L, E+1]``: each expert's share ``f`` of the picks (1/E at
        balance) and, last, the picks no expert computed."""
        e = self.n_experts
        return lax.stop_gradient(
            jnp.concatenate([moms[:, :e], moms[:, -1:]], axis=1)
        )

    def _aux_from_moments(self, moms):
        """[L, 2E+2] per-layer aux moments (f, p, z — see ``_layer``)
        -> fp32 [load-balance loss, z-loss], layer-averaged.  The
        product ``E·Σ f·p`` forms HERE, after any microbatch
        averaging, so pipeline microbatching never changes the loss."""
        e = self.n_experts
        f, p, z = moms[:, :e], moms[:, e:2 * e], moms[:, 2 * e]
        lb = e * jnp.sum(f * p, axis=-1)
        return jnp.stack([jnp.mean(lb), jnp.mean(z)])

    def _pp_value(self, v):
        """Combine a per-stage metric across pipeline stages: with the
        scattered head every stage holds an equal-slice partial (mean
        of means = global mean via pmean); the masked path replicates
        the last stage's value.  Identity when pp == 1."""
        if self.pp == 1:
            return v
        if self._pp_scatter:
            return lax.pmean(v, PIPE_AXIS)
        return last_stage_value(v)

    def _pp_slice_tokens(self, arr):
        """This stage's 1/pp token slice of a [B_loc, T_loc, ...]
        array, flattened row-major over (B, T) — the ONE geometry both
        the scattered head (activations) and ``_pp_targets`` (labels)
        must share, or logits and targets misalign."""
        n_tok = arr.shape[0] * arr.shape[1]
        flat = arr.reshape((n_tok,) + arr.shape[2:])
        sl = n_tok // self.pp
        return lax.dynamic_slice_in_dim(
            flat, lax.axis_index(PIPE_AXIS) * sl, sl, axis=0
        )

    def _pp_targets(self, y):
        """Token-slice the targets the same way the scattered head
        sliced the activations (identity otherwise)."""
        return self._pp_slice_tokens(y) if self._pp_scatter else y

    def _metrics(self, logits_loc, targets, top5: bool = False):
        """loss/top-1 (+ optional top-5, val-only: its candidate
        all_gathers are pure overhead on the train hot path)."""
        targets = self._pp_targets(targets)
        loss = tp_lib.sharded_softmax_xent(logits_loc, targets, self.vocab)
        err = tp_lib.sharded_top1_err(logits_loc, targets, self.vocab)
        # average over the data/seq shards (each computed a local mean);
        # with pp, keep only the last stage's value first
        dp = self._dp_axes
        loss = lax.pmean(self._pp_value(loss), (*dp, SEQ_AXIS))
        err = lax.pmean(self._pp_value(err), (*dp, SEQ_AXIS))
        if not top5:
            return loss, err
        err5 = tp_lib.sharded_topk_err(logits_loc, targets, self.vocab, k=5)
        # the model-axis pmean is a numerical no-op (every shard holds
        # the same gathered candidates) but marks err5 vma-invariant
        err5 = lax.pmean(
            self._pp_value(err5), (*dp, SEQ_AXIS, MODEL_AXIS)
        )
        return loss, err, err5

    # -- contract ---------------------------------------------------------

    def build_model(self, n_replicas: int = 1) -> None:
        with setup_phase("data"):
            self.data = MarkovLMData(
                vocab=self.vocab,
                seq_len=self.seq_len,
                batch_size=int(self.config.get("batch_size", 8)),
                n_replicas=n_replicas,
                n_train=int(self.config.get("n_train", 2048)),
                # ``validate: false``: no validation set, so the
                # worker's per-epoch validation pass does not run (a
                # set smaller than one global batch is none either)
                n_val=int(self.config.get("n_val", 256))
                if self.config.get("validate", True) else 0,
                seed=self.seed,
            )
        # params materialize in compile_iter_fns, under jit with sharded
        # out_shardings — the full tree never lives on one device
        self.params = None
        self.opt_state = None

    def _xent_chunks(self) -> int:
        """Vocab chunks of the training head (1: the dense head), from
        the ``xent_chunks`` key and the local vocab."""
        xc = self.config.get("xent_chunks", "auto")
        v_loc = self.vocab // self.tp
        if xc == "auto":
            return tp_lib.pick_xent_chunks(v_loc) if v_loc >= 65536 else 1
        n_xent_chunks = max(1, int(xc or 1))
        if v_loc % n_xent_chunks:
            raise ValueError(
                f"xent_chunks={n_xent_chunks} must divide the "
                f"local vocab {v_loc} (vocab {self.vocab} / tp "
                f"{self.tp}) — a ragged chunking would silently "
                f"drop the tail vocab columns from the loss"
            )
        return n_xent_chunks

    def compile_iter_fns(
        self,
        mesh: Mesh | None = None,
        exch_strategy: str | None = None,
        **unknown,
    ) -> None:
        if unknown:
            raise TypeError(
                f"Llama.compile_iter_fns: unknown kwargs {sorted(unknown)}"
            )
        # validated before the mesh and the parameter shapes are built
        plan = ExchangePlan.from_config(self.config, exch_strategy)
        if mesh is None:
            mesh = make_mesh(
                model=self.tp, seq=self.sp, pipe=self.pp, expert=self.ep
            )
        self.mesh = mesh
        assert mesh.shape[MODEL_AXIS] == self.tp, (
            f"mesh model axis {mesh.shape[MODEL_AXIS]} != tp {self.tp}"
        )
        assert mesh.shape[SEQ_AXIS] == self.sp
        assert mesh.shape.get(PIPE_AXIS, 1) == self.pp, (
            f"mesh pipe axis {mesh.shape.get(PIPE_AXIS, 1)} != pp {self.pp}"
        )
        assert mesh.shape.get(EXPERT_AXIS, 1) == self.ep, (
            f"mesh expert axis {mesh.shape.get(EXPERT_AXIS, 1)} != "
            f"ep {self.ep}"
        )
        n_dp = dp_replicas(mesh)
        # the per-shard batch must be the configured batch_size: the
        # scattered head's token-slice guard (and the data pipeline's
        # shard math) are derived from it, so a mesh whose data axis
        # disagrees with build_model's n_replicas would silently slice
        # the wrong token count (ADVICE-style hazard, caught here)
        assert (
            n_dp * int(self.config.get("batch_size", 8))
            == self.data.global_batch
        ), (
            f"mesh (expert x data) {n_dp} x per-replica "
            f"batch {self.config.get('batch_size', 8)} != global batch "
            f"{self.data.global_batch} (build_model n_replicas must "
            f"match the mesh)"
        )
        # the DP reduction set: (expert, data) when the mesh carries an
        # expert axis (size 1 is free), data alone on bare meshes
        dp_axes = (
            (EXPERT_AXIS, DATA_AXIS)
            if EXPERT_AXIS in mesh.shape else (DATA_AXIS,)
        )
        self._dp_axes = dp_axes

        specs = self.param_specs()
        # optimizer-state layout mirrors the params': adam m/v (t is
        # replicated), momentum velocity; sgd keeps no state
        if self.opt_name == "adam":
            opt_specs = {"m": specs, "v": specs, "t": P()}
        elif self.opt_name == "sgd":
            opt_specs = ()
        else:  # momentum / nesterov velocity
            opt_specs = specs

        # The DP gradient exchange (wire dtype x collective shape x
        # compression, ``parallel.ExchangePlan``) reduces over the DP
        # axes only; TP/SP collectives are part of the model math.
        # Its flat buffers (zero1 m/v, EF residuals) hold a shard of
        # the already tp/pp-sharded local pack, so they vary over
        # every non-seq mesh axis (param grads are psum'd over seq
        # inside autodiff).  MoE: expert and dense leaves reduce over
        # DIFFERENT axis sets, so that exchange is per leaf.
        plan = self.exchange = plan.bind(
            mesh.shape,
            # LOCAL (per-device) parameter-pack size: what the
            # exchange packs
            n_elems=self._local_params(mesh.shape)[0],
            replica_axes=dp_axes,
            flat_axes=tuple(
                a for a in (PIPE_AXIS, EXPERT_AXIS, DATA_AXIS, MODEL_AXIS)
                if a in mesh.shape
            ),
            optimizer=self.optimizer,
            per_leaf=bool(self.n_experts),
        )
        if plan.zero1:
            opt_specs = plan.opt_state_specs
        ef_specs = plan.ef_specs
        self._specs, self._opt_specs = specs, opt_specs
        batch_spec = P(
            dp_axes if len(dp_axes) > 1 else dp_axes[0], SEQ_AXIS
        )
        # chunked-head resolution: the streamed head is a MEMORY
        # feature — at 8B-scale vocab the [N, V] logits don't fit
        # next to the activations — not a throughput one (benched on
        # the 32k-vocab proxy: -1.4%, the backward's chunk recompute
        # costs one extra head matmul).  "auto" therefore chunks only
        # when the LOCAL vocab is >= 64k; an int pins the chunk
        # count; 0/1 forces the dense head.
        n_xent_chunks = self._n_xent_chunks = self._xent_chunks()
        self.keep_bytes_limit = device_bytes_limit(mesh.devices.flat)
        (self.remat_kept_calls, self.remat_kept_attn_calls,
         self.remat_kept_moe_calls) = self.remat_keep_calls(
            self.keep_bytes_limit
        )

        # expert-sharded leaves exchange differently (see step below);
        # identified once from the specs
        def _leaf_has_expert(spec):
            return any(
                ax == EXPERT_AXIS
                or (isinstance(ax, tuple) and EXPERT_AXIS in ax)
                for ax in spec
            )

        expert_mask = jax.tree.map(
            _leaf_has_expert, specs, is_leaf=lambda s: isinstance(s, P)
        )
        ep = self.ep

        # the state beside parameters and optimizer state (a selection
        # bias): an argument more of the step, and its first result
        # after ``ef``, only for a model that has one
        state_specs = self._state_specs = (
            (jax.tree.map(lambda _: P(), self._init_net_state()),)
            if self.moe_select_bias else ()
        )
        picks = self.data.global_batch * self.seq_len * self.moe_top_k
        has_ssm = self.has_mamba
        has_gate = self.attention_gate

        def step(params, opt_state, ef, x, y, lr, *state):
            # Pre-cast params to DP-VARYING before autodiff: if they
            # stayed invariant, the vma transpose of their broadcast
            # into the data-varying compute would insert an implicit
            # fp32 psum of the grads — summing (not averaging) over
            # data and bypassing the strategy's wire dtype.  With the
            # cast, grads come back as per-shard local grads and the
            # strategy's allreduce-mean below IS the DP exchange.
            # (Expert-sharded leaves are already expert-varying; only
            # the missing axes are cast.)
            def pvary_dp(a):
                need = tuple(
                    ax for ax in dp_axes if ax not in jax.typeof(a).vma
                )
                return lax.pcast(a, need, to="varying") if need else a

            params_v = jax.tree.map(pvary_dp, params)

            def head_xent(h2, yf, p):
                """(loss_vec [N], pred [N]) of hidden rows [N, D]."""
                if n_xent_chunks > 1:
                    # chunked head: unembed + xent streamed over vocab
                    # chunks — full logits never hit HBM (tp.py)
                    return tp_lib.chunked_unembed_xent(
                        h2, self._head_weight(p), yf, self.vocab,
                        n_xent_chunks, MODEL_AXIS,
                    )
                # dense custom head: logits saved once in compute
                # dtype, grad matmuls get bf16 operands (autodiff
                # handed them an fp32 dlogits — ~52% MXU on the
                # lm_head dW, profiled r4)
                return tp_lib.dense_unembed_xent(
                    h2, self._head_weight(p), yf, self.vocab, MODEL_AXIS,
                )

            def loss_fn(p):
                # LOCAL (per-data-shard) metrics: data axis stays out
                # of autodiff (see cast above); SP/TP reductions remain
                # part of the model math
                yv = self._pp_targets(y)
                counters = ()
                more = dict(net_state=state[0]) if state else {}
                if self.mtp_depth:
                    more["mtp_ids"] = y
                out = self._forward(
                    p, x, head=False, with_aux=bool(self.n_experts),
                    with_ssm=has_ssm, with_gate=has_gate, **more
                )
                gate_open = ()
                if has_gate:    # the gated calls' counters ride out last
                    out, gate = out
                    gate_open = (gate,)
                if has_ssm:     # the scans' counters come last
                    *out, ssm_stats = out
                    out = out[0] if len(out) == 1 else out
                if self.n_experts:
                    h, aux, routing = out
                    counters = (routing,)
                else:
                    h = out
                # [N, D] rows; a looped decoder's R exits [R, N, D]
                h2 = h.reshape(*h.shape[:-3], -1, h.shape[-1])
                yf = yv.reshape(-1)
                with jax.named_scope("blk_head"):
                    if self.ut_steps > 1:
                        # the dense head would keep R sets of [N, V]
                        # logits for the backward, or replay each:
                        # the exits' own head needs neither
                        # (``_exit_loss``); the streamed head keeps
                        # none, an exit at a time
                        head = None
                        if n_xent_chunks > 1:
                            def head(z):
                                return head_xent(z, yf, p)

                        loss, err, exit_counters = self._exit_loss(
                            p, h2, yf, head
                        )
                        counters += (
                            lax.pmean(exit_counters, SEQ_AXIS),
                        )
                    elif self.mtp_depth:
                        # the main exit and the MTP module's through
                        # the one head (``_mtp_loss``)
                        loss, err = self._mtp_loss(
                            p, h2, yv,
                            (lambda z, labels: head_xent(z, labels, p))
                            if n_xent_chunks > 1 else None,
                        )
                    else:
                        loss_vec, pred = head_xent(h2, yf, p)
                        loss = jnp.mean(loss_vec)
                        err = jnp.mean((pred != yf).astype(jnp.float32))
                    loss = lax.pmean(self._pp_value(loss), SEQ_AXIS)
                    err = lax.pmean(self._pp_value(err), SEQ_AXIS)
                if has_ssm:
                    counters += (ssm_stats,)
                counters += gate_open
                if self.n_experts:
                    # MoE aux losses (layer-averaged in _forward,
                    # already globally token-averaged inside moe_ffn):
                    # load balance + optional z-loss — gradients flow
                    # to the routers through probs
                    loss = (
                        loss
                        + self.moe_aux_coef * aux[0]
                        + self.moe_z_coef * aux[1]
                    )
                return loss, (err, *counters)

            # check_vma=True autodiff returns exact grads for the TP/SP
            # layout (psum↔pvary transposes); the data-parallel mean is
            # THE exchange, routed through the strategy (bf16 wire on
            # ici16/nccl16 — reference: exchanger_strategy fp16 wire)
            # (the step's counters ride out with the error: a MoE's
            # routing, then a looped decoder's exits)
            (loss, (err, *counters)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params_v)
            params, opt_state, ef = plan.apply(
                params, grads, opt_state, ef, lr,
                expert_mask=expert_mask, ep=ep,
            )
            loss = lax.pmean(loss, dp_axes)
            err = lax.pmean(err, dp_axes)
            if self.ut_steps > 1:   # token means, as the loss is
                counters[-1] = lax.pmean(counters[-1], dp_axes)
            if has_ssm:
                # [L_mamba, 2]: the extreme over the replicas'
                # sequences, the mean of their states' sizes (a gate's
                # counters come after them)
                every, at = (*dp_axes, SEQ_AXIS), -1 - has_gate
                counters[at] = jnp.stack([
                    lax.pmin(counters[at][:, 0], every),
                    lax.pmean(counters[at][:, 1], every),
                ], axis=1)
            if has_gate:
                # [calls]: the mean over every replica's tokens and
                # every shard's heads
                counters[-1] = lax.pmean(
                    counters[-1], (*dp_axes, SEQ_AXIS, MODEL_AXIS))
            if state:
                # after the optimizer, outside it and the exchange:
                # each router's selection bias a step toward balance,
                # from the pick fractions the step counted (global
                # means already: every replica moves alike)
                bias = select_bias_step(
                    state[0]["moe_bias"], counters[0][:, :self.n_experts],
                    picks, self.moe_bias_rate,
                )
                state = ({"moe_bias": bias},)
                counters.insert(1, jnp.max(jnp.abs(bias), axis=-1))
            return params, opt_state, ef, *state, loss, err, *counters

        def val(params, x, y):
            logits = self._forward(params, x)
            return self._metrics(logits, y, top5=True)

        # TPU compiler knobs (utils/xla_options); the overlap preset
        # under the same gate as ClassifierModel.compile_iter_fns
        from theanompi_tpu.utils.xla_options import xla_compiler_options

        is_tpu = mesh.devices.flat[0].platform == "tpu"
        # a MoE step also gives out its routing counters [L, E+1]
        # (and each selection bias's largest size [L], where it has
        # them), a looped decoder's its exit counters [2R + 1], a
        # mamba stack's its scans' [L_mamba, 2], a gated one's [calls]
        counter_out = self._counter_out_specs = (P(),) * (
            bool(self.n_experts) + len(state_specs) + (self.ut_steps > 1)
            + has_ssm + has_gate
        )
        self._compiler_options = xla_compiler_options(
            self.config,
            overlap=plan.bucketed and is_tpu,
        )
        self._train_step = jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(specs, opt_specs, ef_specs, batch_spec,
                          batch_spec, P(), *state_specs),
                out_specs=(specs, opt_specs, ef_specs, *state_specs,
                           P(), P(), *counter_out),
            ),
            donate_argnums=(0, 1, 2, *range(6, 6 + len(state_specs))),
            compiler_options=self._compiler_options,
        )

        # device-resident multi-step path (same design as
        # ClassifierModel: dataset staged to HBM once, K steps ride
        # one lax.scan dispatch, batch indexing from a device step
        # counter — host dispatch latency amortizes over K)
        self._train_scan = None
        self._scan_k = 0
        if self.config.get("device_data_cache"):
            self._init_device_cache(step)
        self._val_step = jax.jit(
            jax.shard_map(
                val,
                mesh=mesh,
                in_specs=(specs, batch_spec, batch_spec),
                out_specs=(P(), P(), P()),
            ),
            compiler_options=self._compiler_options,
        )

        if self.params is None:
            # sharded init: jit + out_shardings lets GSPMD partition the
            # RNG and slice each param straight onto its mesh shards
            shardings = self._shardings(specs)
            opt_shardings = self._shardings(opt_specs)

            def init(key):
                params = self._init_full_params(key)
                # zero1: flat shards sliced onto the mesh by
                # out_shardings (the replicated m/v never materialize)
                return params, (
                    plan.init_opt_state() if plan.zero1
                    else self.optimizer.init(params)
                )

            self.params, self.opt_state = jax.jit(
                init, out_shardings=(shardings, opt_shardings),
                compiler_options=self._compiler_options,
            )(jax.random.PRNGKey(self.seed))
        if not plan.keeps_restored_ef(self.ef_state, self._restored):
            self.ef_state = plan.init_ef(mesh)
        if state_specs and self.net_state is None:
            self.net_state = jax.device_put(
                self._init_net_state(), NamedSharding(mesh, P())
            )
        self._batch_sharding = NamedSharding(mesh, batch_spec)
        self._init_feed(
            self._batch_sharding, dtypes=(jnp.int32, jnp.int32)
        )

    def _shardings(self, spec_tree) -> PyTree:
        """The mesh's ``NamedSharding`` of every spec of a tree."""
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    def _init_device_cache(self, shard_step) -> None:
        """Stage the whole token set into HBM and compile K-step
        scans over ``shard_step`` (the per-shard train body)."""
        k = int(self.config.get("steps_per_call", 2) or 0)
        get = getattr(self.data, "dataset_sequences", None)
        if k < 2 or get is None:
            import warnings

            warnings.warn(
                "device_data_cache requested but "
                + ("steps_per_call < 2" if get is not None else
                   "the data object does not expose "
                   "dataset_sequences()")
                + "; falling back to per-step host staging",
                stacklevel=3,
            )
            return
        gb = int(self.data.global_batch)
        b_loc = int(self.config.get("batch_size", 8))
        t_loc = self.seq_len // self.sp
        # (mesh data axis x b_loc == gb already asserted by
        # compile_iter_fns before this runs)
        specs, opt_specs = self._specs, self._opt_specs
        ef_specs = self.exchange.ef_specs
        rep = NamedSharding(self.mesh, P())

        d_size = self.mesh.shape[DATA_AXIS]
        has_exp = EXPERT_AXIS in self.mesh.shape
        counter_out = self._counter_out_specs

        state_specs = self._state_specs
        n_state = len(state_specs)

        def make_scan(length: int):
            def scan_steps(params, opt_state, ef, step, seqs, perm, lr,
                           *state):
                # flat DP replica index, expert-major — must match the
                # batch spec's (expert, data) shard ordering
                dme = lax.axis_index(DATA_AXIS)
                if has_exp:
                    dme = lax.axis_index(EXPERT_AXIS) * d_size + dme
                sme = lax.axis_index(SEQ_AXIS)
                nb = perm.shape[0] // gb

                def body(carry, _):
                    params, opt_state, ef, st, *state = carry
                    i = (st % nb).astype(jnp.int32)
                    idx = lax.dynamic_slice(
                        perm, (i * gb + dme * b_loc,), (b_loc,)
                    )
                    rows = seqs[idx]  # [b_loc, T+1]: this shard's rows
                    x = lax.dynamic_slice(
                        rows, (0, sme * t_loc), (b_loc, t_loc)
                    )
                    y = lax.dynamic_slice(
                        rows, (0, sme * t_loc + 1), (b_loc, t_loc)
                    )
                    params, opt_state, ef, *per_step = shard_step(
                        params, opt_state, ef, x, y, lr, *state
                    )
                    state, per_step = per_step[:n_state], per_step[n_state:]
                    return (
                        (params, opt_state, ef, st + 1, *state),
                        tuple(per_step),
                    )

                # per step: loss, err and the step's counters, if any
                (params, opt_state, ef, step, *state), per_step = lax.scan(
                    body, (params, opt_state, ef, step, *state), None,
                    length=length,
                )
                return params, opt_state, ef, step, *state, *per_step

            return jax.jit(
                jax.shard_map(
                    scan_steps,
                    mesh=self.mesh,
                    in_specs=(specs, opt_specs, ef_specs,
                              P(), P(), P(), P(), *state_specs),
                    out_specs=(specs, opt_specs, ef_specs, P(),
                               *state_specs, P(), P(), *counter_out),
                ),
                # the state comes back under the shardings it went in
                # with, so the second dispatch finds the first one's
                # trace: left to the compiler, equivalent specs come
                # back spelled otherwise (``P()`` for ``P(None)``) and
                # the jit traces and lowers the whole step once more,
                # on the host, while the chip waits (1 s at 32 layer
                # calls; PERF.md, PR 33)
                out_shardings=(
                    *map(self._shardings, (specs, opt_specs, ef_specs)),
                    rep, *map(self._shardings, state_specs),
                    *[rep] * (2 + len(counter_out)),
                ),
                donate_argnums=(0, 1, 2, 3, *range(7, 7 + n_state)),
                compiler_options=self._compiler_options,
            )

        self._train_scan = make_scan(k)
        # 1-step variant keeps train_iter on the SAME device-resident
        # batch indexing (advancing _step_dev) so per-step calls — an
        # epoch tail, a caller mixing paths — can't desync the device
        # index from the host position.  jit is lazy: never called,
        # never compiled.
        self._train_scan1 = make_scan(1)
        self._scan_k = k
        with setup_phase("stage_data"):
            self._seqs_dev = jax.device_put(
                jnp.asarray(get(), jnp.int32), rep
            )
        self._step_dev = jax.device_put(jnp.zeros((), jnp.int32), rep)
        self._perm_src = None
        self._perm_dev = None
        self._lr_val = None
        self._lr_dev = None

    def _scan_dispatch(self, scan_fn, count: int, k: int,
                       recorder: Recorder):
        with recorder.phase("load"):
            self._stage_cached_inputs()
        with recorder.phase("dispatch", first=count, k=k):
            (
                self.params,
                self.opt_state,
                self.ef_state,
                self._step_dev,
                *rest,
            ) = scan_fn(
                self.params, self.opt_state, self.ef_state,
                self._step_dev, self._seqs_dev, self._perm_dev,
                self._lr_dev, *self._state_args(),
            )
            losses, errs, *counters = self._take_state(rest)
        recorder.train_error(count, losses, errs)
        self._record_counters(recorder, counters)

    def _state_args(self) -> tuple:
        """``net_state`` as the step's last arguments: ``()`` for a
        model that has none."""
        return (self.net_state,) if self._state_specs else ()

    def _take_state(self, results):
        """A step's results after ``ef`` (or the scan's after its step
        counter): the new ``net_state`` kept, the rest given back."""
        if self._state_specs:
            self.net_state, *results = results
        return results

    def _record_counters(self, recorder: Recorder, counters) -> None:
        """Hand a step's counters (device values; read with the loss
        at the recorder's next fence) to the recorder: a MoE's
        routing (with its selection biases' sizes), then a looped
        decoder's exits, a mamba stack's scans, an attention gate's
        means."""
        counters = list(counters)
        if self.n_experts:
            recorder.moe_routing(
                counters.pop(0),
                # picks a layer CALL: a looped MoE has R * L of them
                picks=self.data.global_batch * self.seq_len * self.moe_top_k,
                **(dict(held=self.moe_experts_held)
                   if self.moe_experts_held is not None else {}),
                **(dict(bias_abs_max=counters.pop(0))
                   if self._state_specs else {}),
            )
        if self.ut_steps > 1:
            recorder.ut_exits(counters.pop(0))
        if self.has_mamba:
            recorder.ssm_scan(counters.pop(0))
        if self.attention_gate:
            recorder.attn_gate(counters.pop(0))

    def train_chunk(self, count: int, k: int, recorder: Recorder) -> None:
        if k == self._scan_k and self._train_scan is not None:
            self._scan_dispatch(self._train_scan, count, k, recorder)
            return
        for j in range(k):
            self.train_iter(count + j, recorder)

    def put_batch(self, batch):
        # one copy of the transfer discipline (data/HostStager): async
        # int32 puts onto the batch sharding, device ops labelled
        # host_load — shared by the train, val, and streaming-feed paths
        return self._stager.stage(batch)

    @property
    def train_step_fn(self):
        return self._train_step

    def train_step_cost_analysis(self):
        """XLA ``cost_analysis()`` of the jitted train step (same
        surface as ``ClassifierModel.train_step_cost_analysis``)."""
        x, y = self.put_batch(self.data.train_batch(0))
        return self._train_step.lower(
            self.params, self.opt_state, self.ef_state, x, y,
            jnp.float32(self.current_lr), *self._state_args(),
        ).compile().cost_analysis()

    def train_step_hlo_text(self):
        """Optimized-HLO text of the ACTIVE training executable — the
        K-step scan when compiled (what ``train_chunk`` actually
        dispatches), else the single step.  The step-phase profiler's
        scope-attribution source (``obs/profiler.py``): HLO
        instruction names are module-unique, so the text must come
        from the executable the profiled window runs.  Call after one
        warm ``train_chunk`` (the scan path stages lr/permutation
        lazily)."""
        from theanompi_tpu.utils.trace_comm import compiled_hlo_text

        if self._train_scan is not None and self._perm_dev is not None:
            lowered = self._train_scan.lower(
                self.params, self.opt_state, self.ef_state,
                self._step_dev, self._seqs_dev, self._perm_dev,
                self._lr_dev, *self._state_args(),
            )
        else:
            x, y = self.put_batch(self.data.train_batch(0))
            lowered = self._train_step.lower(
                self.params, self.opt_state, self.ef_state, x, y,
                jnp.float32(self.current_lr), *self._state_args(),
            )
        return compiled_hlo_text(lowered.compile())

    def train_iter(self, count: int, recorder: Recorder) -> None:
        if self._train_scan is not None:
            # device-resident single step: stays on the cached batch
            # indexing and advances _step_dev, so per-step calls (an
            # epoch tail, mixed callers) can't desync the device
            # index from the host position
            self._scan_dispatch(self._train_scan1, count, 1, recorder)
            return
        with recorder.phase("load"):
            if self._feed is not None:
                # pipelined feed: fetched + staged by the producer
                # thread under the previous step's compute
                x, y = self._feed.next(count)
            else:
                x, y = self.put_batch(self.data.train_batch(count))
        with recorder.phase("dispatch", first=count, k=1):
            (
                self.params,
                self.opt_state,
                self.ef_state,
                *rest,
            ) = self._train_step(
                self.params, self.opt_state, self.ef_state, x, y,
                jnp.float32(self.current_lr), *self._state_args(),
            )
            loss, err, *counters = self._take_state(rest)
        # device scalars, materialized lazily at the next print window
        # or epoch end (Recorder.flush) — no per-step host fence
        recorder.train_error(count, loss, err)
        self._record_counters(recorder, counters)

    def val_iter(self, count: int, recorder: Recorder):
        x, y = self.put_batch(self.data.val_batch(count))
        loss, err, err5 = self._val_step(self.params, x, y)
        return float(loss), float(err), float(err5)

    # -- serving (theanompi_tpu/serving) ----------------------------------

    def make_decoder(self, *, paged: bool = False, **kw):
        """KV-cache inference decoder over this model's (compiled,
        possibly checkpoint-restored) params — the train → checkpoint
        → serve path.  ``paged=True`` builds the block-table /
        prefix-cache decoder.  See
        ``theanompi_tpu.serving.LlamaDecoder`` /
        ``PagedLlamaDecoder``."""
        from theanompi_tpu.serving import LlamaDecoder, PagedLlamaDecoder

        cls = PagedLlamaDecoder if paged else LlamaDecoder
        return cls(self, **kw)

    # -- checkpoint (save/load/adjust_hyperp inherited from TMModel) ------

    def checkpoint_trees(self) -> dict[str, PyTree]:
        trees = {"params": self.params, "opt_state": self.opt_state}
        if getattr(self, "ef_state", None):
            trees["ef_state"] = self.ef_state
        if self.net_state is not None or self.moe_select_bias:
            # (a slot before the step is compiled too, so that a
            # checkpoint's selection bias is loaded, not dropped)
            trees["net_state"] = (
                self.net_state if self.net_state is not None
                else self._init_net_state()
            )
        return trees

    def _place_restored(self) -> None:
        if self.mesh is None:
            return

        def put(tree, spec_tree):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                tree, spec_tree,
            )

        self.params = put(self.params, self._specs)
        self.opt_state = put(self.opt_state, self._opt_specs)
        if getattr(self, "ef_state", None):
            self.ef_state = put(self.ef_state, self.exchange.ef_specs)
        if self.net_state is not None:
            self.net_state = put(self.net_state, self._state_specs[0])


# Llama-3-8B shape (the BASELINE stretch config), for reference and
# bench configs; smoke tests use much smaller dims.
LLAMA3_8B = dict(
    dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, vocab=128256, seq_len=8192,
)
