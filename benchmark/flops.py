"""Operations and bytes a call NEEDS, computed from its shapes.

Nothing here asks XLA: ``cost_analysis`` of a step that rematerialises
its layers counts the recomputed operations too, and a utilization
built on it flatters the program (ROADMAP S3).  A multiply-accumulate
is two operations.  Recompute is never counted.
"""

from __future__ import annotations

# (blocks, bottleneck width) per stage; output width is 4x
RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def resnet50_forward_macs(crop: int = 224, n_classes: int = 1000) -> int:
    """Multiply-accumulates of one ResNet-50 v1.5 forward pass on one
    ``crop x crop`` image: convolutions and the classifier (BN, ReLU
    and pooling are not matrix work).  v1.5 strides the 3x3, so the
    first 1x1 of a strided block still runs at the input resolution.
    4.09 GMAC at 224 (hand count in ``tests/test_flops.py``)."""
    h = (crop + 2 * 3 - 7) // 2 + 1          # 7x7/2 stem, pad 3
    macs = h * h * 64 * 7 * 7 * 3
    h = -(-h // 2)                           # 3x3/2 max pool, SAME
    c_in = 64
    for stage, (blocks, ch) in enumerate(RESNET50_STAGES):
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            h_out = h // stride
            macs += h * h * c_in * ch                 # 1x1
            macs += h_out * h_out * ch * ch * 9       # 3x3 (strided)
            macs += h_out * h_out * ch * 4 * ch       # 1x1
            if stride != 1 or c_in != 4 * ch:
                macs += h_out * h_out * c_in * 4 * ch  # projection
            h, c_in = h_out, 4 * ch
    return macs + c_in * n_classes


def resnet50_train_flops_per_image(crop: int = 224,
                                   n_classes: int = 1000) -> float:
    """Forward, and a backward of twice the forward (one product for
    the input gradient, one for the weight gradient)."""
    return 3 * 2.0 * resnet50_forward_macs(crop, n_classes)


def decoder_matmul_params(*, dim: int, n_layers: int, n_heads: int,
                          n_kv_heads: int, head_dim: int, ffn_dim: int,
                          vocab: int) -> int:
    """Parameters a token is multiplied with in a dense decoder: the
    four attention projections, the three SwiGLU matrices and the
    untied output head.  The embedding is a lookup, not a product."""
    attn = dim * n_heads * head_dim * 2 + dim * n_kv_heads * head_dim * 2
    return n_layers * (attn + 3 * dim * ffn_dim) + dim * vocab


def attention_flops_per_token(*, seq_len: int, n_layers: int, n_heads: int,
                              head_dim: int, causal: bool = True) -> float:
    """Forward score and value products of one token against a
    sequence of ``seq_len`` (half of them under a causal mask)."""
    full = 2 * 2.0 * seq_len * n_heads * head_dim * n_layers
    return full / 2 if causal else full


def decoder_train_flops_per_token(*, seq_len: int, **widths) -> float:
    """Forward and backward (3x the forward) of the dense decoder per
    token at full sequences of ``seq_len``.  Adam, norms, RoPE and the
    softmax are not counted: they are not matrix work."""
    fwd = 2.0 * decoder_matmul_params(**widths) + attention_flops_per_token(
        seq_len=seq_len, n_layers=widths["n_layers"],
        n_heads=widths["n_heads"], head_dim=widths["head_dim"],
    )
    return 3 * fwd


def decoder_weight_bytes(bytes_per_param: int, **widths) -> int:
    """Bytes one decode step must read of the weights (every matrix
    once; the embedding rows it looks up are negligible)."""
    return bytes_per_param * decoder_matmul_params(**widths)


def flash_call_need(kind: str, *, batch: int, n_heads: int, seq_len: int,
                    head_dim: int, dtype_bytes: int = 2,
                    causal: bool = True) -> tuple[float, float]:
    """``(operations, bytes)`` one flash-attention kernel call needs.

    ``kind`` is ``"fwd"`` (scores and values: 2 products), ``"dkv"``
    (dV, dP and dK: 3) or ``"dq"`` (dQ: 1); recomputing the scores in
    the backward kernels is the algorithm's price for not storing
    them and is not counted.  Bytes are each operand and result
    crossing HBM once: q, k, v, o (+ do, dq/dk/dv in the backward)."""
    products = {"fwd": 2, "dkv": 3, "dq": 1}[kind]
    tensors = {"fwd": 4, "dkv": 6, "dq": 5}[kind]
    ops = products * 2.0 * batch * n_heads * seq_len * seq_len * head_dim
    if causal:
        ops /= 2
    nbytes = tensors * batch * n_heads * seq_len * head_dim * dtype_bytes
    return ops, float(nbytes)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the least time the chip could take, and which of
    the two peaks bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
