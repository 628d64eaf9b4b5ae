"""Operations and bytes a WINDOW flash-attention kernel call needs,
from its shapes (the rules of ``flops.py``: a multiply-accumulate is
two operations, recompute is never counted)."""

from __future__ import annotations


def window_flash_call_need(kind: str, *, batch: int, n_heads: int,
                           seq_len: int, head_dim: int, window: int,
                           dtype_bytes: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` one flash-attention kernel call needs
    under a causal mask with a window: a query sees itself and the
    ``window - 1`` keys before it.

    The products and tensors a ``kind`` (``"fwd"``, ``"dkv"``,
    ``"dq"``) are ``flops.flash_call_need``'s; the operations run over
    the band's ``T W - W^2 / 2`` visible pairs a head where that count
    takes the triangle's ``T^2 / 2`` (the same convention: the
    diagonal's half pairs are left out, so the count stays under the
    ``T W - W (W - 1) / 2`` pairs a kernel cannot avoid; at ``W >= T``
    it IS the triangle's).  Bytes are each operand and result crossing
    HBM once, as there: a window moves no tensor less."""
    products = {"fwd": 2, "dkv": 3, "dq": 1}[kind]
    tensors = {"fwd": 4, "dkv": 6, "dq": 5}[kind]
    w = min(window, seq_len)
    pairs = seq_len * w - w * w / 2
    ops = products * 2.0 * batch * n_heads * pairs * head_dim
    nbytes = tensors * batch * n_heads * seq_len * head_dim * dtype_bytes
    return ops, float(nbytes)
