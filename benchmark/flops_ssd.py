"""Operations and bytes a chunked state-space scan (SSD, Mamba-2)
NEEDS, from its shapes (the rules of ``flops.py``: a multiply-
accumulate is two operations, recompute is never counted)."""

from __future__ import annotations


def ssd_flops_per_token(*, n_heads: int, head_dim: int, d_state: int,
                        n_groups: int, chunk: int) -> float:
    """Forward operations a token of ONE scan over chunks of ``chunk``
    positions.  Inside a chunk a token meets the ``chunk / 2`` tokens
    up to itself (the triangle's convention of
    ``flops.flash_call_need``: the diagonal's half pairs left out):
    ``C B^T`` once a group, ``2 (chunk / 2) d_state n_groups``, and
    the masked scores times ``dt x``, ``2 (chunk / 2) n_heads
    head_dim``; a token's part of its chunk's state, ``B^T (dt x)``,
    and what the carried state gives it, ``C S``: ``2 d_state n_heads
    head_dim`` each.  The decays, their cumulative sums and the carry
    between chunks are no matrix work and are not counted."""
    inner = n_heads * head_dim
    pairs = chunk / 2
    return (2.0 * pairs * d_state * n_groups + 2.0 * pairs * inner
            + 2 * 2.0 * d_state * inner)


def ssd_call_need(kind: str, *, batch: int, seq_len: int, n_heads: int,
                  head_dim: int, d_state: int, n_groups: int, chunk: int,
                  dtype_bytes: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` one scan needs over ``[batch,
    seq_len]`` tokens.  ``kind`` ``"fwd"``: the four products of
    ``ssd_flops_per_token``; ``"bwd"``: two gradient products for each
    of them, twice the forward.  Bytes are each operand and result
    crossing HBM once: forward ``x`` and ``y`` (``[.., n_heads,
    head_dim]``), ``B`` and ``C`` (``[.., n_groups, d_state]``) and
    ``dt`` (``[.., n_heads]`` float32); backward ``x``, ``dy`` and
    ``dx``, ``B``, ``C``, ``dB`` and ``dC``, ``dt`` and its gradient.
    ``A`` and ``D`` are a vector a head."""
    tokens = batch * seq_len
    per_token = ssd_flops_per_token(
        n_heads=n_heads, head_dim=head_dim, d_state=d_state,
        n_groups=n_groups, chunk=min(chunk, seq_len),
    )
    inner, gn = n_heads * head_dim, n_groups * d_state
    if kind == "fwd":
        nbytes = dtype_bytes * (2 * inner + 2 * gn) + 4 * n_heads
        return tokens * per_token, float(tokens * nbytes)
    assert kind == "bwd", kind
    nbytes = dtype_bytes * (3 * inner + 4 * gn) + 2 * 4 * n_heads
    return 2 * tokens * per_token, float(tokens * nbytes)
