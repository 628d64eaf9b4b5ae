"""Operations and bytes the grouped expert products NEED, from their
shapes (the rules of ``flops.py``: a multiply-accumulate is two
operations, recompute and padding are never counted)."""

from __future__ import annotations


def grouped_matmul_need(*, rows: int, d_model: int, d_expert: int,
                        n_experts: int,
                        dtype_bytes: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE grouped product of a dropless
    expert layer over ``rows`` (token, pick) rows sorted by expert.

    Every product of the layer has the same need: gate and up are
    ``[rows, d_model] x [E, d_model, d_expert]``, down is ``[rows,
    d_expert] x [E, d_expert, d_model]``, their input-gradient
    products are the same with the weight transposed, and a
    weight-gradient product is ``[rows, a]^T [rows, b]`` per expert
    into ``[E, a, b]``.  Each multiplies ``rows x d_model x d_expert``
    times whatever the group sizes are, and each moves one ``[rows,
    d_model]``, one ``[rows, d_expert]`` and one ``[E, d_model,
    d_expert]`` array across HBM once."""
    ops = 2.0 * rows * d_model * d_expert
    nbytes = dtype_bytes * (
        rows * (d_model + d_expert) + n_experts * d_model * d_expert
    )
    return ops, float(nbytes)
