"""kernels of the expert layer (parallel/moe.py: ``lax.ragged_dot``,
which the v5e compiler turns into Mosaic grouped-matmul kernels): the
least time the chip could take for the grouped products the trace
holds — each call's operations and bytes from its shapes
(``flops_moe.grouped_matmul_need``: the same for gate, up, down and
their six backward products) against the peaks table — over the
device time of those calls and of the small kernels that build their
tile tables."""
from .. import flops, flops_moe
from .. import trace_reduce as tr
from ._moe import grouped_kernels, kernel_spec


def read(facts):
    spec = kernel_spec(facts)
    trace = facts.get("trace")
    if not spec or not trace or not trace["devices"]:
        return None
    products, tables = grouped_kernels(facts)
    took, calls = tr.op_seconds_matching(trace, products.__contains__)
    took += tr.op_seconds_matching(trace, tables.__contains__)[0]
    if not calls:
        return None
    ops, nbytes = flops_moe.grouped_matmul_need(**spec["shape"])
    least = calls * flops.least_seconds(ops, nbytes, facts["peaks"])[0]
    return 100.0 * least / took
