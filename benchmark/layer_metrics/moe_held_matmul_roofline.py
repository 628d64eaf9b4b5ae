"""kernels of the expert layer under a held range (ops/grouped_matmul.py
with a prefix plan): the least time the chip could take for the grouped
products the trace holds — each call's operations and bytes
(``flops_moe.grouped_matmul_need``) at the rows the program's COUNTER
gives (``moe_rows_held`` of the last fenced step, the mean over the
expert layers: the need is linear in the rows and every layer call
runs the same products) and the experts held, against the peaks table
— over the device time of those calls and of the kernels that build
their tile plans.  A share's rows are the seed's router's, not the
file's, and stay within a few percent of ``picks * held / E`` over a
run (a share by itself holds its router, PERF.md, PR 37), so the last
step's stand for the traced ones."""
from .. import flops, flops_moe
from .. import trace_reduce as tr
from ._moe import grouped_kernels, kernel_spec
from ._scopes import moe_counters


def read(facts):
    spec = kernel_spec(facts)
    trace = facts.get("trace")
    counters = moe_counters(facts)
    if (not spec or not trace or not trace["devices"] or not counters
            or "moe_rows_held" not in counters):
        return None
    products, tables = grouped_kernels(facts)
    took, calls = tr.op_seconds_matching(trace, products.__contains__)
    took += tr.op_seconds_matching(trace, tables.__contains__)[0]
    if not calls:
        return None
    rows = counters["moe_rows_held"]
    shape = dict(spec["shape"], rows=sum(rows) / len(rows),
                 n_experts=counters["moe_experts_held"])
    ops, nbytes = flops_moe.grouped_matmul_need(**shape)
    least = calls * flops.least_seconds(ops, nbytes, facts["peaks"])[0]
    return 100.0 * least / took
