"""kernels (ops/attention.py): the least time the chip could take for
the flash-attention calls the trace holds — each call's operations and
bytes from its shapes (``flops.flash_call_need``) against the peaks
table — over the device time those calls took.  The calls are the
``tpu_custom_call`` instructions of the step's HLO whose line holds
the configuration's ``kernels.flash_attention.hlo_part``; what a call
returns says which kernel it is: (out, logsumexp) is the forward,
(dk, dv) and dq the two backward kernels."""
import re

from .. import flops, hlo_read
from .. import trace_reduce as tr

_RESULT = re.compile(r"=\s*(\(.*?\)|\S+)\s+custom-call\(")


def kernel_kind(hlo_line: str) -> str | None:
    m = _RESULT.search(hlo_line)
    if not m:
        return None
    result = m.group(1)
    if not result.startswith("("):
        return "dq"
    return "fwd" if "f32[" in result else "dkv"


def read(facts):
    spec = facts["cell"]["config"].get("kernels", {}).get("flash_attention")
    trace = facts.get("trace")
    if not spec or not trace or not trace["devices"]:
        return None
    kinds = {
        name: kernel_kind(line)
        for name, line in hlo_read.custom_calls(facts.get("hlo_text", "")).items()
        if spec["hlo_part"] in line
    }
    least = took = 0.0
    for kind in ("fwd", "dkv", "dq"):
        names = {n for n, k in kinds.items() if k == kind}
        sec, calls = tr.op_seconds_matching(trace, names.__contains__)
        ops, nbytes = flops.flash_call_need(kind, **spec["shape"])
        least += calls * flops.least_seconds(ops, nbytes, facts["peaks"])[0]
        took += sec
    return 100.0 * least / took if took else None
