"""kernels (ops/attention.py under a window): the least time the chip
could take for the WINDOW flash-attention calls the trace holds — each
call's operations over the band's visible pairs and its bytes
(``flops_window.window_flash_call_need``) against the peaks table —
over the device time those calls took.  The calls are the
``tpu_custom_call`` instructions of the step's HLO whose line holds
the configuration's ``kernels.window_attention.hlo_part`` (the
program calls them under a name of their own, ``_flash_window_jit``;
the full layers' calls hold ``_flash_jit`` and are
``flash_attention_roofline``'s); what a call returns says which
kernel it is, as there.  ``None`` for a program without such calls
(every program from before PR 41)."""
from .. import flops, flops_window, hlo_read
from .. import trace_reduce as tr
from .flash_attention_roofline import kernel_kind


def read(facts):
    spec = facts["cell"]["config"].get("kernels", {}).get("window_attention")
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
        ops, nbytes = flops_window.window_flash_call_need(kind, **spec["shape"])
        least += calls * flops.least_seconds(ops, nbytes, facts["peaks"])[0]
        took += sec
    return 100.0 * least / took if took else None
