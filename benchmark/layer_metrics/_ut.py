"""What the readers of the looped decoder share (PR 33).

``models/llama.py`` puts two ``jax.named_scope``s into the step of a
looped decoder (``ut_steps`` R > 1): ``ut_stack`` around the R passes
over the one stack of layers (each pass's closing norm included) and
``ut_exit`` around the exits' heads, losses, gate and exit
distribution.  Every instruction under one, forward, replayed and
backward, names it in its ``op_name``; the trace names an op event by
its instruction and the step's compiled text (``facts["hlo_text"]``)
maps an instruction to its ``op_name``; a fusion carries its root's.
The flash kernels are counted under ``ut_stack`` whatever their line
says, found as ``flash_attention_roofline`` finds them, by the
configuration's ``kernels.flash_attention.hlo_part``: every attention
call of such a model is a layer call of the stack.

Every function returns ``None`` where there is nothing to read — a
plain decoder, a program from before PR 33, no trace — and never
raises for that.
"""

from __future__ import annotations

import re

from .. import hlo_read
from .. import trace_reduce as tr
from ._common import step_runs

SCOPES = ("ut_stack", "ut_exit")
_SCOPE = re.compile("|".join(SCOPES))
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(facts: dict) -> dict[str, str]:
    """``{instruction name: scope}`` of the step's compiled text: the
    innermost of the two scopes in the instruction's ``op_name``, and
    ``ut_stack`` for the flash kernels of a text that has the scope."""
    text = facts.get("hlo_text", "")
    out: dict[str, str] = {}
    for line in text.splitlines():
        m = hlo_read._INSTR.match(line)
        op = _OP_NAME.search(line)
        if not m or not op:
            continue
        found = _SCOPE.findall(op.group(1))
        if found:
            out[m.group(1)] = found[-1]
    spec = facts["cell"]["config"].get("kernels", {}).get("flash_attention")
    if out and spec:
        out.update({
            name: "ut_stack"
            for name, line in hlo_read.custom_calls(text).items()
            if spec["hlo_part"] in line
        })
    return out


def scope_seconds(facts: dict) -> tuple[dict[str, float], float, int] | None:
    """``({scope: device seconds}, seconds of the step program's runs,
    steps those runs hold)`` over the traced window; self time, so a
    ``while`` that holds the passes or the exits counts nothing
    itself."""
    runs = step_runs(facts)
    scopes = instruction_scopes(facts)
    if not runs or not scopes:
        return None
    total: dict[str, float] = dict.fromkeys(SCOPES, 0.0)
    for name, sec in tr.self_seconds_by_name(facts["trace"]).items():
        scope = scopes.get(name)
        if scope is not None:
            total[scope] += sec
    if not any(total.values()):
        return None         # another program's trace (a rehearsal)
    program_s = sum(e - s for _, s, e in runs) * tr.PS
    return total, program_s, len(runs) * facts["scan_k"]
