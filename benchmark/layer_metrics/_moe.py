"""What the readers of the expert layer share (PR 26).

``parallel/moe.py`` puts four ``jax.named_scope``s around the parts
of ``moe_ffn`` — ``moe_route``, ``moe_dispatch``, ``moe_experts``,
``moe_combine`` — so every instruction of the layer, forward,
recomputed and backward, names one in its ``op_name``.  The trace
names an op event by its instruction, and the step's compiled text
(``facts["hlo_text"]``) maps an instruction to its ``op_name``; a
fusion carries its root's.  The grouped products are the exception:
the v5e compiler rewrites ``lax.ragged_dot`` into Mosaic kernels
(``tpu_custom_call``) that lose the scope, so they are found as the
flash kernels are, by the configuration's
``kernels.moe_grouped_matmul.hlo_part`` in their line, and counted
under ``moe_experts``.

Every function returns ``None`` where there is nothing to read — a
dense model, a program from before PR 26, no trace — and never
raises for that.
"""

from __future__ import annotations

import re

from .. import hlo_read
from .. import trace_reduce as tr
from ._common import step_runs
from .flash_attention_roofline import _RESULT     # a custom call's result

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
_SCOPE = re.compile("|".join(SCOPES))
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def kernel_spec(facts: dict) -> dict | None:
    return (facts["cell"]["config"].get("kernels", {})
            .get("moe_grouped_matmul"))


def grouped_kernels(facts: dict) -> tuple[set, set]:
    """``(product kernels, their metadata kernels)``: instruction
    names of the step's grouped-product custom calls.  The metadata
    kernel (tile -> group tables from the group sizes) returns a tuple
    of ``s32``; a product returns its one array."""
    spec = kernel_spec(facts)
    products: set = set()
    tables: set = set()
    if not spec:
        return products, tables
    calls = hlo_read.custom_calls(facts.get("hlo_text", ""))
    for name, line in calls.items():
        if spec["hlo_part"] not in line:
            continue
        m = _RESULT.search(line)
        is_table = bool(m) and m.group(1).startswith("(s32")
        (tables if is_table else products).add(name)
    return products, tables


def instruction_scopes(facts: dict) -> dict[str, str]:
    """``{instruction name: moe scope}`` of the step's compiled text:
    the innermost ``moe_*`` scope of the instruction's ``op_name``,
    and ``moe_experts`` for the grouped-product kernels."""
    out: dict[str, str] = {}
    for line in facts.get("hlo_text", "").splitlines():
        m = hlo_read._INSTR.match(line)
        op = _OP_NAME.search(line)
        if not m or not op:
            continue
        found = _SCOPE.findall(op.group(1))
        if found:
            out[m.group(1)] = found[-1]
    products, tables = grouped_kernels(facts)
    out.update(dict.fromkeys(products | tables, "moe_experts"))
    return out


def scope_seconds(facts: dict) -> tuple[dict[str, float], float, int] | None:
    """``({scope: device seconds}, seconds of the step program's runs,
    steps those runs hold)`` over the traced window; self time, so a
    ``while`` that holds the layer counts nothing itself."""
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
