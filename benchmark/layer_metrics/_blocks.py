"""What the readers of the step program's BLOCKS share (PR 35).

The program names the parts of its step with ``jax.named_scope``s:
``blk_embed``, ``blk_attn``, ``blk_ffn``, ``blk_head``
(``models/llama.py``), ``blk_conv``, ``blk_bn``, ``blk_pool``,
``blk_head`` (``ops/layers.py``, ``models/base.py``), ``opt_update``
(``parallel/plan.py``) and ``exchange_b<i>``
(``parallel/exchange.py``).  Block names are data here: any ``blk_``
label the compiled text holds is a block, so the next one needs a
scope in the program and no reader.  Every instruction of the step's
compiled text (``facts["hlo_text"]``) gets ONE block and ONE phase:

- block: the innermost block name of its ``op_name``.  A fusion that
  holds a ``convolution`` or a ``dot`` takes the block (and the
  phase) of that product, read from its fused computation's lines:
  XLA fuses a weight-gradient product with the optimizer update that
  consumes it, and by its root such a fusion would read as optimizer.
  A fusion whose own ``op_name`` names no block takes the block most
  of its fused instructions name.  A Pallas kernel
  (``tpu_custom_call``) whose line holds a
  ``kernels.<kernel>.hlo_part`` of the configuration takes that
  kernel's block (``KERNEL_BLOCKS``).  Anything else is ``other``;
- phase: ``replay`` where the ``op_name`` holds
  ``rematted_computation`` (a ``jax.checkpoint``'s recomputation),
  else ``bwd`` where it holds ``transpose(``, else ``fwd``;
- ``carries_opt``: the instruction, or an instruction of its fused
  computation, lies under ``opt_update``.  ``opt_s`` therefore
  OVERLAPS the blocks: a weight-gradient product fused with its Adam
  update counts once under its block and once there.  ``carries`` is
  the same for every block: the blocks a fusion's instructions name
  beside its own (ResNet's convolution fusions hold batch norm's
  reductions), for ``block_table.py``; no metric reads it.

Time is the trace's self time by instruction name
(``trace_reduce.self_seconds_by_name``) per step of the step
program's runs, as ``_ut.scope_seconds`` takes it.  Instructions that
only hold others (``while``, ``call``, ``conditional``) are left
out: their self time is the program waiting between two of its
instructions, not work of a block.

Every function returns ``None`` where there is nothing to read — no
trace, another program's trace (a rehearsal), or a compiled text
without a ``blk_`` name: a program from before PR 35, or an
executable that JAX's persistent compile cache kept from one (the
cache's key leaves ``named_scope``s out, so a cached executable keeps
the names it was compiled with) — and never raises for that.
"""

from __future__ import annotations

import functools
import re
from collections import Counter

from .. import hlo_read
from .. import trace_reduce as tr
from ._common import step_runs

PHASES = ("fwd", "replay", "bwd")
OTHER = "other"
OPT = "opt_update"
#: the block of a Pallas kernel the configuration names under
#: ``kernels.<kernel>.hlo_part``
KERNEL_BLOCKS = {"flash_attention": "blk_attn",
                 "moe_grouped_matmul": "blk_ffn"}

_BLOCK = re.compile(r"blk_\w+|opt_update|exchange_b\d+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_PRODUCT = re.compile(r"\s(?:convolution|dot)\(")


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "replay"
    return "bwd" if "transpose(" in op_name else "fwd"


def _block_of(op_name: str) -> str | None:
    found = _BLOCK.findall(op_name)
    return found[-1] if found else None


def _op_name(line: str) -> str:
    m = _OP_NAME.search(line)
    return m.group(1) if m else ""


def computations(text: str) -> dict[str, list[str]]:
    """``{computation name: its instruction lines}`` of a compiled
    text."""
    out: dict[str, list[str]] = {}
    lines: list[str] | None = None
    for line in text.splitlines():
        if lines is None:
            m = _COMPUTATION.match(line)
            if m:
                lines = out[m.group(1)] = []
        elif line.startswith("}"):
            lines = None
        else:
            lines.append(line)
    return out


@functools.lru_cache(maxsize=2)
def _instructions(text: str, kernel_parts: tuple) -> dict[str, dict]:
    comps = computations(text)
    out: dict[str, dict] = {}
    for lines in comps.values():
        for line in lines:
            m = hlo_read._INSTR.match(line)
            if not m:
                continue
            op_name = _op_name(line)
            decides = op_name
            block = _block_of(op_name)
            opt = OPT in op_name
            inside: Counter = Counter()
            called = _CALLS.search(line)
            if called and " fusion(" in line:
                fused = comps.get(called.group(1), ())
                names = [_op_name(ln) for ln in fused]
                inside.update(filter(None, map(_block_of, names)))
                products = [
                    n for n, ln in zip(names, fused)
                    if _PRODUCT.search(ln) and _block_of(n)
                ]
                if products:
                    decides = products[0]
                    block = _block_of(decides)
                elif block is None and inside:
                    block = inside.most_common(1)[0][0]
                    decides = next(n for n in names if _block_of(n) == block)
                opt = opt or any(OPT in n for n in names)
            if "tpu_custom_call" in line:
                for part, kernel_block in kernel_parts:
                    if part in line:
                        block = kernel_block
            out[m.group(1)] = {
                "block": block or OTHER, "phase": phase_of(decides),
                "carries_opt": opt, "op_name": op_name,
                "carries": tuple(sorted(set(inside) - {block})),
            }
    return out


def instruction_blocks(facts: dict) -> dict[str, dict] | None:
    """``{instruction name: {block, phase, carries_opt, op_name,
    carries}}`` of the step's compiled text, or ``None`` where the
    text names no ``blk_`` block at all."""
    text = facts.get("hlo_text") or ""
    if "blk_" not in text:
        return None
    kernels = facts["cell"]["config"].get("kernels", {})
    parts = tuple(
        (spec["hlo_part"], KERNEL_BLOCKS[kernel])
        for kernel, spec in sorted(kernels.items())
        if kernel in KERNEL_BLOCKS and "hlo_part" in spec
    )
    return _instructions(text, parts)


def block_seconds(facts: dict) -> dict | None:
    """The step program's device time by block and phase over the
    traced window::

        {"blocks": {block: {phase: seconds}},   # "other" among them
         "opt_s": seconds of instructions with ``carries_opt``,
         "carried": {block: seconds of instructions of ANOTHER block
                     that hold instructions of this one},
         "held_s": self seconds of the instructions that only hold
                   others (the program between two instructions),
         "others": {instruction name: seconds} of block "other",
         "program_s": seconds of the step program's runs,
         "steps": steps those runs hold}
    """
    runs = step_runs(facts)
    instructions = instruction_blocks(facts)
    if not runs or not instructions:
        return None
    blocks: dict[str, dict[str, float]] = {}
    others: dict[str, float] = {}
    carried: dict[str, float] = {}
    opt_s = held_s = 0.0
    for name, sec in tr.self_seconds_by_name(facts["trace"]).items():
        got = instructions.get(name)
        if got is None:         # another program's instruction
            continue
        if tr.is_container(name):
            held_s += sec
            continue
        by_phase = blocks.setdefault(
            got["block"], dict.fromkeys(PHASES, 0.0))
        by_phase[got["phase"]] += sec
        if got["carries_opt"]:
            opt_s += sec
        for block in got["carries"]:
            carried[block] = carried.get(block, 0.0) + sec
        if got["block"] == OTHER:
            others[name] = sec
    if not any(b != OTHER for b in blocks):
        return None         # another program's trace (a rehearsal)
    return {
        "blocks": blocks, "opt_s": opt_s, "carried": carried,
        "held_s": held_s, "others": others,
        "program_s": sum(e - s for _, s, e in runs) * tr.PS,
        "steps": len(runs) * facts["scan_k"],
    }


# -- what the metrics read ---------------------------------------------------


def block_ms(facts: dict, block: str) -> float | None:
    """Milliseconds a step in the block, all three phases."""
    got = block_seconds(facts)
    if got is None or block not in got["blocks"]:
        return None
    return 1e3 * sum(got["blocks"][block].values()) / got["steps"]


def phase_ms(facts: dict, phase: str) -> float | None:
    """Milliseconds a step in the phase, whatever the block."""
    got = block_seconds(facts)
    if got is None:
        return None
    return 1e3 * sum(b[phase] for b in got["blocks"].values()) / got["steps"]


def opt_ms(facts: dict) -> float | None:
    got = block_seconds(facts)
    return None if got is None else 1e3 * got["opt_s"] / got["steps"]


def named_share(facts: dict) -> float | None:
    """Of the step program's self time in instructions that do work,
    the share in instructions with a block."""
    got = block_seconds(facts)
    if got is None:
        return None
    total = sum(sum(b.values()) for b in got["blocks"].values())
    return 1.0 - sum(got["others"].values()) / total if total else None
