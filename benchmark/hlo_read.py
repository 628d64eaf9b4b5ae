"""The little of a compiled program's HLO text the benchmark reads:
which instructions are collectives and how many bytes they carry, and
which custom calls are which Pallas kernel.  Counts, not times."""

from __future__ import annotations

import re

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def collectives(hlo_text: str) -> list[dict]:
    """One entry per collective instruction: its name, its kind and
    the bytes of its result (for an all-reduce, the operand's).  An
    asynchronous pair counts once, at its ``-start``."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        for op in COLLECTIVE_OPS:
            at = re.search(rf"\s{op}(-start)?\(", rest)
            if at:
                shape = rest[:at.start()]
                if at.group(1):
                    # a -start returns (operands, results[, scratch]):
                    # the result is the second half of what it lists
                    shapes = _SHAPE.findall(shape)
                    half = shapes[len(shapes) // 2:] or shapes
                    nbytes = sum(
                        _shape_bytes(f"{d}[{dims}]") for d, dims in half
                    )
                else:
                    nbytes = _shape_bytes(shape)
                out.append({"name": name, "op": op, "bytes": nbytes})
                break
    return out


def custom_calls(hlo_text: str) -> dict[str, str]:
    """``{instruction name: the line}`` of every ``tpu_custom_call``
    (a Pallas kernel compiled by Mosaic)."""
    out = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = line
    return out
