"""Where a traced run's step went, by the program's own block names:
the whole table behind the ``*_block_ms`` metrics
(``layer_metrics/_blocks.py``), from what a ``--trace 1`` run leaves
in its scratch directory (``step_hlo.txt`` and the profiler's
``.xplane.pb``).

    python3 -m benchmark.tools.block_table .bench_scratch/<cell> [--cell NAME]

Needs no chip: it reads files.  Prints one JSON object a line:

- ``names``: the block names the compiled text holds.  None at all
  means an executable from before PR 35 — or one that JAX's
  persistent compile cache kept from then: the cache's key leaves
  ``named_scope``s out, so run against an empty cache directory;
- ``table``: ``{block: {fwd, replay, bwd}}`` in ms a step, ``other``
  among the blocks, with ``opt_update_ms`` (overlaps the blocks),
  ``carried_ms`` (``{block: ms in instructions counted under ANOTHER
  block that hold instructions of this one}``: what fusion hides from
  a block's row), ``held_ms`` (self time of ``while`` / ``call``: the
  program between two instructions), ``step_device_ms`` and
  ``block_named_share``;
- ``other``: the ten dearest instructions without a block, ms a step,
  with their ``op_name`` and the start of their text.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .. import trace_reduce as tr
from ..layer_metrics import _blocks
from ..run import load_cell, program_knobs


def facts_of(scratch: Path, cell_name: str) -> dict:
    """The facts the block readers need, from a scratch directory."""
    cell = load_cell(cell_name)
    return {
        "cell": cell,
        "hlo_text": (scratch / "step_hlo.txt").read_text(),
        "scan_k": int(program_knobs(cell["config"])["steps_per_call"]),
        "trace": tr.load_xplane(tr.find_xplane(str(scratch / "trace"))),
    }


def report(facts: dict) -> list[dict]:
    instructions = _blocks.instruction_blocks(facts) or {}
    names = sorted({
        i["block"] for i in instructions.values()
        if i["block"] != _blocks.OTHER
    })
    out: list[dict] = [{"names": names}]
    got = _blocks.block_seconds(facts)
    if got is None:
        return out
    per_step = 1e3 / got["steps"]
    out.append({
        "table": {
            block: {p: round(s * per_step, 3) for p, s in by_phase.items()}
            for block, by_phase in sorted(got["blocks"].items())
        },
        "opt_update_ms": got["opt_s"] * per_step,
        "carried_ms": {b: round(s * per_step, 3)
                       for b, s in sorted(got["carried"].items())},
        "held_ms": got["held_s"] * per_step,
        "step_device_ms": got["program_s"] * per_step,
        "block_named_share": _blocks.named_share(facts),
    })
    text = facts["trace"].get("text", {})
    dearest = sorted(got["others"].items(), key=lambda kv: -kv[1])[:10]
    out.append({"other": [
        {"instruction": name, "ms": sec * per_step,
         "op_name": instructions[name]["op_name"],
         "text": text.get(name, "")[:160]}
        for name, sec in dearest
    ]})
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("scratch", type=Path)
    ap.add_argument("--cell", help="default: the directory's name")
    args = ap.parse_args(argv)
    facts = facts_of(args.scratch, args.cell or args.scratch.resolve().name)
    for line in report(facts):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
