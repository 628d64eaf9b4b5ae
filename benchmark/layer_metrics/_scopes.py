"""Device time of the step program under ONE ``jax.named_scope`` of
the program's, for the scopes that are not blocks (PR 37):
``mla_proj`` (latent attention's projections, inside ``blk_attn``),
``moe_shared`` (the shared expert, inside ``blk_ffn``) and ``mtp``
(the multi-token-prediction module, with blocks of its own inside).

The join is ``_blocks.py``'s: an instruction of the step's compiled
text (``facts["hlo_text"]``) is under the scope when its ``op_name``
holds it — for a fusion that holds a ``convolution`` or a ``dot``,
when that product's does (XLA fuses a weight-gradient product with
the optimizer update that consumes it, and by its root such a fusion
would read as optimizer); a Pallas kernel carries its own
``op_name``.  Time is the trace's self time by instruction name over
the step program's runs; instructions that only hold others are left
out.

Every function returns ``None`` where there is nothing to read — no
trace, another program's trace (a rehearsal), a program without the
scope (every program from before PR 37) — and never raises for that.
"""

from __future__ import annotations

import functools

from .. import hlo_read
from .. import trace_reduce as tr
from . import _blocks
from ._common import step_runs


@functools.lru_cache(maxsize=8)
def _under(text: str, scope: str) -> frozenset:
    """Names of the instructions of ``text`` under ``scope``."""
    comps = _blocks.computations(text)
    out = set()
    for lines in comps.values():
        for line in lines:
            m = hlo_read._INSTR.match(line)
            if not m:
                continue
            decides = _blocks._op_name(line)
            called = _blocks._CALLS.search(line)
            if called and " fusion(" in line:
                products = [
                    _blocks._op_name(ln)
                    for ln in comps.get(called.group(1), ())
                    if _blocks._PRODUCT.search(ln)
                ]
                if products:
                    decides = products[0]
            if scope in decides.replace("(", "/").replace(")", "/").split("/"):
                out.add(m.group(1))
    return frozenset(out)


def scope_seconds(facts: dict, scope: str) -> tuple[float, float, int] | None:
    """``(device seconds under the scope, seconds of the step
    program's runs, steps those runs hold)`` over the traced window."""
    text = facts.get("hlo_text") or ""
    runs = step_runs(facts)
    if not runs or scope not in text:
        return None
    names = _under(text, scope)
    total = sum(
        sec for name, sec in tr.self_seconds_by_name(facts["trace"]).items()
        if name in names and not tr.is_container(name)
    )
    if not total:
        return None         # another program's trace (a rehearsal)
    program_s = sum(e - s for _, s, e in runs) * tr.PS
    return total, program_s, len(runs) * facts["scan_k"]


def scope_ms(facts: dict, scope: str) -> float | None:
    """Milliseconds a step under the scope, forward, replay and
    backward."""
    got = scope_seconds(facts, scope)
    return None if got is None else 1e3 * got[0] / got[2]


def moe_counters(facts: dict) -> dict | None:
    """The routing counters of the run's last fenced step: a recorded
    trace's ``"moe_counters"``, else the program's own
    (``theanompi_tpu.obs.last_moe_counters``)."""
    if "scan_k" not in facts:       # not a training run's facts
        return None
    counters = (facts.get("trace") or {}).get("moe_counters")
    if counters is None:
        try:
            from theanompi_tpu.obs import last_moe_counters
        except ImportError:         # a program from before PR 26
            return None
        counters = last_moe_counters()
    return counters or None
