"""Find the knee of an open-loop cell once, on the chip: one process,
one engine, one window per rate.

    python3 -m benchmark.tools.knee_sweep <cell> <seconds> <rate> [<rate> ...]

Prints one JSON line per rate: the end-to-end metrics, the medians,
what failed, and whether a backlog grew (queue depth over the window's
decode steps, seconds needed to drain).  The knee is the highest rate
at which the traffic file's ``limits`` hold with no growing backlog;
the cell then offers 0.8 of it, written into the traffic file as a
number with this table in PERF.md.  Not part of a run of the
benchmark: nothing here is read by ``run.py``.
"""

from __future__ import annotations

import json
import math
import sys
import time


def pick_knee(lines: list[dict], ttft_limit_ms: float) -> dict:
    """The rule, applied to a sweep's lines: the TPOT limit is 1.5 x
    the median ``tpot_s`` at the lowest rate, rounded up to 5 ms; the
    knee is the highest rate up to which every rate kept both p90
    limits, lost no request and grew no backlog (mean queue depth of
    the window's second half at most one above the first's)."""
    lines = sorted(lines, key=lambda l: l["rate_rps"])
    tpot_limit = 5 * math.ceil(1.5 * lines[0]["tpot_ms_median"] / 5)
    knee = None
    for line in lines:
        held = (
            line["failed"] == 0
            and line["ttft_p90_ms"] <= ttft_limit_ms
            and line["tpot_p90_ms"] <= tpot_limit
            and line["queue_depth_mean_second_half"]
            <= line["queue_depth_mean_first_half"] + 1.0
        )
        if not held:
            break
        knee = line["rate_rps"]
    return {"knee_rps": knee, "ttft_limit_ms": ttft_limit_ms,
            "tpot_limit_ms": tpot_limit,
            "rate_rps": None if knee is None else round(0.8 * knee, 2)}


def main(argv: list[str]) -> int:
    from .. import run as harness
    from ..drivers import open_loop

    cell = harness.load_cell(argv[0])
    seconds = float(argv[1])
    devices, stamp = harness.take_devices(cell, rehearsal=False)

    from theanompi_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ctx = {
        "cell": cell, "seed": 0, "seconds": seconds, "devices": devices,
        "meter": harness.CompileMeter(),
        "tracing": harness.Tracing(harness.ROOT / ".bench_scratch" / "sweep",
                                   False),
        "log": lambda **kw: print(json.dumps(kw), flush=True),
    }
    model, decoder, engine, recorder = open_loop.build(ctx)
    try:
        for i, rate in enumerate(float(r) for r in argv[2:]):
            traffic = dict(cell["traffic"], rate_rps=rate)
            win = open_loop.window(ctx, engine, traffic, seed=100 + i,
                                   seconds=seconds, vocab=model.vocab)
            t0, t1 = win["t0_wall"], win["t0_wall"] + seconds
            steps = [s for s in recorder.steps if t0 <= s["t"] <= t1]
            half = len(steps) // 2
            depth = [s["queue_depth"] for s in steps]
            rows = win["rows"]
            line = {
                "rate_rps": rate, "device": stamp,
                "attempted": win["attempted"], "failed": win["failed"],
                **open_loop.end_to_end(win),
                "ttft_ms_median": 1e3 * open_loop.percentile(
                    [r["ttft_s"] for r in rows], 50),
                "tpot_ms_median": 1e3 * open_loop.percentile(
                    [r["tpot_s"] for r in rows], 50),
                "queue_depth_mean_first_half": (
                    sum(depth[:half]) / max(1, half)),
                "queue_depth_mean_second_half": (
                    sum(depth[half:]) / max(1, len(depth) - half)),
                "queue_depth_max": max(depth, default=0),
                "slots_mean": (sum(s["active_slots"] for s in steps)
                               / max(1, len(steps))),
                "decode_steps": len(steps),
                "drained_s": win["drained_s"],
                "generator_lateness": win["lateness"],
                "compiles_in_window": win["compiles_in_window"],
            }
            print(json.dumps(line), flush=True)
            while engine.queue_depth() or engine.active_slots():
                time.sleep(0.2)     # the next rate starts from empty
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
