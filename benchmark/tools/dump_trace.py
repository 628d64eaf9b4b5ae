"""Look at a trace by hand before trusting code against it, and keep a
small piece of it as the recorded fixture.

    python3 -m benchmark.tools.dump_trace <trace dir> [<out.json.gz> [<max seconds> [<slice from>]]]

Prints every plane and line of the ``.xplane.pb`` with its event count
and first events, then the neutral form's reductions; with an output
path, writes the neutral form (``trace_reduce``) of the first ``max
seconds`` (default 0.25) that hold whole program runs, gzipped; with
``slice from`` (seconds after the first device event) it writes the
``max seconds`` from there instead, cutting the program run it falls
into — how a piece of one long multi-chip step becomes a fixture.
"""

from __future__ import annotations

import gzip
import json
import sys


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    from .. import trace_reduce as tr

    path = tr.find_xplane(argv[0])
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            print(f"  LINE {line.name!r} events={len(events)}")
            if plane.name.startswith("/host:") and not any(
                e.name.startswith(tr.HOST_SPAN_PREFIX) for e in events
            ):
                continue
            for e in events[:6]:
                stats = {k: str(v)[:80] for k, v in list(e.stats)[:8]}
                print(f"    {e.name[:90]!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f} {stats}")
    trace = tr.load_xplane(path)
    summary = tr.summarize(trace)
    print(json.dumps({k: summary[k] for k in ("busy_s", "window_s")}))
    print("device_ops", json.dumps(summary["device_ops"]))
    print("idle_gaps", json.dumps(summary["idle_gaps"]))
    runs = tr.module_runs(trace)
    names = sorted({r[0] for r in runs})
    print("modules", json.dumps(names), "runs", len(runs))
    print("collective, exposed s:", tr.exposed_collective_seconds(trace))
    if len(argv) > 1:
        limit_ps = int(float(argv[2] if len(argv) > 2 else 0.25) / tr.PS)
        w0, _ = tr.window_ps(trace)
        if len(argv) > 3:
            start = w0 + int(float(argv[3]) / tr.PS)
            piece = tr.clip(trace, start, start + limit_ps, cut_programs=True)
        else:
            ends = [r[2] for r in runs if r[2] - w0 <= limit_ps]
            end = max(ends) if ends else w0 + limit_ps
            piece = tr.clip(trace, w0, end)
        with gzip.open(argv[1], "wt") as f:
            json.dump(piece, f, separators=(",", ":"))
        print("wrote", argv[1], "events",
              sum(len(d["ops"]) for d in piece["devices"].values()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
