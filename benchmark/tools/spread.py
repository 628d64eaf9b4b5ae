"""The spread of a cell's runs, as the driver reads it: for each
metric the median and the distance between the quartiles over the
median, per set of runs, and the wider of the sets' spreads.

    python3 -m benchmark.tools.spread <file of result lines> [<runs per set>]

The file holds one run's last line per line (other lines are
skipped), in the order the runs were made; the first ``runs per set``
are set 1, the next set 2.  A bound is about five times the widest
spread over the cells and never under 1 %.
"""

from __future__ import annotations

import json
import statistics
import sys


def quartile_spread(values: list[float]) -> tuple[float, float]:
    """``(median, (q3 - q1) / median)`` with inclusive quartiles."""
    med = statistics.median(values)
    if len(values) < 2:
        return med, 0.0
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return med, (q3 - q1) / med


def main(argv: list[str]) -> int:
    lines = []
    with open(argv[0]) as f:
        for raw in f:
            raw = raw.strip()
            if raw.startswith("{") and '"metrics"' in raw:
                lines.append(json.loads(raw))
    per_set = int(argv[1]) if len(argv) > 1 else len(lines)
    sets = [lines[i:i + per_set] for i in range(0, len(lines), per_set)]
    names = sorted({k for line in lines for k in line["metrics"]})
    print(json.dumps({
        "runs": len(lines), "sets": [len(s) for s in sets],
        "correct": all(line["correct"] for line in lines),
        "failed": [line["failed"] for line in lines],
        "attempted": [line["attempted"] for line in lines],
        "memory_peak_gib": max(
            line["device"]["memory_peak_bytes"] for line in lines) / 2 ** 30,
    }))
    for name in names:
        row = {"metric": name, "sets": []}
        for s in sets:
            values = [line["metrics"][name]["value"] for line in s
                      if name in line["metrics"]]
            med, spread = quartile_spread(values)
            row["sets"].append({"median": med, "spread": spread,
                                "values": values})
        row["widest_spread"] = max(x["spread"] for x in row["sets"])
        if len(row["sets"]) > 1:
            a, b = row["sets"][0]["median"], row["sets"][1]["median"]
            row["set2_over_set1"] = b / a - 1
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
