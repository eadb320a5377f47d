"""Spread of a cell's metrics over two sets of runs, for setting bounds.

  python3 benchmarks/chip/spread.py <set_a.jsonl> <set_b.jsonl>

Each file holds the last stdout line of each run of one set (the same
seeds in both).  For every metric: each set's median and the distance
between its quartiles (``statistics.quantiles(values, n=4)``) as a share
of the median, and five times the wider of the two, the bound that share
suggests (never under 1%).
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def load(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def main(argv: list[str]) -> int:
    sets = [load(p) for p in argv]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        cols = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            cols.append((statistics.median(vals), spread(vals), vals))
        widest = max(c[1] for c in cols)
        print(json.dumps({
            "metric": name,
            "medians": [c[0] for c in cols],
            "spreads": [c[1] for c in cols],
            "bound_5x": max(0.01, 5 * widest),
            "values": [c[2] for c in cols]}))
    for i, s in enumerate(sets):
        print(json.dumps({"set": i, "correct": [r["correct"] for r in s],
                          "gaps": [r["checks"]["widest_gap"]["value"]
                                   for r in s]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
