#!/usr/bin/env python3
"""Spread of sets of runs: `python3 benchmarks/spread.py setA.jsonl setB.jsonl`.

Each file holds one set: the result lines of several runs of one cell
(other lines are skipped). For every metric prints each set's median and
spread (distance between the first and third quartile, as
`statistics.quantiles(values, n=4)` gives them, over the median), the
wider of the spreads, and five times it: the bound the contract asks for.
A run's first line in a set may be a cold one: `--skip-first` leaves it out.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.harness.window import iqr_spread as spread  # noqa: E402


def load(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"metrics"' in line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("sets", nargs="+")
    ap.add_argument("--skip-first", action="store_true")
    args = ap.parse_args()
    sets = [load(p)[1 if args.skip_first else 0:] for p in args.sets]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        row, widest = [], 0.0
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            if len(vals) < 2:
                row.append("n<2")
                continue
            sp = spread(vals)
            widest = max(widest, sp)
            row.append(f"median {statistics.median(vals):.6g} spread "
                       f"{100 * sp:.3f}% n={len(vals)} "
                       f"[{min(vals):.6g}..{max(vals):.6g}]")
        print(f"{name}: " + " | ".join(row)
              + f" | widest {100 * widest:.3f}% x5 = {500 * widest:.2f}%")
    bad = [r for s in sets for r in s if not r["correct"] or r["failed"]]
    print(f"runs: {[len(s) for s in sets]}, not correct or with failures: "
          f"{len(bad)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
