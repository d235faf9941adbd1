#!/usr/bin/env python3
"""Spread of each metric over runs of one cell, as the bounds are set:
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) over the median.

    python3 benchmarks/chip/spread.py a1.out a2.out ... -- b1.out b2.out ...

Each file's last line is a run's result line; ``--`` splits two sets
run on the same seeds. Prints, per metric and set, the median, the
spread and the values; the spread of the set's runs without the one
farthest from its median; and the wider of the sets' spreads, of which
a bound is about five times.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    """``values`` without the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def last_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(last_line(a))
    sets.append(cur)
    names = {n for runs in sets for r in runs for n in r["metrics"]}
    for i, runs in enumerate(sets):
        print(f"set {i}: correct {[r['correct'] for r in runs]} "
              f"attempted {[r['attempted'] for r in runs]} "
              f"failed {[r['failed'] for r in runs]}")
    for name in sorted(names):
        widest = 0.0
        for i, runs in enumerate(sets):
            v = [r["metrics"][name]["value"] for r in runs
                 if name in r["metrics"]]
            if len(v) < 3:
                continue
            s = spread(v)
            widest = max(widest, s)
            print(f"{name} set {i}: median {statistics.median(v)!r} "
                  f"spread {s:.4f} trimmed {spread(trimmed(v)):.4f} "
                  f"values {v}")
        print(f"{name}: widest spread {widest:.4f}, x5 {5 * widest:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
