#!/usr/bin/env python3
"""Spread of each metric over a set of result lines: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a share
of the median, which is what a bound is set from (about five times the widest).
Beside it the two readings the driver's check takes: ``trimmed`` leaves out the
run farthest from the median (a bound is too tight under twice the sets' mean
of it), ``pooled`` is the spread of all the sets' runs together (a bound is too
loose over eight times the widest).

    python3 spread.py <file-with-one-result-line-per-run> [more files = more sets]
"""

from __future__ import annotations

import json
import statistics
import sys


def read(path: str) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{") or '"metrics"' not in line:
                continue
            for k, m in json.loads(line)["metrics"].items():
                out.setdefault(k, []).append(m["value"])
    return out


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values: list[float]) -> float:
    m = statistics.median(values)
    rest = sorted(values, key=lambda x: abs(x - m))[:-1]
    return spread(rest)


def main(argv: list[str]) -> int:
    sets = [read(p) for p in argv[1:]]
    for name in sorted({k for s in sets for k in s}):
        cols, pool = [], []
        for s in sets:
            v = s.get(name, [])
            pool += v
            cols.append(
                f"n={len(v)} median={statistics.median(v):.6g} spread={100 * spread(v):.2f}% trimmed={100 * trimmed(v):.2f}%"
                if len(v) >= 3
                else f"n={len(v)}"
            )
        if len(sets) > 1 and len(pool) >= 2:
            cols.append(f"pooled={100 * spread(pool):.2f}%")
        print(f"{name:28s} " + " | ".join(cols))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
