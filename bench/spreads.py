#!/usr/bin/env python3
"""Median and spread of each metric over sets of runs of one cell, from
which the bounds of ``BENCHMARK.json`` are set.

    python3 bench/spreads.py <set dir> [<set dir> ...]

Each directory holds one file per run, ``*.out``, with the run's standard
output; its last line is the run's result.  For every metric the table
gives each set's median, its spread (IQR ÷ median, ``stats.spread``) and
the spread with the set's run farthest from the median left out, then the
widest spread over the sets.
"""
from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Sequence

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.stats import spread  # noqa: E402


def results(set_dir: Path) -> List[dict]:
    """The result line of every run in ``set_dir``, in file-name order."""
    out = []
    for f in sorted(Path(set_dir).glob("*.out")):
        lines = f.read_text().strip().splitlines()
        if lines:
            out.append(json.loads(lines[-1]))
    return out


def without_farthest(values: Sequence[float]) -> List[float]:
    """``values`` less the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def table(sets: Sequence[Sequence[dict]]) -> Dict[str, dict]:
    """Per metric: each set's ``median``, ``spread`` and ``trimmed``
    spread, and ``widest``, the largest spread of a set."""
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    out = {}
    for name in names:
        rows = []
        for s in sets:
            v = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            rows.append({
                "n": len(v),
                "median": statistics.median(v),
                "spread": spread(v),
                "trimmed": spread(without_farthest(v)),
            })
        out[name] = {"sets": rows, "widest": max(r["spread"] for r in rows)}
    return out


def main(argv: Sequence[str]) -> int:
    sets = [results(Path(d)) for d in argv]
    for s, d in zip(sets, argv):
        print(f"{d}: {len(s)} runs, correct {sum(bool(r['correct']) for r in s)}")
    for name, row in table(sets).items():
        cells = "  ".join(
            f"n={r['n']} median={r['median']:.6g} spread={r['spread']:.4%} "
            f"trimmed={r['trimmed']:.4%}"
            for r in row["sets"]
        )
        print(f"{name}: {cells}  widest={row['widest']:.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
