#!/usr/bin/env python3
"""The control of the correctness check: the reference itself, put in the
program's place and computed in bfloat16, the precision below the float32
the engine states.  Its answers go through the same comparison as a run's,
and must fail it.

    python3 bench/control.py --workload tpch-sf1.mix-serial --seed 7 --requests 10

prints one JSON line: the cell, the seed and each number compared, as the
control reads it beside the cell's limit.  It needs no chip: the control
and the reference are numpy on the host.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import ml_dtypes  # noqa: E402

from bench import check, spec, tpch_gen  # noqa: E402
from bench.traffic import Clients, queries_of  # noqa: E402

LOWER = ml_dtypes.bfloat16


def readings(cell: spec.Cell, seed: int, requests: int, dt=LOWER) -> dict:
    """The numbers compared when the first ``requests`` requests of each
    client are answered by the reference computed in ``dt``."""
    qmods = {q: spec.query(q) for q in queries_of(cell.mix)}
    rels = tpch_gen.generate(float(cell.config["scale_factor"]), seed)
    clients = Clients(cell.mix, seed, lambda q, rng: qmods[q].binding(rng))
    asked = [clients.next(c) for _ in range(requests) for c in range(clients.n)]
    served = ((q, b, qmods[q].reference(rels, dt, **b)) for q, b in asked)
    return check.readings(served, lambda q, b: qmods[q].reference(rels, **b))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=10, help="requests per client")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    values = readings(cell, args.seed % 2**63, args.requests)
    checks = check.judge(values, cell.config["limits"])
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "control": "bfloat16",
        "fails": not check.passed(checks), "checks": checks,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
