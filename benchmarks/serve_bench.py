"""Serving benchmark: warm cached path vs cold compile-per-request.

    PYTHONPATH=src python -m benchmarks.serve_bench [--scale S] [--requests N]

Drives ``repro.serve.query_server.QueryServer`` with a mixed parameter
workload over all five TPC-H queries (every request a fresh binding, so
nothing is answer-cacheable — only the *executable* is reusable), and
compares against the pipeline a parameterless engine is forced into:
synthesis + lowering + a fresh whole-plan jit for every request.

Emits the uniform BENCH record (``benchmarks.common.write_record``) with

* ``serve/<q>/warm``  — median warm seconds/request (micro-batched),
* ``serve/<q>/cold``  — median compile-per-request seconds,
* ``checks.warm_over_cold_rps`` — aggregate throughput ratio, gated ≥ 10×
  by ``benchmarks.perf_gate`` in CI.
"""
from __future__ import annotations

import numpy as np

from repro.core.cost import AnalyticCostModel
from repro.core.synthesis import synthesize
from repro.data import tpch
from repro.exec import engine as E
from repro.exec.queries import REGISTRY as QUERIES
from repro.session import connect
from .common import emit, write_record

# per-query parameter samplers: fresh bindings drawn over sensible domains
PARAM_SPACE = {
    "q1": lambda rng: {"date": float(rng.uniform(0.3, 0.95))},
    "q3": lambda rng: {"date": float(rng.uniform(0.02, 0.2))},
    "q5": lambda rng: {"region": int(rng.integers(0, 5))},
    "q9": lambda rng: {"color": int(rng.integers(0, 92))},
    "q18": lambda rng: {"threshold": float(rng.uniform(50.0, 250.0))},
}


def _workload(rng, n_per_query: int):
    reqs = [
        (qname, PARAM_SPACE[qname](rng))
        for qname in sorted(QUERIES)
        for _ in range(n_per_query)
    ]
    rng.shuffle(reqs)
    return reqs


def run(
    scale: float = 0.005,
    requests: int = 8,
    cold_requests: int = 2,
    max_batch: int = 8,
    seed: int = 0,
    out: str = "BENCH_serve.json",
):
    import time

    import jax

    from repro.serve.query_server import QueryServer

    # the cold path measures a genuinely fresh compile per request; a
    # persistent (on-disk) compilation cache — the one ``connect`` turns on,
    # or one CI restores — would serve those compiles from disk and deflate
    # the warm/cold ratio this bench gates on, so switch it off for this
    # process
    jax.config.update("jax_enable_compilation_cache", False)

    rng = np.random.default_rng(seed)
    db = tpch.generate(scale=scale, seed=seed).tables()
    delta = AnalyticCostModel()
    session = connect(db, delta=delta)
    sigma = session.sigma

    # -- warm path: compile once per shape, serve a mixed stream -----------
    srv = QueryServer(session, max_batch=max_batch)
    srv.warm_up()
    for qname, params in _workload(rng, requests):
        srv.submit(qname, **params)
    t0 = time.perf_counter()
    done = srv.run_until_done()
    warm_wall = time.perf_counter() - t0
    assert len(done) == requests * len(QUERIES)
    stats = srv.stats()
    warm_rps = len(done) / warm_wall

    results = {}
    by_query = {}
    for r in done:
        by_query.setdefault(r.qname, []).append(r)
    for qname, rs in sorted(by_query.items()):
        shape = stats["shapes"][qname]
        # the server was warmed up, so busy_s is pure warm execution wall
        sec = shape["busy_s"] / max(1, shape["served"])
        results[f"serve/{qname}/warm"] = {
            "seconds": sec,
            "requests": len(rs),
            "batches": sorted({r.batch_size for r in rs}),
        }
        emit(f"serve_{qname}/warm", sec * 1e6, f"reqs={len(rs)}")

    # -- cold path: the compile-per-request pipeline -----------------------
    from repro.core.lower import compile as compile_plan

    cold_secs = {}
    for qname in sorted(QUERIES):
        q = QUERIES[qname]
        ts = []
        for _ in range(cold_requests):
            params = q.bind_defaults(PARAM_SPACE[qname](rng))
            t0 = time.perf_counter()
            res = synthesize(q.llql(), sigma, delta)  # per-request synthesis
            plan = compile_plan(q.llql(), res.choices)
            ex = E.Executable(plan, db, sigma=sigma)  # fresh trace, no cache
            ex(db, params).items_np()
            ts.append(time.perf_counter() - t0)
        cold_secs[qname] = float(np.median(ts))
        results[f"serve/{qname}/cold"] = {
            "seconds": cold_secs[qname],
            "requests": cold_requests,
        }
        emit(f"serve_{qname}/cold", cold_secs[qname] * 1e6, "")

    cold_rps = 1.0 / float(np.mean(list(cold_secs.values())))
    ratio = warm_rps / cold_rps
    emit(
        "serve/aggregate", warm_wall / len(done) * 1e6,
        f"warm_rps={warm_rps:.1f},cold_rps={cold_rps:.2f},ratio={ratio:.1f}x,"
        f"warm_p99_ms={stats['warm_p99_ms']:.2f}",
    )
    write_record(
        out,
        "serve",
        results,
        shards=1,
        checks={
            "warm_over_cold_rps": {"value": ratio, "min": 10.0},
        },
        scale=scale,
        warm_rps=warm_rps,
        cold_rps=cold_rps,
        warm_p50_ms=stats["warm_p50_ms"],
        warm_p99_ms=stats["warm_p99_ms"],
        synth_runs=stats["synth_runs"],
    )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.005)
    ap.add_argument("--requests", type=int, default=8,
                    help="warm requests per query")
    ap.add_argument("--cold-requests", type=int, default=2,
                    help="compile-per-request samples per query")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    from .common import header

    header()
    run(
        scale=args.scale,
        requests=args.requests,
        cold_requests=args.cold_requests,
        max_batch=args.max_batch,
        out=args.out,
    )
