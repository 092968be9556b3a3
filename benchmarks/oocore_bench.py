"""Out-of-core TPC-H benchmark — compressed chunked streaming vs decoded
device residency (DESIGN.md §10).

The five TPC-H queries run twice over the same generated data:

* **resident** — every relation decoded on device, per-query cached
  ``Executable`` (whole-plan jit), the repo's standard path;
* **streamed** — ``storage.chunk_db`` applies the storage plan under a
  device ``memory_budget_bytes`` that cannot hold the decoded fact table,
  so lineitem lives host-side as per-chunk encoded columns and the engine
  streams it: encoded bytes H2D (next chunk's upload overlapping the
  current chunk's compute), decoded on device, folded into carried
  accumulator state chunk by chunk.

Timed warm, interleaved best-of-N (drift hits both alike).  Device memory
for the streamed side is the engine's deterministic byte ledger
(``ExecutionReport.peak_chunk_bytes`` / ``peak_state_bytes`` of
``engine.last_report()``): 2× the decoded chunk working set (double
buffer) + the carried accumulator state — the CPU backend reports no
allocator stats, so the accounting is arithmetic, not sampled.

The record embeds both acceptance checks (enforced by
``benchmarks.perf_gate``, wired into CI):

* ``oocore_throughput_ratio_ge_0.8`` — streamed ≥ 0.8× resident
  throughput on the 5-query mix;
* ``oocore_memory_ratio_le_0.5`` — streamed device working set for the
  out-of-core relations ≤ 0.5× their decoded size.

    python -m benchmarks.oocore_bench --scale 0.05 --out BENCH_oocore.json
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import plan as P
from repro.core.cost import AnalyticCostModel
from repro.core.lower import compile as compile_plan
from repro.core.synthesis import synthesize
from repro.data import storage as S
from repro.data import tpch
from repro.data.table import collect_stats
from repro.exec import engine as E
from repro.exec.queries import QUERIES
from .common import emit, write_record

THROUGHPUT_BAR = 0.8
MEMORY_BAR = 0.5


def _once(fn) -> float:
    # each query result is materialized via items_np(): plan results hold
    # no bare array leaves, so this — not block_until_ready — is the honest
    # end-to-end barrier (it drains the async chunk loop AND the host-side
    # result extraction both paths share)
    t0 = time.perf_counter()
    for r in fn():
        r.items_np()
    return time.perf_counter() - t0


def _time_pair(fn_a, fn_b, repeats: int):
    fn_a(), fn_b()  # warm: both sides compiled before any timing
    ta, tb = [], []
    for _ in range(repeats):
        ta.append(_once(fn_a))
        tb.append(_once(fn_b))
    return float(np.min(ta)), float(np.min(tb))


def run(
    scale: float = 0.05,
    budget_bytes: int = 4 << 20,
    chunk_rows: int = 1 << 15,
    repeats: int = 5,
    seed: int = 3,
    out: str | None = None,
):
    from repro.costmodel import load_model

    delta = load_model() or AnalyticCostModel()
    db = tpch.generate(scale=scale, seed=seed).tables()
    sigma = collect_stats(db)
    cdb = S.chunk_db(db, memory_budget_bytes=budget_bytes, chunk_rows=chunk_rows)
    streamed_rels = sorted(r for r, t in cdb.items() if S.is_chunked(t))
    assert streamed_rels, "budget did not force any relation out of core"

    qnames = sorted(QUERIES)
    plans, params = [], []
    for qn in qnames:
        q = QUERIES[qn]
        choices = synthesize(q.llql(), sigma, delta).choices
        plans.append(
            P.fuse(
                compile_plan(q.llql(), choices), sigma=sigma,
                streamed=streamed_rels,
            )
        )
        params.append(q.defaults)
    ex_res = [E.cached_executable(p, db, sigma=sigma) for p in plans]
    ex_str = [E.cached_executable(p, cdb, sigma=sigma) for p in plans]

    def run_resident():
        return [ex(db, pv) for ex, pv in zip(ex_res, params)]

    def run_streamed():
        return [ex(cdb, pv) for ex, pv in zip(ex_str, params)]

    # correctness first: streamed answers match resident on every query
    for qn, rs, st in zip(qnames, run_resident(), run_streamed()):
        ref, got = rs.items_np(), st.items_np()
        assert set(ref) == set(got), qn
        for k in ref:
            np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-5)

    # deterministic memory ledger for one full streamed pass, aggregated
    # from the per-execution ExecutionReports (peaks max, counters sum)
    reports = []
    for ex, pv in zip(ex_str, params):
        ex(cdb, pv)
        reports.append(E.last_report())
    stats = {
        "regions": sum(r.streamed_regions for r in reports),
        "chunks": sum(r.chunks for r in reports),
        "h2d_bytes": sum(r.h2d_bytes for r in reports),
        "peak_chunk_bytes": max(r.peak_chunk_bytes for r in reports),
        "peak_state_bytes": max(r.peak_state_bytes for r in reports),
    }
    assert stats["regions"] >= len(streamed_rels), stats
    fact_decoded = sum(
        4 * db[r].nrows * len(db[r].names()) for r in streamed_rels
    )
    fact_encoded = sum(
        sum(c.nbytes for chunk in cdb[r].chunks for c in chunk.values())
        for r in streamed_rels
    )
    streamed_peak = stats["peak_chunk_bytes"] + stats["peak_state_bytes"]
    memory_ratio = streamed_peak / fact_decoded

    sec_res, sec_str = _time_pair(run_resident, run_streamed, repeats)
    throughput_ratio = sec_res / sec_str if sec_str > 0 else float("inf")

    entry = {
        "seconds": sec_str,
        "resident_ms": sec_res * 1e3,
        "streamed_ms": sec_str * 1e3,
        "throughput_ratio": round(throughput_ratio, 3),
        "memory_ratio": round(memory_ratio, 4),
        "queries": qnames,
        "streamed_relations": streamed_rels,
        "budget_bytes": budget_bytes,
        "chunk_rows": chunk_rows,
        "fact_decoded_bytes": fact_decoded,
        "fact_encoded_bytes": fact_encoded,
        "compression_ratio": round(fact_decoded / fact_encoded, 3),
        "stream_stats": stats,
    }
    emit(
        "oocore_tpch_mix",
        sec_str * 1e6,
        f"ms={sec_str*1e3:.2f},resident_ms={sec_res*1e3:.2f},"
        f"tput={throughput_ratio:.2f}x,mem={memory_ratio:.2f}x,"
        f"comp={fact_decoded/fact_encoded:.2f}x,"
        f"streamed={'+'.join(streamed_rels)}",
    )
    if out:
        write_record(
            out, "oocore",
            {"oocore/tpch_mix": entry},
            scale=scale,
            checks={
                "oocore_throughput_ratio_ge_0.8": {
                    "value": float(throughput_ratio), "min": THROUGHPUT_BAR,
                },
                "oocore_memory_ratio_le_0.5": {
                    "value": float(memory_ratio), "max": MEMORY_BAR,
                },
            },
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--budget-mb", type=float, default=4.0)
    ap.add_argument("--chunk-rows", type=int, default=1 << 15)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--out", default="BENCH_oocore.json")
    args = ap.parse_args()
    run(
        args.scale, int(args.budget_mb * (1 << 20)), args.chunk_rows,
        args.repeats, args.seed, args.out,
    )


if __name__ == "__main__":
    main()
