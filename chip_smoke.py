#!/usr/bin/env python3
"""Run the resident TPC-H query path once on a TPU and check every answer.

    python chip_smoke.py                # one chip: SF 1, all phases below
    python chip_smoke.py --chips 4      # four chips: the sharded path only
    python chip_smoke.py --scale 10     # another TPC-H scale factor

Default phase, on one chip, through the entry points a user calls:

1. generate TPC-H at ``--scale`` from ``--seed`` onto the device;
2. ``repro.connect(db)``: run q1, q3, q5, q9 and q18 at their default
   bindings, cold and warm, each checked against its numpy reference;
3. rebind q1's date and q18's threshold: the cached executables must not
   retrace;
4. serve 12 mixed requests (q1 date, q18 threshold, q5 region) through
   ``QueryServer(session)`` until drained, each checked against its
   reference;
5. run q1 and q18 in a ``memory_budget`` session that streams lineitem
   from the host (budget a quarter of lineitem's bytes): the results must
   be bitwise equal to the resident ones.

``--chips 4`` opens a ``shards=4`` session instead, runs the five queries,
serves a few requests through ``QueryServer``, checks all against the
references, and prints each device's share of lineitem's bytes.

Every query must report ``degraded == faults == retries == 0``: a result the
degradation ladder rescued is a failure here.  Without a TPU the script
exits non-zero before it prints a result.  The last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

QUERY_NAMES = ("q1", "q3", "q5", "q9", "q18")
SERVED = (  # the QueryServer mix: 12 requests over three shapes
    ("q1", {"date": 0.3}), ("q18", {"threshold": 150.0}), ("q5", {"region": 0}),
    ("q1", {"date": 0.6}), ("q18", {"threshold": 200.0}), ("q5", {"region": 1}),
    ("q1", {"date": 0.9}), ("q18", {"threshold": 250.0}), ("q5", {"region": 2}),
    ("q1", {"date": 0.75}), ("q18", {"threshold": 300.0}), ("q5", {"region": 4}),
)
SHARDED_SERVED = SERVED[:6]
# a micro-batch of B bindings runs as one vmapped program whose per-row
# temporaries scale with B: q1 at SF 10 needs ~9 GB of HBM at B=2 and more
# than the chip holds at B=4 (v5e compile, memory_analysis)
SERVER_MAX_BATCH = 2


class SmokeFailure(RuntimeError):
    pass


_START = time.perf_counter()


def log(msg: str) -> None:
    """One progress line, stamped with seconds since start: a run cut by
    its time limit still shows how far it got."""
    print(f"[{time.perf_counter() - _START:.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class References:
    """Numpy references over a host copy of the database, one per binding."""

    def __init__(self, db):
        self.host = {
            rel: dataclasses.replace(
                t, columns={c: np.asarray(v) for c, v in t.columns.items()}
            )
            for rel, t in db.items()
        }
        self._memo = {}

    def __call__(self, qname: str, binding: dict):
        from repro.exec.queries import QUERIES

        q = QUERIES[qname]
        full = q.bind_defaults(binding)
        key = (qname, tuple(sorted(full.items())))
        if key not in self._memo:
            self._memo[key] = q.reference(self.host, **full)
        return self._memo[key]


def matches(got: dict, want: dict) -> bool:
    from repro.session import CROSS_EXECUTOR_ATOL, CROSS_EXECUTOR_RTOL

    return set(got) == set(want) and all(
        np.allclose(got[k], want[k], rtol=CROSS_EXECUTOR_RTOL, atol=CROSS_EXECUTOR_ATOL)
        for k in want
    )


def bitwise_equal(a: dict, b: dict) -> bool:
    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def check_clean(what: str, rep) -> None:
    check(
        rep.degraded == 0 and rep.faults == 0 and rep.retries == 0,
        f"{what}: degraded={rep.degraded} faults={rep.faults} "
        f"retries={rep.retries} (ladder rung {rep.degradation!r})",
    )


def timed_query(session, qname: str, **binding):
    t0 = time.perf_counter()
    out = session.query(qname, **binding)
    return out, time.perf_counter() - t0


def run_queries(session, refs, label: str) -> dict:
    """Each query cold then warm at its default binding; returns results."""
    results = {}
    for qname in QUERY_NAMES:
        out, cold = timed_query(session, qname)
        check_clean(f"{label} {qname} cold", session.report())
        out2, warm = timed_query(session, qname)
        rep = session.report()
        check_clean(f"{label} {qname} warm", rep)
        check(bitwise_equal(out, out2), f"{label} {qname}: warm != cold result")
        ok = matches(out, refs(qname, {}))
        modes = ",".join(f"{s}={m}" for s, m in sorted(rep.modes().items()))
        log(
            f"{label} {qname}: groups={len(out)} matches_reference={ok} "
            f"cold_s={cold} warm_s={warm} modes=[{modes}] "
            f"degraded={rep.degraded} faults={rep.faults} retries={rep.retries}"
        )
        check(ok, f"{label} {qname} differs from its numpy reference")
        results[qname] = out
    return results


def serve(session, refs, requests, label: str) -> None:
    from repro.serve.query_server import QueryServer

    srv = QueryServer(session, max_batch=SERVER_MAX_BATCH)
    for qname, binding in requests:
        srv.submit(qname, **binding)
    log(f"{label}: {len(requests)} requests queued")
    t0 = time.perf_counter()
    responses = srv.run_until_done()
    wall = time.perf_counter() - t0
    check(len(responses) == len(requests), f"{label}: {len(responses)} responses")
    for r in sorted(responses, key=lambda r: r.rid):
        check(r.ok, f"{label} request {r.rid} {r.qname} failed: {r.error!r}")
        check(
            not r.degraded and r.retries == 0,
            f"{label} request {r.rid}: degraded={r.degraded!r} retries={r.retries}",
        )
        ok = matches(r.result, refs(r.qname, r.params))
        log(
            f"{label} request {r.rid} {r.qname} {r.params}: ok={r.ok} "
            f"matches_reference={ok} batch={r.batch_size} latency_s={r.latency_s}"
        )
        check(ok, f"{label} request {r.rid} differs from its numpy reference")
    st = srv.stats()
    log(
        f"{label} server: responses={st['responses']} batches={st['batches']} "
        f"faults={st['faults']} retries={st['retries']} degraded={st['degraded']} "
        f"errors={st['errors']} wall_s={wall}"
    )
    check(
        st["faults"] == st["retries"] == st["degraded"] == st["errors"] == 0,
        f"{label} server counters not clean: {st}",
    )


def default_phase(db, refs) -> None:
    import repro

    lineitem_bytes = sum(a.nbytes for a in db["lineitem"].columns.values())
    session = repro.connect(db)
    resident = run_queries(session, refs, "resident")

    for qname, binding in (("q1", {"date": 0.5}), ("q18", {"threshold": 200.0})):
        ex = session.shape(qname).executable
        before = ex.trace_count
        out, wall = timed_query(session, qname, **binding)
        check_clean(f"rebind {qname}", session.report())
        ok = matches(out, refs(qname, binding))
        log(
            f"rebind {qname} {binding}: trace_count {before}->{ex.trace_count} "
            f"matches_reference={ok} wall_s={wall}"
        )
        check(ex.trace_count == before, f"rebinding {qname} retraced")
        check(ok, f"rebind {qname} differs from its numpy reference")

    serve(session, refs, SERVED, "served")

    budget = lineitem_bytes // 4
    t0 = time.perf_counter()
    # chunks as large as the budget allows two of at once (the current one
    # and the prefetched next): each chunk's fold merges the carried
    # dictionary state, whose cost grows with its capacity, so fewer,
    # larger chunks keep q18's streamed fold from dominating the run
    row_bytes = lineitem_bytes // db["lineitem"].nrows
    chunk_rows = 1 << ((budget // (2 * row_bytes)).bit_length() - 1)
    streamed = repro.connect(db, memory_budget=budget, chunk_rows=chunk_rows)
    log(
        f"streamed session: budget_bytes={budget} chunk_rows={chunk_rows} "
        f"chunked={streamed.streamed} "
        f"setup_s={time.perf_counter() - t0}"
    )
    check("lineitem" in streamed.streamed, "lineitem is not streamed")
    for qname in ("q1", "q18"):
        out, wall = timed_query(streamed, qname)
        rep = streamed.report()
        check_clean(f"streamed {qname}", rep)
        same = bitwise_equal(out, resident[qname])
        modes = ",".join(f"{s}={m}" for s, m in sorted(rep.modes().items()))
        log(
            f"streamed {qname}: bitwise_equal_resident={same} wall_s={wall} "
            f"chunks={rep.chunks} h2d_bytes={rep.h2d_bytes} modes=[{modes}]"
        )
        check(same, f"streamed {qname} is not bitwise equal to resident")


def sharded_phase(db, refs, chips: int) -> None:
    import repro

    session = repro.connect(db, shards=chips)
    run_queries(session, refs, f"sharded{chips}")
    placed = session.shape("q1").executable.inputs[0]["lineitem"]
    total = sum(a.nbytes for a in placed.values())
    per_dev = {}
    for a in placed.values():
        for shard in a.addressable_shards:
            per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) + shard.data.nbytes
    for dev_id, nbytes in sorted(per_dev.items()):
        log(f"lineitem on device {dev_id}: bytes={nbytes} share={nbytes / total}")
    check(len(per_dev) == chips, f"lineitem spans {len(per_dev)} devices, not {chips}")
    serve(session, refs, SHARDED_SERVED, f"sharded{chips}-served")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    # SF 1 (6M lineitems): on a v5e the warm query path takes 38-67 s per
    # join query at SF 10, so this run's ~25 query executions would take
    # far more than 20 minutes there, and q1's float32 group sums drift past
    # the reference tolerance (PERF.md)
    ap.add_argument("--scale", type=float, default=1.0, help="TPC-H scale factor")
    ap.add_argument("--seed", type=int, default=0, help="data generator seed")
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4 runs only the sharded path over four chips",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.data import tpch
    from repro.session import use_compile_cache

    cache_dir = use_compile_cache()
    log(
        f"device kind={devices[0].device_kind} count={len(devices)} "
        f"compile_cache={cache_dir}"
    )
    t0 = time.perf_counter()
    db = tpch.generate(scale=args.scale, seed=args.seed).tables()
    jax.block_until_ready([t.columns for t in db.values()])
    table_bytes = {
        rel: sum(a.nbytes for a in t.columns.values()) for rel, t in db.items()
    }
    log(
        f"tpch scale={args.scale} seed={args.seed} rows={db['lineitem'].nrows} "
        f"table_bytes={table_bytes} total_bytes={sum(table_bytes.values())} "
        f"generate_s={time.perf_counter() - t0}"
    )
    refs = References(db)
    log("host copy for the references made")

    if args.chips == 1:
        default_phase(db, refs)
    else:
        sharded_phase(db, refs, args.chips)

    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    log(f"peak_bytes_in_use={peak}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
