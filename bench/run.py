#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip and print its result.

    python3 bench/run.py --workload tpch-sf1.mix-serial --seed 7 --seconds 51 --trace 0

Set-up (timed as ``setup_s``, from the start of this process): generate the
cell's TPC-H data from ``--seed``, open a session with ``repro.connect`` as
the configuration says, put a ``QueryServer`` in front of it, and serve one
request of every query shape at every batch size the mix can form, so that
every program is compiled (or loaded from JAX's persistent cache) before the
window.  Window: the closed-loop clients of the traffic mix for
``--seconds``.  After it: the device's peak memory, then every answer of
the window checked against the benchmark's own numpy reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared beside its limit).
With ``--trace 1`` the window runs under the JAX profiler and ``metrics``
holds the cell's per-layer metrics.  Without a TPU, or with fewer chips
than the cell asks for, it exits with status 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, spec, stats, tpch_gen, trace_reduce  # noqa: E402
from bench.client import closed_loop  # noqa: E402
from bench.traffic import Clients, queries_of  # noqa: E402
from bench.window import Window  # noqa: E402

#: the JAX monitoring event of a trace, which ``session.retraces`` counts
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoAccelerator(RuntimeError):
    pass


def require_accelerator(chips: int):
    """The devices to run on; raises when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {len(devices)}")
    return devices


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_PROCESS:.1f}s] {msg}", file=sys.stderr, flush=True)


class _Traces:
    """Host-clock times of JAX's trace events."""

    def __init__(self):
        from jax import monitoring

        self.at = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == TRACE_EVENT:
            self.at.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.at)


def batch_sizes(max_batch: int):
    """Every batch size whose program or host-side unpacking differs:
    one, each power-of-two bucket, and ``max_batch`` itself."""
    sizes = {1, max_batch}
    b = 2
    while b < max_batch:
        sizes.add(b)
        b *= 2
    return sorted(sizes)


def load(rels, sorted_on):
    """The generated relations as the program's device tables."""
    import jax

    from repro.data.table import from_numpy

    db = {rel: from_numpy(cols, sorted_on=sorted_on[rel]) for rel, cols in rels.items()}
    jax.block_until_ready([t.columns for t in db.values()])
    return db


def warm(server, qmods, max_batch: int) -> None:
    """Serve every query shape at every batch size once, through the same
    ``submit``/``step`` path the window uses."""
    import numpy as np

    rng = np.random.default_rng(0)
    for qname, mod in qmods.items():
        for b in batch_sizes(max_batch):
            for _ in range(b):
                server.submit(qname, **mod.binding(rng))
            for r in server.run_until_done():
                if not r.ok:
                    raise RuntimeError(f"warm-up {qname} x{b} failed: {r.error!r}")
            server.finished.clear()


def configure_compile_cache() -> None:
    """JAX's persistent cache where the program keeps it (inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), holding
    every program however quickly it compiled, so that a cell's second run
    compiles nothing."""
    import jax

    from repro.session import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices, t_start: float) -> dict:
    import jax

    import repro
    from repro.serve.query_server import QueryServer

    traces = _Traces()
    devices = devices[: cell.chips]  # on a larger host the other chips hold nothing of the cell
    cfg, mix = cell.config, cell.mix
    sf = float(cfg["scale_factor"])
    qmods = {q: spec.query(q) for q in queries_of(mix)}
    log(
        f"cell={cell.name} seed={seed} device={devices[0].device_kind} x{len(devices)} "
        f"cache={jax.config.jax_compilation_cache_dir}"
    )

    rels = tpch_gen.generate(sf, seed)
    db = load(rels, tpch_gen.SORTED_ON)
    session = repro.connect(db, **cfg.get("session", {}))
    server = QueryServer(session, max_batch=int(mix["max_batch"]))
    warm(server, qmods, int(mix["max_batch"]))
    clients = Clients(mix, seed, lambda q, rng: qmods[q].binding(rng))
    log(f"set-up done: streamed={session.streamed}")

    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    if trace:
        # native events and the client loop's spans only: the Python tracer would
        # record every call of the host loops and slow them many times over
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=options)
    counters_before = dict(server.counters)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        done, queued = closed_loop(server, clients, t0 + seconds)
    t_last = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    setup_s = t0 - t_start
    answers = stats.weighted(done, t0 + seconds)
    win = [c for c, _ in answers]
    counters_after = dict(server.counters)
    jax_traces = traces.between(t0, t_last)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(
        f"window: answered={len(win)} weight={sum(w for _, w in answers):.3f} queued={queued} "
        f"traces={jax_traces} server={counters_after}"
    )

    # the program's state goes before the reference runs
    del server, session, db, clients
    gc.collect()

    checked = [c for c in win if c.result is not None]
    memo = {}

    def reference(qname, binding):
        key = (qname, tuple(sorted(binding.items())))
        if key not in memo:
            memo[key] = qmods[qname].reference(rels, **binding)
        return memo[key]

    t_check = time.perf_counter()
    values = check.readings(((c.qname, c.binding, c.result) for c in checked), reference)
    checks = check.judge(values, cfg["limits"])
    errored = [c for c in win if c.failed.startswith("error")]
    correct = check.passed(checks) and not errored
    log(f"checked {len(checked)} answers in {time.perf_counter() - t_check:.1f}s; errors={len(errored)}")

    failed = sum(1 for c in win if c.failed)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result = {"correct": bool(correct), "attempted": len(win), "failed": failed}
    if not trace:
        values_e2e = {
            "qps": stats.qps(answers, seconds),
            "latency_p50_s": stats.latency_percentile(answers, 50, seconds),
            "latency_p95_s": stats.latency_percentile(answers, 95, seconds),
            "peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        }
        # ``<quantity>.<group>`` is ``<quantity>`` under a bound of its own,
        # for the cells that list it
        read = {m["name"]: values_e2e[m["name"].split(".")[0]] for m in cell.end_to_end}
        result["metrics"] = {
            m["name"]: {"value": read[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
            if read[m["name"]] is not None
        }
        result["device"] = device
    else:
        red = trace_reduce.reduce_dir(trace_dir.name, n_devices=cell.chips)
        trace_dir.cleanup()
        ctx = Window(
            completions=win, t0=t0, t_last=t_last,
            counters_before=counters_before, counters_after=counters_after,
            jax_traces=jax_traces, scale_factor=sf, queries=qmods,
            peaks=spec.peaks(devices[0].device_kind), trace=red,
        )
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = {**device, "busy_s": red.busy_s, "window_s": red.window_s}
        result["breakdown"] = red.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        devices = require_accelerator(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    configure_compile_cache()
    result = run(cell, args.seed % 2**63, args.seconds, bool(args.trace), devices, T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
