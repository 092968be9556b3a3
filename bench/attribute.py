#!/usr/bin/env python3
"""Where one cell's window goes, by the program's own spans and scopes.

    python3 bench/attribute.py --workload tpch-sf1.mix-serial --seed 7 --seconds 51

Sets the cell up as ``bench/run.py`` does, runs its window under the JAX
profiler and checks no answer.  Prints one JSON line (``attribution``):

* ``idle_in_step_s``: the first chip's idle time inside the client loop's
  ``bench.step`` spans; ``idle_by_span``: the part of it under each of the
  server's spans (``serve.dispatch``, ``serve.sync``, ``serve.convert``),
  and ``span_cover`` the share those three cover together;
* ``busy_s`` and ``busy_by_scope``: device own time, averaged over the
  chips, under each device scope (``dict.build``, ``dict.probe``,
  ``stream.decode``; the innermost where they nest; ``""`` for none), and
  ``unscoped_top`` the five ops under none that take the most;
* ``h2d_s``: the union of the runtime's ``TransferToDevice`` events, and
  ``h2d_in_dispatch`` the share of it inside ``serve.dispatch`` spans;
* ``answers`` and ``ms_per_answer``: the spans and scopes per answer.

``--scale`` multiplies the configuration's scale factor and its session's
memory budget (chunks stay the largest power of two of which two fit), for
small traces; ``--keep DIR`` leaves the trace there as ``trace.xplane.pb.xz``.
Without a TPU it exits with status 2.
"""
from __future__ import annotations

import argparse
import glob
import json
import lzma
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as R  # noqa: E402
from bench import spec, tpch_gen, trace_reduce, xplane_meta  # noqa: E402
from bench.client import closed_loop  # noqa: E402
from bench.traffic import Clients, queries_of  # noqa: E402

STEP = trace_reduce.SPAN + "step"


def names():
    """The program's span and scope names (``exec/engine.py``)."""
    from repro.exec import engine as E

    return (E.SPAN_DISPATCH, E.SPAN_SYNC, E.SPAN_CONVERT), (
        E.SCOPE_BUILD, E.SCOPE_PROBE, E.SCOPE_DECODE,
    )


def intersect(a, b):
    """The intersection of two sets of intervals, as disjoint intervals."""
    a, b = trace_reduce.union(a), trace_reduce.union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length_s(intervals) -> float:
    return sum(b - a for a, b in intervals) / 1e9


def attribute(raw: bytes, answers: int, n_devices: int = 1) -> dict:
    """The attribution of one traced window (a serialized XSpace)."""
    from jax.profiler import ProfileData

    spans, scopes = names()
    red = trace_reduce.reduce(ProfileData.from_serialized_xspace(raw), n_devices)
    idle = intersect(red.gaps(0), [(a, b) for a, b, n in red.client if n == STEP])
    by_span = {
        s: _length_s(intersect(idle, [(a, b) for a, b, n in red.client if n == s]))
        for s in spans
    }
    idle_s = _length_s(idle)
    h2d = trace_reduce.union(red.transfers)
    dispatch = [(a, b) for a, b, n in red.client if n == spans[0]]
    by_scope = {s: 0.0 for s in scopes + ("",)}
    unscoped: dict = {}
    for o, t in xplane_meta.own_in_window(raw, red.window):
        scope = xplane_meta.scope_of(o.path, scopes)
        by_scope[scope or ""] += t
        if scope is None:
            key = trace_reduce.op_name(o, o.path or "(no name stack)")
            unscoped[key] = unscoped.get(key, 0.0) + t
    per = 1e3 / max(1, answers)
    return {
        "window_s": red.window_s,
        "busy_s": red.busy_s,
        "idle_in_step_s": idle_s,
        "idle_by_span": by_span,
        # the server's spans follow one another, so their parts add up
        "span_cover": sum(by_span.values()) / idle_s if idle_s else None,
        "busy_by_scope": by_scope,
        "unscoped_top": sorted(unscoped.items(), key=lambda kv: -kv[1])[:5],
        # the runtime's host-to-device transfer events that fall inside
        # the server's dispatch spans
        "h2d_s": _length_s(h2d),
        "h2d_in_dispatch": _length_s(intersect(h2d, dispatch)) / _length_s(h2d) if h2d else None,
        "answers": answers,
        "ms_per_answer": {
            **{s: v * per for s, v in by_span.items()},
            **{s or "unscoped": v * per for s, v in by_scope.items()},
        },
    }


def scaled(cell: spec.Cell, scale: float) -> spec.Cell:
    """The cell at ``scale`` times its scale factor; a memory budget keeps
    its share of lineitem, and chunks stay the largest power of two of
    which two fit it."""
    cfg = dict(cell.config)
    sf = float(cfg["scale_factor"]) * scale
    sess = dict(cfg.get("session", {}))
    if "memory_budget" in sess:
        sess["memory_budget"] = int(sess["memory_budget"] * scale)
        sess["chunk_rows"] = 1 << ((sess["memory_budget"] // 80).bit_length() - 1)
    cell.config = dict(cfg, scale_factor=sf, session=sess)
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    cell = scaled(spec.cell(args.workload), args.scale)
    try:
        devices = R.require_accelerator(cell.chips)
    except R.NoAccelerator as e:
        print(f"attribute: {e}", file=sys.stderr)
        return 2
    import jax

    import repro
    from repro.serve.query_server import QueryServer

    R.configure_compile_cache()
    cfg, mix = cell.config, cell.mix
    qmods = {q: spec.query(q) for q in queries_of(mix)}
    db = R.load(tpch_gen.generate(float(cfg["scale_factor"]), args.seed % 2**63), tpch_gen.SORTED_ON)
    server = QueryServer(repro.connect(db, **cfg.get("session", {})), max_batch=int(mix["max_batch"]))
    R.warm(server, qmods, int(mix["max_batch"]))
    clients = Clients(mix, args.seed % 2**63, lambda q, rng: qmods[q].binding(rng))
    R.log(f"set-up done: {cell.name} at SF {cfg['scale_factor']}")
    with tempfile.TemporaryDirectory(prefix="bench-attr-") as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN + "window"):
            done, _ = closed_loop(server, clients, t0 + args.seconds)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        raw = Path(path).read_bytes()
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        Path(args.keep, "trace.xplane.pb.xz").write_bytes(lzma.compress(raw))
    out = attribute(raw, len(done), n_devices=cell.chips)
    print(json.dumps({"workload": cell.name, "seed": args.seed, "attribution": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
