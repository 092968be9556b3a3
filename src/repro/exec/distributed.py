"""Distributed query execution — the paper's operators at pod scale.

DBFlex is a single-core engine; this module is the scale-out adaptation
(DESIGN.md §4).  Distribution is entirely *plan-driven*: ``plan.legalize``
assigns every symbol a partitioning property and inserts explicit conversion
nodes, and this module realizes those nodes inside one ``shard_map``:

* ``Repartition(hash)``      — ``_plan_repartition``: route every frame row
  to the shard owning ``hash(key)`` (one all-to-all with statically-shaped
  bucket buffers).  This is what makes co-partitioned joins reachable: a
  dictionary built after a hash repartition and a probe stream repartitioned
  on the same key land on the same shards.
* ``Repartition(broadcast)`` — all-gather the frame rows onto every shard
  (the broadcast-build placement for small build sides).
* ``Exchange(shuffle)``      — ``_plan_exchange``: merge per-shard partial
  dictionaries by routing their entries to the hash-owner shard and
  re-building locally (the classic combiner: wire volume is
  O(groups/shard), not O(rows)).  The rebuild is op-aware: each value lane
  combines by the monoid ``legalize`` copied from the producing node.
* ``Exchange(allreduce)``    — per-field psum/pmin/pmax of scalar ref
  records (``Exchange.field_ops``).

The hash route uses the same multiplicative mix as the dictionaries, so
every repartition is exactly "partition by hash prefix" — each shard's
dictionary is VMEM-sizable, which is what makes the Pallas probe kernels
applicable per-shard (the radix-partitioning story of DESIGN.md §2).

All functions run inside ``shard_map`` over a named mesh axis (or axis
tuple: pass ``("pod", "data")`` for hierarchical two-level meshes — XLA
lowers the combined-axis all_to_all to the hierarchical schedule).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.dicts import base as dbase
from repro.dicts import registry
from repro.testing import faults as _faults

Axis = Union[str, Tuple[str, ...]]


def _axis_size(axis: Axis) -> jax.Array:
    if isinstance(axis, str):
        return lax.axis_size(axis)
    n = 1
    for a in axis:
        n = n * lax.axis_size(a)
    return n


def _axis_index(axis: Axis) -> jax.Array:
    return lax.axis_index(axis)


def _route(
    keys: jax.Array, n_sh: int, *payloads: jax.Array
) -> Tuple[jax.Array, ...]:
    """Bucket rows by hash(key) % n_sh into a [n_sh, n_local] send buffer.
    Returns (buf_keys, *buf_payloads, order, sorted_tgt, pos) — the order
    metadata lets callers route responses back to original positions."""
    n = keys.shape[0]
    tgt = (dbase._mix(keys, dbase._H2) % jnp.uint32(n_sh)).astype(jnp.int32)
    # dead rows (PAD keys) still get routed; they simply never match
    order = jnp.argsort(tgt)
    st = tgt[order]
    start = jnp.searchsorted(st, jnp.arange(n_sh, dtype=jnp.int32), side="left")
    pos = jnp.arange(n, dtype=jnp.int32) - start[st]
    buf_k = jnp.full((n_sh, n), dbase.PAD, keys.dtype).at[st, pos].set(keys[order])
    outs = [buf_k]
    for p in payloads:
        shape = (n_sh, n) + p.shape[1:]
        buf = jnp.zeros(shape, p.dtype).at[st, pos].set(p[order])
        outs.append(buf)
    return (*outs, order, st, pos)


def _a2a(x: jax.Array, axis: Axis) -> jax.Array:
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)


# ---------------------------------------------------------------------------
# row repartitioning primitives (per-shard bodies — call inside shard_map)
# ---------------------------------------------------------------------------


def repartition_cols(
    keys: jax.Array,  # [n_local] int32 routing keys
    mask: jax.Array,  # [n_local] bool live-row mask
    cols: Dict[str, jax.Array],  # named [n_local] payload columns
    axis: Axis,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Hash-route every live row to the shard owning ``hash(key) % n_sh``
    (one all-to-all over statically-shaped [n_sh, n_local] bucket buffers).
    Returns ``(mask', cols')`` with ``n_sh * n_local`` rows per shard — dead
    and buffer-padding rows are masked out.  Rows with equal keys land on
    the same shard, so dictionaries built from (and probes routed through)
    the same key values are co-partitioned."""
    n_sh = _axis_size(axis)
    rk = jnp.where(mask, keys, dbase.PAD)
    names = list(cols)
    routed = _route(rk, n_sh, mask.astype(jnp.int32), *(cols[c] for c in names))
    bufs = routed[1 : 2 + len(names)]
    new_mask = _a2a(bufs[0], axis).reshape(-1).astype(bool)
    new_cols = {
        c: _a2a(b, axis).reshape(-1) for c, b in zip(names, bufs[1:])
    }
    return new_mask, new_cols


def broadcast_cols(
    mask: jax.Array, cols: Dict[str, jax.Array], axis: Axis
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """All-gather every shard's rows onto every shard (the broadcast-build
    placement).  Returns ``(mask', cols')`` with ``n_sh * n_local`` rows,
    identical on every shard."""
    g = lambda x: lax.all_gather(x, axis, axis=0, tiled=True)
    return g(mask), {c: g(a) for c, a in cols.items()}


def _plan_repartition(node, frame, *, axis: Axis, params=None):
    """Realize a ``Repartition`` plan node on an executor Frame: move the
    rows of every bound loop variable's table together (they share row order
    and mask), preserving the variable bindings."""
    from repro.core.lower import compile_rowfn_frame
    from repro.data.table import Table
    from repro.exec import engine as E

    # injection point: cross-shard row movement (all-to-all / all-gather).
    # Fires at trace time inside the shard_map body — a cold-path stand-in
    # for a collective aborting mid-flight.
    _faults.check("shard-merge", detail=f"repartition {node.kind}")
    mask = frame.primary.live_mask()
    flat: Dict[str, jax.Array] = {}
    for var in frame.order:
        for c, a in frame.tables[var].columns.items():
            flat[f"{var}\0{c}"] = a
    if node.kind == "broadcast":
        new_mask, new_flat = broadcast_cols(mask, flat, axis)
    else:
        keys = jnp.asarray(
            compile_rowfn_frame(node.keyexpr, frame.tables, params), jnp.int32
        )
        new_mask, new_flat = repartition_cols(keys, mask, flat, axis)
    n_new = new_mask.shape[0]
    tables = {}
    for var in frame.order:
        pre = f"{var}\0"
        cols = {
            k[len(pre):]: a for k, a in new_flat.items() if k.startswith(pre)
        }
        # physical row order is shuffled: orderedness metadata is void
        tables[var] = Table(cols, n_new, mask=new_mask, sorted_on=())
    return E.Frame(tables, frame.order, frame.rels)


# ---------------------------------------------------------------------------
# physical-plan execution under shard_map
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedDictResult:
    """Global view of a shuffled result dictionary: each shard's slice holds
    its hash-owned keys, concatenated over shards (keys globally unique)."""

    ds: str
    keys: jax.Array  # [n_sh * C]
    vals: jax.Array  # [n_sh * C, V]
    valid: jax.Array  # [n_sh * C] bool

    def arrays(self):
        return self.keys, self.vals, self.valid

    def items_np(self):
        from repro.exec import engine as E

        return E.host_items(self.keys, self.vals, self.valid)

    def size(self) -> int:
        import numpy as np

        return int(np.asarray(self.valid).sum())


def _plan_exchange(node, built, *, axis: Axis):
    """Realize an Exchange node: route the per-shard partial dictionary's
    entries to their hash-owner shard (all-to-all) and merge with one local
    build — the per-shard-dictionary + Exchange pair of DESIGN.md §4.

    Both merge forms are **op-aware**: ``legalize`` copies the producing
    node's per-lane combine monoids onto the Exchange, so shuffle merges
    re-build with ``ops`` (each lane combines by its own monoid when
    partials for one key meet on the owner shard) and ``allreduce``
    exchanges (scalar Reduce records) psum/pmin/pmax per field."""
    from repro.exec import engine as E

    # injection point: cross-shard partial-dictionary merge (shuffle
    # all-to-all, allreduce psum/pmin/pmax) — trace time, like dict-build
    _faults.check("shard-merge", detail=f"exchange {node.kind}")
    if node.kind == "allreduce":
        fops = dict(getattr(node, "field_ops", ()) or ())
        if not isinstance(built, dict) or all(
            op == "sum" for op in fops.values()
        ):
            return jax.tree.map(lambda v: lax.psum(v, axis), built)
        merged = {}
        for name, v in built.items():
            op = fops.get(name, "sum")
            if op == "min":
                merged[name] = lax.pmin(v, axis)
            elif op == "max":
                merged[name] = lax.pmax(v, axis)
            else:
                merged[name] = lax.psum(v, axis)
        return merged

    mod = registry.get(built.res.ds)
    ks, vs, valid = built.res.arrays()
    lk = jnp.where(valid, ks, dbase.PAD)
    n_sh = _axis_size(axis)
    buf_k, buf_v, *_ = _route(lk, n_sh, vs)
    rk = _a2a(buf_k, axis).reshape(-1)
    rv = _a2a(buf_v, axis).reshape(-1, vs.shape[-1])
    # merge capacity must cover the worst hash skew: one shard can own up to
    # every routed entry (n_sh × the per-shard capacity), so size for it —
    # this is the same total footprint a single-shard build of the global
    # input would use, just concentrated on the owning shard
    merge_cap = dbase.next_pow2(int(n_sh) * ks.shape[0])
    ops = tuple(getattr(node, "ops", ()) or ())
    kw = {} if dbase.all_sum(ops) else {"ops": ops}
    t2 = mod.build(rk, rv, merge_cap, valid=rk != dbase.PAD, **kw)
    res = E.DictResult(built.res.ds, t2)
    return E.BuiltDict(res, built.choice, lanes=built.lanes, kind=built.kind)


def _place_inputs(db, mesh, axis: Axis, shard_rels, n_sh: int):
    """Place every relation's columns and mask on the mesh once, at executor
    build: relations in ``shard_rels`` are padded to a multiple of ``n_sh``
    rows and split along ``axis``, the rest are replicated.  Calls then feed
    arrays that already sit where ``shard_map`` wants them, so no launch
    moves a fact table off the device it was loaded on.  Returns
    ``(cols, masks, col_specs, mask_specs, sorted_on)`` keyed by relation."""
    cols_in, masks_in, col_specs, mask_specs, sorted_meta = {}, {}, {}, {}, {}
    for rel, t in db.items():
        mask = t.live_mask()
        cols = dict(t.columns)
        if rel in shard_rels:
            pad = (-t.nrows) % n_sh
            if pad:
                cols = {
                    c: jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
                    for c, v in cols.items()
                }
                mask = jnp.concatenate([mask, jnp.zeros((pad,), bool)])
            spec = P(axis)
        else:
            spec = P()
        placed = NamedSharding(mesh, spec)
        cols_in[rel] = {c: jax.device_put(v, placed) for c, v in cols.items()}
        masks_in[rel] = jax.device_put(mask, placed)
        col_specs[rel] = {c: spec for c in cols}
        mask_specs[rel] = spec
        sorted_meta[rel] = t.sorted_on
    return cols_in, masks_in, col_specs, mask_specs, sorted_meta


def sharded_executor(
    plan,
    db,
    mesh: jax.sharding.Mesh,
    axis: Axis,
    shard_rels: Tuple[str, ...] = ("lineitem",),
    sigma=None,
    fuse: bool = True,
):
    """Build the distributed realization of a compiled physical plan
    (``repro.core.plan``) with ``shard_rels`` row-sharded over ``axis`` and
    every other relation replicated, and return a zero-argument callable
    executing it.  ``plan.legalize`` assigns partitioning properties and
    makes every cross-shard conversion an explicit
    ``Repartition``/``Exchange`` node; the callable realizes those nodes
    under one jitted ``shard_map`` — including co-partitioned joins, where a
    dictionary built from sharded rows is hash-repartitioned by its key and
    probe streams are repartitioned (or mask-partitioned) to match.
    Repeated calls of the returned callable reuse the jit trace (benchmark
    loops time execution, not re-tracing).

    The *same* plan object the single-shard executor runs is accepted here —
    the distributed realization is a property of the executor, not the plan.
    Sorted-input/merge fast paths are disabled per shard (a shard holds a
    contiguous slice, but hinted kernels are tuned for the single-shard
    layout; correctness first).
    """
    from jax.sharding import PartitionSpec as PSpec

    from repro.core import plan as cplan
    from repro.data.table import Table
    from repro.exec import engine as E

    if isinstance(plan, cplan.BoundPlan):
        default_params = plan.binding_map()
        plan = plan.plan
    else:
        default_params = None

    splan, props = cplan.legalize(plan, tuple(shard_rels))
    if fuse:
        # fuse the per-shard partial phase of the legalized plan: the
        # Repartition/Exchange nodes legalization inserted are natural
        # region boundaries, so every fused region is a purely shard-local
        # streaming pass (DESIGN.md §7).  Σ here carries *global* rows — a
        # conservative over-estimate of the per-shard working set for the
        # VMEM budget.  ``fuse=False`` keeps the materialized node-by-node
        # form (benchmarks, fusion-equivalence tests).
        splan = cplan.fuse(splan, sigma=sigma)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n_sh = 1
    for a in axes:
        n_sh *= mesh.shape[a]

    cols_in, masks_in, col_specs, mask_specs, sorted_meta = _place_inputs(
        db, mesh, axis, shard_rels, n_sh
    )

    # parameter values are replicated scalars; stable dtypes keep the trace
    param_specs = {name: PSpec() for name in plan.param_names()}
    trace_counter = [0]
    # ExecutionReport plumbing: execute_plan's per-region telemetry fires as
    # Python side effects *at trace time* inside shard_map; capture that
    # trace report once per retrace and republish it per call with the
    # measured wall time (same protocol as the single-shard Executable)
    report_state = {"trace": None, "seen": 0}

    def publish(wall_s: float) -> None:
        if trace_counter[0] != report_state["seen"]:
            report_state["trace"] = E.last_report()
            report_state["seen"] = trace_counter[0]
        E.republish_report(
            report_state["trace"], wall_s, trace_counter[0], shards=n_sh
        )

    def coerce(params):
        return E.coerce_bindings(plan, params, defaults=default_params)

    fused_regions = sum(isinstance(n, cplan.Pipeline) for n in splan.nodes)

    def run_local(cols, masks, pvals):
        trace_counter[0] += 1  # python side effect: fires per trace only
        # injection point: per-shard local execution — trace time, models
        # one shard's device exhausting memory during the partial phase
        # (default error kind ``oom``)
        _faults.check("shard-oom", detail=f"{n_sh} shards")
        local_db = {}
        for rel in cols:
            n = next(iter(cols[rel].values())).shape[0]
            local_db[rel] = Table(
                cols[rel], n, mask=masks[rel], sorted_on=sorted_meta[rel]
            )
        return E.execute_plan(
            splan,
            local_db,
            sigma=None,
            exchange_impl=functools.partial(_plan_exchange, axis=axis),
            repartition_impl=functools.partial(_plan_repartition, axis=axis),
            allow_sorted=False,
            params=pvals,
        )

    result_node = (
        plan.node_defining(plan.result) if plan.result is not None else None
    )
    if result_node is None or isinstance(result_node, cplan.Reduce):
        # scalar ref-record result: per-shard partials were already psum-ed
        # by the allreduce Exchange, so every shard holds the global answer
        def body_scalar(cols, masks, pvals):
            return run_local(cols, masks, pvals)

        wrapped_scalar = jax.jit(
            compat.shard_map(
                body_scalar,
                mesh=mesh,
                in_specs=(col_specs, mask_specs, param_specs),
                out_specs=PSpec(),
            )
        )

        def run_scalar(params=None):
            # injection point: sharded whole-plan dispatch (the sharded
            # twin of ``kernel-launch``) — fires per call, warm and cold
            _faults.check("shard-exec")
            t0 = time.perf_counter()
            try:
                out = jax.block_until_ready(
                    wrapped_scalar(cols_in, masks_in, coerce(params))
                )
            except Exception as e:  # noqa: BLE001 — boundary translation
                E._raise_classified(e)
            publish(time.perf_counter() - t0)
            run_scalar.last_report = E.last_report()
            return out

        run_scalar.trace_counter = trace_counter
        run_scalar.inputs = (cols_in, masks_in)
        run_scalar.last_report = None
        run_scalar.fused_regions = fused_regions
        run_scalar.n_shards = n_sh
        return run_scalar

    def body(cols, masks, pvals):
        ks, vs, valid = run_local(cols, masks, pvals).arrays()
        return ks, vs, valid.astype(jnp.int32)

    # a Replicated result dictionary is identical on every shard — take one
    # copy; partitioned results concatenate the per-shard key-disjoint slices
    replicated = isinstance(props.get(plan.result), cplan.Replicated)
    spec_k = PSpec() if replicated else PSpec(axis)
    spec_v = PSpec(None, None) if replicated else PSpec(axis, None)
    wrapped = jax.jit(
        compat.shard_map(
            body,
            mesh=mesh,
            in_specs=(col_specs, mask_specs, param_specs),
            out_specs=(spec_k, spec_v, spec_k),
        )
    )
    ds = getattr(result_node, "choice", None)

    def run(params=None):
        # injection point: sharded whole-plan dispatch (the sharded twin
        # of ``kernel-launch``) — fires per call, warm and cold
        _faults.check("shard-exec")
        t0 = time.perf_counter()
        try:
            ks, vs, valid = jax.block_until_ready(
                wrapped(cols_in, masks_in, coerce(params))
            )
        except Exception as e:  # noqa: BLE001 — boundary translation
            E._raise_classified(e)
        publish(time.perf_counter() - t0)
        run.last_report = E.last_report()
        return ShardedDictResult(
            ds.ds if ds is not None else "ht_linear", ks, vs, valid.astype(bool)
        )

    run.trace_counter = trace_counter
    run.inputs = (cols_in, masks_in)
    run.last_report = None
    run.fused_regions = fused_regions
    run.n_shards = n_sh
    return run


def sharded_shared_executor(
    plans,
    db,
    mesh: jax.sharding.Mesh,
    axis: Axis,
    shard_rels: Tuple[str, ...] = ("lineitem",),
    sigma=None,
    fusion=None,
):
    """Distributed shared-scan batch executor (DESIGN.md §9).

    Each plan is legalized and fused exactly as in :func:`sharded_executor`;
    the per-shard *partial* phases are then merged across plans with
    ``plan.merge_shared_scans`` — the shard-local fact pass is paid once for
    the whole batch — while every plan keeps its own ``Exchange`` nodes,
    so cross-shard merges stay **per query** (each query's partial
    dictionaries are shuffled/psum-ed independently; results are identical
    to running the queries one at a time).  Returns a callable
    ``run(params_list) -> [result, ...]`` in ``plans`` order; semiring
    min/max lanes merge through the op-aware exchanges."""
    from jax.sharding import PartitionSpec as PSpec

    from repro.core import plan as cplan
    from repro.data.table import Table
    from repro.exec import engine as E

    plans = tuple(plans)
    assert not any(isinstance(p, cplan.BoundPlan) for p in plans), (
        "bind parameters per call via params_list"
    )
    splans, propss = [], []
    for p in plans:
        sp_, props = cplan.legalize(p, tuple(shard_rels))
        splans.append(cplan.fuse(sp_, sigma=sigma))
        propss.append(props)
    shared = cplan.merge_shared_scans(splans, sigma=sigma, fusion=fusion)

    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n_sh = 1
    for a in axes:
        n_sh *= mesh.shape[a]

    cols_in, masks_in, col_specs, mask_specs, sorted_meta = _place_inputs(
        db, mesh, axis, shard_rels, n_sh
    )

    param_specs = tuple(
        {name: PSpec() for name in p.param_names()} for p in plans
    )
    trace_counter = [0]

    # per-plan demux metadata: scalar refs come out psum-ed (replicated);
    # dictionary results concatenate key-disjoint shard slices unless the
    # legalizer proved them replicated
    kinds, out_specs = [], []
    for sp_, props in zip(splans, propss):
        rn = (
            sp_.node_defining(sp_.result) if sp_.result is not None else None
        )
        if rn is None or isinstance(rn, cplan.Reduce):
            kinds.append(("refs", None))
            out_specs.append(PSpec())
        else:
            replicated = isinstance(props.get(sp_.result), cplan.Replicated)
            kinds.append(("dict", getattr(rn, "choice", None)))
            out_specs.append(
                (
                    PSpec() if replicated else PSpec(axis),
                    PSpec(None, None) if replicated else PSpec(axis, None),
                    PSpec() if replicated else PSpec(axis),
                )
            )

    def body(cols, masks, pvals_list):
        trace_counter[0] += 1  # python side effect: fires per trace only
        local_db = {}
        for rel in cols:
            n = next(iter(cols[rel].values())).shape[0]
            local_db[rel] = Table(
                cols[rel], n, mask=masks[rel], sorted_on=sorted_meta[rel]
            )
        outs = E.execute_shared_plan(
            shared,
            local_db,
            sigma=None,
            allow_sorted=False,
            params_list=list(pvals_list),
            exchange_impl=functools.partial(_plan_exchange, axis=axis),
            repartition_impl=functools.partial(_plan_repartition, axis=axis),
        )
        flat = []
        for (kind, _), out in zip(kinds, outs):
            if kind == "refs":
                flat.append(out)
            else:
                ks, vs, valid = out.arrays()
                flat.append((ks, vs, valid.astype(jnp.int32)))
        return tuple(flat)

    wrapped = jax.jit(
        compat.shard_map(
            body,
            mesh=mesh,
            in_specs=(col_specs, mask_specs, param_specs),
            out_specs=tuple(out_specs),
        )
    )

    report_state = {"trace": None, "seen": 0}

    def run(params_list=None):
        params_list = list(params_list or [None] * len(plans))
        coerced = tuple(
            E.coerce_bindings(p, params_list[i]) for i, p in enumerate(plans)
        )
        t0 = time.perf_counter()
        flat = jax.block_until_ready(wrapped(cols_in, masks_in, coerced))
        wall = time.perf_counter() - t0
        if trace_counter[0] != report_state["seen"]:
            report_state["trace"] = E.last_report()
            report_state["seen"] = trace_counter[0]
        run.last_report = E.republish_report(
            report_state["trace"], wall, trace_counter[0], shards=n_sh
        )
        res = []
        for (kind, choice), o in zip(kinds, flat):
            if kind == "refs":
                res.append(o)
            else:
                ks, vs, valid = o
                res.append(
                    ShardedDictResult(
                        choice.ds if choice is not None else "ht_linear",
                        ks, vs, valid.astype(bool),
                    )
                )
        return res

    run.trace_counter = trace_counter
    run.last_report = None
    run.shared_plan = shared
    return run


def execute_plan_sharded(
    plan,
    db,
    mesh: jax.sharding.Mesh,
    axis: Axis,
    shard_rels: Tuple[str, ...] = ("lineitem",),
    params=None,
    sigma=None,
    fuse: bool = True,
):
    """Build-and-run convenience over :func:`sharded_executor` (which see).
    Callers timing repeated executions should hold on to the executor (or go
    through :func:`cached_sharded_executor`) — each ``execute_plan_sharded``
    call builds a fresh shard_map wrapper."""
    return sharded_executor(
        plan, db, mesh, axis, shard_rels, sigma=sigma, fuse=fuse
    )(params)


class ShardedExecutable:
    """``engine.Executable``-interface adapter over a sharded ``run``
    callable, so ``Session``/``QueryServer`` drive sharded and single-shard
    shapes through one calling convention ``ex(db, params)``.

    The underlying executor closes over the build-time column arrays, so
    the ``db`` argument is interface parity only (asserted to be the same
    database when provided).  ``call_batched`` executes the batch as B warm
    launches of the one cached ``shard_map`` trace — collectives cannot
    ride ``vmap``, so a sharded micro-batch amortizes the *trace*, not the
    dispatch; the server's retry/deadline machinery is unchanged."""

    #: batched calls re-enter one trace sequentially (no vmapped twin), so
    #: ``QueryServer.warm_up`` skips tracing power-of-two batch buckets
    vmapped_batches = False

    def __init__(self, run, db=None):
        self._run = run
        self._db = db
        self.calls = 0

    @property
    def fused_regions(self) -> int:
        return getattr(self._run, "fused_regions", 0)

    @property
    def n_shards(self) -> int:
        return getattr(self._run, "n_shards", 1)

    @property
    def trace_count(self) -> int:
        return self._run.trace_counter[0]

    @property
    def inputs(self):
        """``(cols, masks)`` as placed on the mesh at build, by relation."""
        return self._run.inputs

    @property
    def last_report(self):
        return getattr(self._run, "last_report", None)

    def __call__(self, db=None, params=None):
        assert db is None or self._db is None or db is self._db, (
            "sharded executables close over their build-time database"
        )
        self.calls += 1
        return self._run(params)

    def call_batched(self, db, params_list):
        return [self(db, p) for p in params_list]


_SHARDED_CACHE: Dict[tuple, Tuple[object, object]] = {}
_SHARDED_CACHE_STATS = {"hits": 0, "misses": 0}
_SHARDED_CACHE_MAX = 32


def cached_sharded_executor(
    plan,
    db,
    mesh: jax.sharding.Mesh,
    axis: Axis,
    shard_rels: Tuple[str, ...] = ("lineitem",),
    sigma=None,
    fuse: bool = True,
):
    """Distributed twin of ``engine.cached_executable``: the built (jitted
    shard_map) executor is cached by (plan fingerprint, DictChoice tuple,
    table schema, database identity, Σ signature, mesh shape, axis, sharded
    relations), so repeated requests with fresh parameter bindings reuse the
    existing trace.  Unlike the single-shard executable (which takes the arrays per
    call), the sharded executor closes over the build-time column arrays —
    so the db rides in the key by *identity*, held strongly and re-verified
    on hit (a bare ``id()`` could alias a recycled address)."""
    from repro.core import plan as cplan
    from repro.exec import engine as E

    bound = None
    if isinstance(plan, cplan.BoundPlan):
        bound = plan.binding_map()
        plan = plan.plan
    key = (
        plan.fingerprint(),
        plan.choices,
        id(db),
        E._db_signature(db),
        E._sigma_signature(sigma),  # Σ drives the fuse pass
        tuple(sorted(mesh.shape.items())),
        axis if isinstance(axis, str) else tuple(axis),
        tuple(shard_rels),
        fuse,  # the materialized-sharded ladder rung is its own trace
    )
    hit = _SHARDED_CACHE.get(key)
    if hit is not None and hit[0] is db:
        _SHARDED_CACHE_STATS["hits"] += 1
        run = hit[1]
    else:
        _SHARDED_CACHE_STATS["misses"] += 1
        # injection point: cold sharded executable construction — same
        # retry contract as the single-shard ``compile`` point (fires
        # before the cache insert, so a failed build leaves no entry)
        _faults.check("compile", detail=f"sharded {str(plan.fingerprint())[:32]}")
        run = sharded_executor(
            plan, db, mesh, axis, shard_rels, sigma=sigma, fuse=fuse
        )
        if len(_SHARDED_CACHE) >= _SHARDED_CACHE_MAX:
            _SHARDED_CACHE.pop(next(iter(_SHARDED_CACHE)))
        _SHARDED_CACHE[key] = (db, run)
    if bound is None:
        return run

    # a BoundPlan shares the underlying plan's cached trace; its bindings
    # become call-time defaults
    def bound_run(params=None):
        return run({**bound, **(params or {})})

    bound_run.trace_counter = run.trace_counter
    bound_run.inputs = run.inputs
    bound_run.fused_regions = run.fused_regions
    bound_run.n_shards = run.n_shards
    return bound_run


# ---------------------------------------------------------------------------
# low-cardinality aggregate: all-reduce instead of shuffle
# ---------------------------------------------------------------------------


def dist_groupby_lowcard_shard(
    keys: jax.Array,  # [n_local] dense group ids in [0, n_groups), PAD = dead
    vals: jax.Array,  # [n_local, V]
    *,
    axis: Axis,
    n_groups: int,
) -> Tuple[jax.Array, jax.Array]:
    """When the group count is tiny (Q1: 6 groups), shuffling is silly: each
    shard scatter-adds into a dense [n_groups, V] accumulator and one
    all-reduce(+) finishes the job.  Group alignment is by dense id, so
    shards with missing groups stay consistent.  The cost model's collective
    term picks between this and the shuffle form (DESIGN.md §4)."""
    valid = keys != dbase.PAD
    safe = jnp.where(valid, keys, n_groups)
    acc = jnp.zeros((n_groups, vals.shape[-1]), vals.dtype).at[safe].add(
        jnp.where(valid[:, None], vals, 0.0), mode="drop"
    )
    cnt = jnp.zeros((n_groups,), jnp.int32).at[safe].add(
        valid.astype(jnp.int32), mode="drop"
    )
    return lax.psum(acc, axis), lax.psum(cnt, axis)
