"""Vectorized physical operators — the generated-engine runtime.

DBFlex emits specialized C++ per query; here the "generated engine" is a
composition of these jit-compatible operators, parameterized by the
dictionary choices the synthesizer made.  Static shapes throughout:
selection is masking (never compaction), joins are FK index-gathers with
found-masks, group-bys are fixed-capacity dictionary builds.

The ds-dispatch points (`build_dict`, `lookup_dict`) are where the paper's
`@ht`/`@st` annotations become machine behaviour:

* ``ht_*``     — scatter/probe hash aggregation (TPU: hash_probe kernel);
* ``st_*``     — sort + segment reduction       (TPU: segment_reduce kernel);
* ``assume_sorted`` build — skips the sort (the paper's hinted insert);
* ``sorted_probes`` lookup — merge windows      (TPU: merge_lookup kernel).
"""
from __future__ import annotations

import time

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import errors as _errors
from repro.dicts import base as dbase
from repro.dicts import registry
from repro.kernels import ops as kops
from repro.data.table import Table
from repro.testing import faults as _faults


def host_items(keys, vals, valid) -> Dict[int, np.ndarray]:
    """The ``{key: value row}`` host view of a dictionary's slot arrays.

    One ``jax.device_get`` fetches the three arrays (their copies overlap);
    the valid slots are compacted with numpy, so Python touches each kept
    entry once and never an empty slot.  Keys are Python ``int`` in slot
    order (dictionary keys are int32); each value is a row of the compacted
    host copy, with the slot array's dtype and row shape."""
    ks, vs, valid = jax.device_get((keys, vals, valid))
    m = valid.astype(bool)
    return dict(zip(ks[m].tolist(), vs[m]))


@dataclass
class DictResult:
    """A materialized LLQL dictionary: backend table + its annotation."""

    ds: str
    table: object  # HashTable | SortedTable

    def items_np(self) -> Dict[int, np.ndarray]:
        return host_items(*self.arrays())

    def arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return registry.get(self.ds).items(self.table)

    def size(self) -> int:
        return int(registry.get(self.ds).size(self.table))


def _safe_gather(a: jax.Array, idx: jax.Array) -> jax.Array:
    """``a[idx]`` tolerant of zero-row gather sources.  A gather from an
    empty relation only ever happens under an all-false found mask (nothing
    can match an empty build side), so indexing a one-row zero pad instead
    is semantics-preserving — XLA's gather itself rejects slice size 1 on a
    0-length axis."""
    if a.shape[0] == 0:
        a = jnp.zeros((1,) + a.shape[1:], a.dtype)
    return a[idx]


def capacity_for(ds: str, n_distinct: int) -> int:
    """Static capacity: 2× slack for hash load factor / merge headroom
    (the rule itself lives in ``dicts.base.default_capacity`` — shared with
    the fusion cost model's VMEM estimates)."""
    return dbase.default_capacity(n_distinct)


# ---------------------------------------------------------------------------
# dictionary build / probe with ds dispatch
# ---------------------------------------------------------------------------


import functools


@functools.lru_cache(maxsize=None)
def _jit_build(
    ds: str,
    capacity: int,
    assume_sorted: bool,
    has_valid: bool,
    ops: Optional[Tuple[str, ...]] = None,
):
    mod = registry.get(ds)
    # all-sum lanes take the exact legacy call (third-party backends need not
    # know about ops); min/max lanes dispatch the semiring-aware build
    kw = {} if dbase.all_sum(ops) else {"ops": ops}
    if has_valid:
        fn = lambda k, v, m: mod.build(
            k, v, capacity, assume_sorted=assume_sorted, valid=m, **kw
        )
    else:
        fn = lambda k, v: mod.build(
            k, v, capacity, assume_sorted=assume_sorted, **kw
        )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _jit_lookup(ds: str, has_valid: bool):
    mod = registry.get(ds)
    if has_valid:
        return jax.jit(lambda t, q, m: mod.lookup(t, q, valid=m))
    return jax.jit(lambda t, q: mod.lookup(t, q))


def build_dict(
    ds: str,
    keys: jax.Array,
    vals: jax.Array,
    capacity: int,
    valid: Optional[jax.Array] = None,
    assume_sorted: bool = False,
    ops: Optional[Tuple[str, ...]] = None,
) -> DictResult:
    # injection point: dictionary construction (fires at trace time when the
    # build runs inside a jitted region — models cold-path build failures)
    _faults.check("dict-build", detail=ds)
    ops = None if dbase.all_sum(ops) else tuple(ops)
    with jax.named_scope(f"{SCOPE_BUILD}.{ds}"):
        if valid is not None:
            # masked rows become PAD holes; the sorted fast path survives the
            # mask (dicts.base.build_sorted dedupes sorted-with-holes exactly)
            t = _jit_build(ds, capacity, assume_sorted, True, ops)(keys, vals, valid)
        else:
            t = _jit_build(ds, capacity, assume_sorted, False, ops)(keys, vals)
    return DictResult(ds, t)


def lookup_dict(
    d: DictResult,
    queries: jax.Array,
    valid: Optional[jax.Array] = None,
    sorted_probes: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(vals[n, V], found[n]).  ``sorted_probes`` routes sort-family lookups
    through the merge path (the paper's hinted lookup)."""
    with jax.named_scope(f"{SCOPE_PROBE}.{d.ds}"):
        if d.ds.startswith("st") and sorted_probes:
            vals, found = kops.merge_lookup(d.table.keys, d.table.vals, queries)
            if valid is not None:
                found = found & valid.astype(bool)
                vals = jnp.where(found[:, None], vals, 0.0)
            return vals, found
        if valid is not None:
            return _jit_lookup(d.ds, True)(d.table, queries, valid)
        return _jit_lookup(d.ds, False)(d.table, queries)


# ---------------------------------------------------------------------------
# relational operators
# ---------------------------------------------------------------------------


def groupby(
    table: Table,
    keys: jax.Array,
    vals: jax.Array,
    ds: str,
    capacity: int,
    assume_sorted: bool = False,
    ops: Tuple[str, ...] = (),
) -> DictResult:
    """Group-by aggregate (Fig. 6c/6d): dict[key] ⊕= val, where ⊕ is each
    lane's combine monoid (``ops``; empty = all-sum, the legacy path).  Bag
    multiplicity only multiplies additive lanes — min/max are idempotent
    over duplicates."""
    if vals.ndim == 1:
        vals = vals[:, None]
    mult = table.multiplicity()[:, None]
    if dbase.all_sum(ops):
        vals = vals * mult
    else:
        sel = jnp.asarray([o == "sum" for o in ops])
        vals = jnp.where(sel[None, :], vals * mult, vals)
    return build_dict(
        ds, keys, vals, capacity, valid=table.mask,
        assume_sorted=assume_sorted, ops=ops,
    )


def scalar_aggregate(
    table: Table, vals: jax.Array, ops: Tuple[str, ...] = ()
) -> jax.Array:
    """Per-lane combine over live rows; vals [n, V] -> [V].  All-sum (the
    default) keeps the historical Σ with bag multiplicity; min/max lanes
    reduce over identity-masked rows (multiplicity is irrelevant there)."""
    if vals.ndim == 1:
        vals = vals[:, None]
    if dbase.all_sum(ops):
        return jnp.sum(vals * table.multiplicity()[:, None], axis=0)
    live = table.live_mask()
    mult = table.multiplicity()
    lanes = []
    for j, op in enumerate(ops):
        col = vals[:, j]
        if op == "sum":
            lanes.append(jnp.sum(col * mult, axis=0))
        elif op == "min":
            lanes.append(jnp.min(jnp.where(live, col, jnp.inf), axis=0))
        else:
            lanes.append(jnp.max(jnp.where(live, col, -jnp.inf), axis=0))
    return jnp.stack(lanes)


def build_index(
    ds: str,
    keys: jax.Array,
    capacity: int,
    valid: Optional[jax.Array] = None,
    assume_sorted: bool = False,
) -> DictResult:
    """Key -> row-index dictionary for FK joins.  Row indices ride in the
    float32 value lane (exact to 2^24 rows; asserted)."""
    n = keys.shape[0]
    assert n < (1 << 24), "index payload exceeds f32 exactness"
    idx = jnp.arange(n, dtype=jnp.float32)[:, None]
    return build_dict(ds, keys, idx, capacity, valid=valid, assume_sorted=assume_sorted)


def fk_join(
    left: Table,
    left_keys: jax.Array,
    right: Table,
    index: DictResult,
    take: Sequence[str],
    sorted_probes: bool = False,
    prefix: str = "",
) -> Table:
    """Key/foreign-key join: probe ``index`` (built on the unique side) with
    ``left_keys``; gather ``take`` columns from ``right``.  Output keeps the
    left table's static shape; non-matching rows are masked out."""
    vals, found = lookup_dict(
        index, left_keys, valid=left.mask, sorted_probes=sorted_probes
    )
    ridx = vals[:, 0].astype(jnp.int32)
    ridx = jnp.where(found, ridx, 0)
    cols = dict(left.columns)
    for c in take:
        cols[prefix + c] = jnp.where(
            found, _safe_gather(right.col(c), ridx),
            jnp.zeros((), right.col(c).dtype),
        )
    return Table(cols, left.nrows, mask=found, sorted_on=left.sorted_on)


def semijoin(
    left: Table, left_keys: jax.Array, index: DictResult, sorted_probes: bool = False
) -> Table:
    _, found = lookup_dict(index, left_keys, valid=left.mask, sorted_probes=sorted_probes)
    return left.with_mask(found)


def groupjoin(
    r_table: Table,
    r_keys: jax.Array,
    f_vals: jax.Array,  # [n, V] partial aggregate from R rows
    s_dict: DictResult,  # key -> partial aggregate of S (g)
    out_ds: str,
    out_capacity: int,
    combine: str = "mul",  # how f and g combine per Fig. 6e: f(r) * g_sum
    sorted_probes: bool = False,
    assume_sorted: bool = False,
) -> DictResult:
    """Fig. 6e/6f compound groupjoin: Agg[k] += f(r) * Sd(k)."""
    g_vals, found = lookup_dict(
        s_dict, r_keys, valid=r_table.mask, sorted_probes=sorted_probes
    )
    if f_vals.ndim == 1:
        f_vals = f_vals[:, None]
    if combine == "mul":
        v = f_vals * g_vals
    else:  # pragma: no cover
        raise ValueError(combine)
    tbl = r_table.with_mask(found)
    return groupby(tbl, r_keys, v, out_ds, out_capacity, assume_sorted=assume_sorted)


# ---------------------------------------------------------------------------
# physical-plan executor (single shard)
# ---------------------------------------------------------------------------


@dataclass
class Frame:
    """Aligned row bindings of a plan pipeline: every bound loop variable maps
    to a table with the same static row count and (conceptually) the same
    mask — Select/probe masks are applied to all members."""

    tables: Dict[str, "Table"]
    order: Tuple[str, ...]
    rels: Dict[str, Optional[str]]  # var -> base relation name (None: derived)

    @property
    def primary(self) -> "Table":
        return self.tables[self.order[0]]

    def with_mask(self, m: jax.Array) -> "Frame":
        return Frame(
            {v: t.with_mask(m) for v, t in self.tables.items()},
            self.order,
            self.rels,
        )


@dataclass
class BuiltDict:
    """A dictionary materialized by a plan node, plus what probes need:
    value-lane names (Reduce field resolution) and, for join indices, the
    source table the stored row-ids point into."""

    res: DictResult
    choice: object  # DictChoice
    lanes: Tuple[str, ...] = ()
    kind: str = "agg"  # "agg" | "index"
    src: Optional["Table"] = None  # index only: gather target


def _dict_scan_table(d: BuiltDict) -> "Table":
    from repro.core.lower import DICT_KEY, DICT_VAL

    ks, vs, valid = d.res.arrays()
    cols = {DICT_KEY: ks}
    for i in range(vs.shape[1]):
        cols[DICT_VAL if i == 0 else f"{DICT_VAL}{i}"] = vs[:, i]
    sorted_on = (DICT_KEY,) if d.res.ds.startswith("st") else ()
    return Table(cols, ks.shape[0], mask=valid.astype(bool), sorted_on=sorted_on)


def _key_info(frame: Frame, keyexpr) -> Tuple[Optional[str], Tuple[str, ...], bool]:
    """(base relation, key columns, probe/build sequence sorted?) for a key
    expression over the frame."""
    from repro.core.cardinality import key_columns
    from repro.core.lower import DICT_KEY

    for var in frame.order:
        cols = key_columns(keyexpr, var)
        if not cols:
            continue
        t = frame.tables[var]
        if "*" in cols:
            if DICT_KEY in t.columns:  # whole-key of a dict scan
                cols = (DICT_KEY,)
            else:
                return frame.rels.get(var), cols, False
        srt = bool(cols) and t.sorted_on[: len(cols)] == tuple(cols)
        return frame.rels.get(var), cols, srt
    return None, (), False


def _capacity(frame: Frame, keyexpr, ds: str, sigma) -> int:
    rel, cols, _ = _key_info(frame, keyexpr)
    if sigma is not None and rel is not None and cols and "*" not in cols:
        try:
            return capacity_for(ds, int(sigma.dist(rel, cols)))
        except KeyError:
            pass
    return capacity_for(ds, frame.primary.nrows)


def execute_plan(
    plan,
    db: Dict[str, "Table"],
    sigma=None,
    exchange_impl=None,
    repartition_impl=None,
    allow_sorted: bool = True,
    params: Optional[Dict[str, object]] = None,
):
    """Run a physical plan (``repro.core.plan``) against a database.

    ``exchange_impl`` realizes Exchange nodes (the sharded executor passes the
    all-to-all merge) and ``repartition_impl`` realizes Repartition nodes
    (hash-route / all-gather of frame rows); on a single shard both are the
    identity.  ``allow_sorted=False`` disables the sorted-input/merge fast
    paths — the sharded executor uses it because hinted kernels assume a
    global sort the shards no longer have.  ``params`` supplies values for
    the plan's free ``L.Param``s (a ``BoundPlan`` carries its own).
    """
    from repro.core import plan as P

    if isinstance(plan, P.BoundPlan):
        params = {**plan.binding_map(), **(params or {})}
        plan = plan.plan

    env: Dict[str, object] = {}
    refs: Dict[str, object] = {}

    rep = _begin_report()
    t_plan = time.perf_counter()
    try:
        for node in plan.nodes:
            _exec_node(
                node, env, refs, db, sigma, allow_sorted, params,
                exchange_impl, repartition_impl,
            )

        if plan.result is not None and isinstance(
            env.get(plan.result), _PendingStream
        ):
            env[plan.result].force(env, refs, sigma, allow_sorted, params)

        return _plan_result(plan, env, refs)
    finally:
        _end_report(rep, time.perf_counter() - t_plan)


def _plan_result(plan, env, refs):
    if plan.result is None:
        if len(refs) == 1:
            return next(iter(refs.values()))
        return refs
    if plan.result in refs:
        return refs[plan.result]
    out = env.get(plan.result)
    if isinstance(out, BuiltDict):
        return out.res
    return out


def _exec_node(
    node,
    env,
    refs,
    db,
    sigma,
    allow_sorted,
    params,
    exchange_impl=None,
    repartition_impl=None,
):
    """Execute ONE plan node against (env, refs) — the executor's dispatch,
    factored out so the shared-scan scheduler (``execute_shared_plan``) can
    interleave nodes from several plans around their shared regions."""
    from repro.core import plan as P
    from repro.core.lower import compile_rowfn_frame as _rowfn_frame

    def compile_rowfn_frame(x, tables):
        return _rowfn_frame(x, tables, params)

    def frame_of(sym: str) -> Frame:
        v = env[sym]
        assert isinstance(v, Frame), f"{sym} is not a row frame"
        p0 = v.tables[v.order[0]]
        if isinstance(p0, _PendingStream):  # bare-node consumer: spill
            p0 = p0.force(env, refs, sigma, allow_sorted, params)
        if _is_chunked(p0):  # bare-node fallback: materialize the relation
            v = Frame({**v.tables, v.order[0]: p0.decode()}, v.order, v.rels)
            env[sym] = v
        return v

    if isinstance(node, P.Scan):
        if node.source in env:
            src = env[node.source]
            if isinstance(src, BuiltDict):
                t, rel = _dict_scan_table(src), None
            elif (
                isinstance(src, (Table, _PendingStream)) or _is_chunked(src)
            ):
                t, rel = src, None
            else:
                raise TypeError(f"cannot scan {node.source}")
        else:
            t, rel = db[node.source], node.source
        env[node.out] = Frame({node.var: t}, (node.var,), {node.var: rel})

    elif isinstance(node, P.Select):
        f = frame_of(node.source)
        m = compile_rowfn_frame(node.pred, f.tables)
        env[node.out] = f.with_mask(jnp.asarray(m, bool))

    elif isinstance(node, P.Project):
        from repro.core import llql as L

        f = frame_of(node.source)
        n = f.primary.nrows
        cols = {}
        sorted_on: Tuple[str, ...] = ()
        for name, fx in node.fields:
            col = jnp.asarray(compile_rowfn_frame(fx, f.tables))
            cols[name] = jnp.broadcast_to(col, (n,))
            # physical row order is the probe side's: an identity copy of
            # a sort-leading column keeps its orderedness
            if (
                not sorted_on
                and isinstance(fx, L.FieldAccess)
                and isinstance(fx.rec, L.FieldAccess)
                and fx.rec.name == "key"
                and isinstance(fx.rec.rec, L.Var)
                and fx.rec.rec.name in f.tables
                and f.tables[fx.rec.rec.name].sorted_on[:1] == (fx.name,)
            ):
                sorted_on = (name,)
        env[node.out] = Table(cols, n, mask=f.primary.mask, sorted_on=sorted_on)

    elif isinstance(node, P.HashBuild):
        f = frame_of(node.source)
        keys = jnp.asarray(
            compile_rowfn_frame(node.keyexpr, f.tables), jnp.int32
        )
        _, _, srt = _key_info(f, node.keyexpr)
        srt = srt and allow_sorted
        cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
        d = build_index(
            node.choice.ds,
            keys,
            cap,
            valid=f.primary.mask,
            assume_sorted=srt and (node.choice.hinted or node.hinted),
        )
        env[node.out] = BuiltDict(d, node.choice, kind="index", src=f.primary)

    elif isinstance(node, P.HashProbe):
        f = frame_of(node.source)
        b = env[node.build]
        assert isinstance(b, BuiltDict) and b.kind == "index", node.build
        keys = jnp.asarray(
            compile_rowfn_frame(node.keyexpr, f.tables), jnp.int32
        )
        _, _, srt = _key_info(f, node.keyexpr)
        srt = srt and allow_sorted
        vals, found = lookup_dict(
            b.res,
            keys,
            valid=f.primary.mask,
            sorted_probes=srt and (node.hinted or b.choice.hinted),
        )
        ridx = jnp.where(found, vals[:, 0].astype(jnp.int32), 0)
        src_t = b.src
        gcols = {
            c: jnp.where(
                found, _safe_gather(src_t.col(c), ridx),
                jnp.zeros((), src_t.col(c).dtype),
            )
            for c in src_t.names()
        }
        gathered = Table(gcols, f.primary.nrows, mask=found)
        masked = f.with_mask(found)
        env[node.out] = Frame(
            {**masked.tables, node.inner_var: gathered},
            masked.order + (node.inner_var,),
            {**masked.rels, node.inner_var: None},
        )

    elif isinstance(node, P.GroupBy):
        fv = env[node.source]
        if isinstance(fv, Frame) and _is_chunked(fv.tables[fv.order[0]]):
            # bare group-by over a chunked relation: run it as a one-stage
            # streamed region (same fold machinery as fused pipelines)
            v0 = fv.order[0]
            _run_streamed_pipeline(
                node, [node], fv.tables[v0], v0, fv.rels.get(v0), env,
                refs, db, sigma, allow_sorted, params,
                P.needed_columns((node,)),
            )
            return
        f = frame_of(node.source)
        n = f.primary.nrows
        keys = jnp.asarray(
            compile_rowfn_frame(node.keyexpr, f.tables), jnp.int32
        )
        _, _, srt = _key_info(f, node.keyexpr)
        srt = srt and allow_sorted
        lanes = [
            jnp.broadcast_to(
                jnp.asarray(compile_rowfn_frame(fx, f.tables), jnp.float32),
                (n,),
            )
            for _, fx in node.values
        ]
        vals = jnp.stack(lanes, axis=1)
        cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
        d = groupby(
            f.primary,
            keys,
            vals,
            node.choice.ds,
            cap,
            assume_sorted=srt and (node.choice.hinted or node.hinted),
            ops=tuple(node.ops),
        )
        env[node.out] = BuiltDict(
            d, node.choice, lanes=tuple(a for a, _ in node.values)
        )

    elif isinstance(node, P.GroupJoin):
        f = frame_of(node.source)
        b = env[node.build]
        assert isinstance(b, BuiltDict), node.build
        n = f.primary.nrows
        keys = jnp.asarray(
            compile_rowfn_frame(node.keyexpr, f.tables), jnp.int32
        )
        _, _, srt = _key_info(f, node.keyexpr)
        srt = srt and allow_sorted
        f_vals = jnp.broadcast_to(
            jnp.asarray(compile_rowfn_frame(node.f_expr, f.tables), jnp.float32),
            (n,),
        )
        cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
        d = groupjoin(
            f.primary,
            keys,
            f_vals[:, None],
            b.res,
            node.choice.ds,
            cap,
            sorted_probes=srt and (node.hinted or b.choice.hinted),
            assume_sorted=srt and node.choice.hinted,
        )
        env[node.out] = BuiltDict(d, node.choice, lanes=("_0",))

    elif isinstance(node, P.Reduce):
        f = frame_of(node.source)
        lanes: Tuple[str, ...] = ("m", "c", "c_c")
        lookup_vals = None
        if node.lookup_sym is not None:
            b = env[node.lookup_sym]
            assert isinstance(b, BuiltDict), node.lookup_sym
            lanes = b.lanes or lanes
            keys = jnp.asarray(
                compile_rowfn_frame(node.lookup_key, f.tables), jnp.int32
            )
            _, _, srt = _key_info(f, node.lookup_key)
            srt = srt and allow_sorted
            lookup_vals, found = lookup_dict(
                b.res,
                keys,
                valid=f.primary.mask,
                sorted_probes=srt and b.choice.hinted,
            )
            f = f.with_mask(found)
        fops = node.ops or ("sum",) * len(node.fields)
        total = {}
        for k, (name, fx) in enumerate(node.fields):
            col = _reduce_field(
                fx, f, node.lookup_var, lookup_vals, lanes, params=params
            )
            total[name] = scalar_aggregate(f.primary, col, ops=(fops[k],))[0]
        refs[node.out] = total

    elif isinstance(node, P.Pipeline):
        _run_pipeline(node, env, refs, db, sigma, allow_sorted, params)

    elif isinstance(node, P.Repartition):
        if repartition_impl is not None:
            env[node.out] = repartition_impl(
                node, frame_of(node.source), params=params
            )
        else:  # single shard: identity (rows already all "here")
            env[node.out] = env[node.source]

    elif isinstance(node, P.Exchange):
        if exchange_impl is not None:
            if node.kind == "shuffle":
                env[node.out] = exchange_impl(node, env[node.source])
            else:  # allreduce over a scalar ref record
                refs[node.source] = exchange_impl(node, refs[node.source])
        else:  # single shard: identity
            if node.source in env:
                env[node.out] = env[node.source]

    else:  # pragma: no cover
        raise AssertionError(node)


# ---------------------------------------------------------------------------
# structured execution telemetry (DESIGN.md §11)
# ---------------------------------------------------------------------------

#: Names under which the served path shows in a ``jax.profiler`` trace; the
#: benchmark's per-layer metrics and idle-gap labels read them from here.
#: Host spans (``jax.profiler.TraceAnnotation``), on the thread that calls
#: ``QueryServer.step()``:
SPAN_DISPATCH = "serve.dispatch"  # enqueue a batch's device work
SPAN_SYNC = "serve.sync"  # wait until the batch's outputs are ready
SPAN_CONVERT = "serve.convert"  # device-to-host copy and Python result dicts
#: Device scopes (``jax.named_scope``).  They reach only the HLO ops'
#: ``op_name`` metadata (a TPU trace's ``tf_op`` stat), never the code.
SCOPE_BUILD = "dict.build"  # ``dict.build.<ds>``: the body of build_dict
SCOPE_PROBE = "dict.probe"  # ``dict.probe.<ds>``: the body of lookup_dict
SCOPE_DECODE = "stream.decode"  # in-trace decode of a streamed chunk


@dataclass
class RegionRecord:
    """Telemetry for ONE fused region, keyed by its terminal symbol.

    ``mode`` is the execution path that produced the region's result
    ("xla", "xla-radix-planned", "kernel-resident", "kernel-radix",
    "streamed:N", "streamed-chained:N", "streamed-kernel:N",
    "streamed-deferred", "shared:N"); ``family`` is the terminal
    dictionary's ds annotation when the terminal builds one.  The call's
    wall time lives on the report."""

    sym: str
    mode: str = ""
    family: str = ""
    chunks: int = 0
    h2d_bytes: int = 0


@dataclass
class ExecutionReport:
    """Structured per-execution telemetry, attached to every
    ``execute_plan`` / ``execute_shared_plan`` / sharded call.

    ``regions`` maps each fused region's terminal symbol to its
    :class:`RegionRecord`; the scalar fields aggregate the streaming ledger
    for the whole execution (deterministic byte arithmetic: ``h2d_bytes``
    the encoded payload bytes that crossed the host→device link,
    ``peak_chunk_bytes`` the largest decoded working set a streamed region
    held on device at once, ``peak_state_bytes`` the largest carried
    accumulator state).  ``wall_s`` is the wall time of the call that
    produced the report (dispatch only, for the asynchronous jitted path);
    ``traced`` marks reports whose region detail was captured at trace time
    (jitted resident path) and republished per call."""

    regions: Dict[str, RegionRecord] = field(default_factory=dict)
    wall_s: float = 0.0
    chunks: int = 0
    h2d_bytes: int = 0
    peak_chunk_bytes: int = 0
    peak_state_bytes: int = 0
    streamed_regions: int = 0
    trace_count: int = 0
    shards: int = 1
    traced: bool = False
    # fault-tolerance ledger (DESIGN.md §12) — stamped by Session/QueryServer
    faults: int = 0  # typed faults observed while producing this result
    retries: int = 0  # same-mode retry attempts consumed
    degraded: int = 0  # ladder rungs descended (0 = primary mode)
    shed: int = 0  # requests shed by admission/deadline in the same round
    degradation: str = ""  # final rung when degraded ("materialized"|"streamed")

    def modes(self) -> Dict[str, str]:
        """``{terminal symbol: execution mode}``."""
        return {s: r.mode for s, r in self.regions.items()}

    def mode(self, sym: str, default: str = "") -> str:
        rec = self.regions.get(sym)
        return rec.mode if rec is not None else default

    def region(self, sym: str) -> Optional[RegionRecord]:
        return self.regions.get(sym)

    def copy(self) -> "ExecutionReport":
        rep = ExecutionReport(
            regions={
                s: RegionRecord(r.sym, r.mode, r.family, r.chunks, r.h2d_bytes)
                for s, r in self.regions.items()
            },
        )
        for f in (
            "wall_s", "chunks", "h2d_bytes", "peak_chunk_bytes",
            "peak_state_bytes", "streamed_regions", "trace_count", "shards",
            "traced", "faults", "retries", "degraded", "shed", "degradation",
        ):
            setattr(rep, f, getattr(self, f))
        return rep

    def summary(self) -> str:
        parts = [f"wall={self.wall_s * 1e3:.2f}ms"]
        if self.shards > 1:
            parts.append(f"shards={self.shards}")
        if self.chunks:
            parts.append(
                f"chunks={self.chunks} h2d={self.h2d_bytes >> 10}KiB"
            )
        if self.degraded:
            parts.append(f"degraded={self.degradation or '?'}")
        if self.faults or self.retries:
            parts.append(f"faults={self.faults} retries={self.retries}")
        lines = [" ".join(parts)]
        for s, r in self.regions.items():
            lines.append(f"  {s}: {r.mode}" + (f" [{r.family}]" if r.family else ""))
        return "\n".join(lines)


_ACTIVE_REPORTS: List[ExecutionReport] = []
_LAST_REPORT = ExecutionReport()


def last_report() -> ExecutionReport:
    """The ExecutionReport of the most recent execution in this process —
    an ``execute_plan`` / ``execute_shared_plan`` call or an executable /
    sharded-executor dispatch (which republish their trace-time report
    with the measured per-call wall time)."""
    return _LAST_REPORT


def publish_report(rep: ExecutionReport) -> ExecutionReport:
    """Install ``rep`` as ``last_report()`` (used by executables and the
    sharded executor to surface per-call reports)."""
    global _LAST_REPORT
    _LAST_REPORT = rep
    return rep


def republish_report(
    base: Optional[ExecutionReport],
    wall_s: float,
    trace_count: int = 0,
    shards: int = 1,
) -> ExecutionReport:
    """Copy a trace-time report and publish it with this call's measured
    wall time — the jitted resident path replays a compiled function, so
    region structure is static per shape while wall time is per call."""
    rep = base.copy() if base is not None else ExecutionReport()
    rep.traced = base is not None
    rep.wall_s = wall_s
    rep.trace_count = trace_count
    rep.shards = shards
    return publish_report(rep)


def _begin_report() -> ExecutionReport:
    rep = ExecutionReport()
    _ACTIVE_REPORTS.append(rep)
    return rep


def _end_report(rep: ExecutionReport, wall_s: float) -> None:
    if rep in _ACTIVE_REPORTS:
        _ACTIVE_REPORTS.remove(rep)
    rep.wall_s = wall_s
    publish_report(rep)


def _record_region(
    sym: str,
    mode: str,
    family: str = "",
    chunks: int = 0,
    h2d_bytes: int = 0,
) -> None:
    """Write one region's telemetry to the active report."""
    if _ACTIVE_REPORTS:
        rep = _ACTIVE_REPORTS[-1]
        rec = rep.regions.get(sym)
        if rec is None:
            rec = rep.regions[sym] = RegionRecord(sym=sym)
        rec.mode = mode
        if family:
            rec.family = family
        rec.chunks += chunks
        rec.h2d_bytes += h2d_bytes


def _account_stream(
    regions: int = 0,
    chunks: int = 0,
    h2d_bytes: int = 0,
    peak_chunk_bytes: int = 0,
    peak_state_bytes: int = 0,
) -> None:
    """Update the streaming ledger on the active report."""
    if _ACTIVE_REPORTS:
        rep = _ACTIVE_REPORTS[-1]
        rep.streamed_regions += regions
        rep.chunks += chunks
        rep.h2d_bytes += h2d_bytes
        rep.peak_chunk_bytes = max(rep.peak_chunk_bytes, peak_chunk_bytes)
        rep.peak_state_bytes = max(rep.peak_state_bytes, peak_state_bytes)


# ---------------------------------------------------------------------------
# out-of-core streaming (DESIGN.md §10)
# ---------------------------------------------------------------------------


def _terminal_family(term) -> str:
    return getattr(getattr(term, "choice", None), "ds", "") or ""


def _is_chunked(x) -> bool:
    from repro.data.storage import is_chunked

    return is_chunked(x)


def _stream_capacity(meta_frame, keyexpr, ds: str, sigma, total_rows: int) -> int:
    """Dictionary capacity for a streamed terminal.  MUST match what the
    resident path would pick (same layout ⇒ bitwise-identical merge): the
    Σ distinct estimate when available, else the TOTAL row count — never the
    per-chunk row count."""
    rel, cols, _ = _key_info(meta_frame, keyexpr)
    if sigma is not None and rel is not None and cols and "*" not in cols:
        try:
            return capacity_for(ds, int(sigma.dist(rel, cols)))
        except KeyError:
            pass
    return capacity_for(ds, total_rows)


def _merge_groupby(table, keys, vals, ds, capacity, state, ops=(),
                   sorted_merge: bool = False):
    """One streamed group-by step: fold a chunk's rows into the carried
    accumulator table.  The carried state's live entries are re-presented as
    (key, value) rows CONCATENATED BEFORE the chunk's rows and rebuilt with
    the unsorted build — XLA's scatter applies duplicate updates in row
    order and the stable sort keeps state rows ahead of same-key chunk rows,
    so the float accumulation order is exactly the resident left-fold:
    bitwise-identical to a one-shot group-by over all rows.

    ``sorted_merge`` (sorted-family dictionaries whose group key IS the
    stream's sort key): the state's live keys are sorted and — because
    chunks are contiguous slices of a key-sorted stream — every state key
    precedes every chunk key, so the state-first concat's live subsequence
    is already nondecreasing (PAD holes allowed anywhere by the
    ``assume_sorted`` contract).  The stable argsort the unsorted build
    would run is the identity permutation on live rows, so skipping it
    feeds ``dedupe_sorted`` the exact same row sequence: bitwise-identical
    output, minus an O((capacity + chunk) log) sort per chunk — the
    dominant cost of streamed sort-dictionary group-bys."""
    if vals.ndim == 1:
        vals = vals[:, None]
    mult = table.multiplicity()[:, None]
    if dbase.all_sum(ops):
        vals = vals * mult
    else:
        sel = jnp.asarray([o == "sum" for o in ops])
        vals = jnp.where(sel[None, :], vals * mult, vals)
    sk, sv = state.keys, state.vals
    svalid = (sk != dbase.PAD) & (sk != dbase.EMPTY)
    mk = jnp.concatenate([jnp.where(svalid, sk, dbase.PAD), keys])
    mv = jnp.concatenate([sv, vals])
    chunk_valid = (
        table.mask if table.mask is not None
        else jnp.ones(keys.shape, bool)
    )
    valid = jnp.concatenate([svalid, chunk_valid])
    return build_dict(
        ds, mk, mv, capacity, valid=valid, assume_sorted=sorted_merge,
        ops=tuple(ops),
    )


class _SortedStreamState(NamedTuple):
    """Carried accumulator of the sorted-stream fast path (a sorted-family
    group-by whose key IS the stream's sort key).  Because chunks are
    contiguous slices of a key-sorted stream, a group is COMPLETE the
    moment the stream moves past its key — so instead of re-scattering a
    full-capacity state every chunk, the fold appends each chunk's
    completed groups to ``out_k``/``out_v`` at the running ``off`` and
    carries only the single still-open boundary group (``bk``/``bv``)."""

    out_k: jax.Array  # [capacity + cap_chunk] emitted unique keys, PAD tail
    out_v: jax.Array  # [capacity + cap_chunk, V]
    off: jax.Array  # scalar: rows of out_k filled so far
    bk: jax.Array  # scalar: open boundary group's key (PAD when none)
    bv: jax.Array  # [V] boundary group's partial fold
    bvalid: jax.Array  # scalar bool


def _sorted_stream_chunk_cap(chunk_rows: int) -> int:
    # distinct keys in a chunk + the seeded boundary row, padded to the
    # st_blocked leaf multiple
    return -(-(chunk_rows + 1) // 128) * 128


def _sorted_stream_init(cap: int, chunk_rows: int, n_lanes: int):
    cc = _sorted_stream_chunk_cap(chunk_rows)
    return _SortedStreamState(
        jnp.full((cap + cc,), dbase.PAD, jnp.int32),
        jnp.zeros((cap + cc, n_lanes), jnp.float32),
        jnp.int32(0),
        jnp.int32(dbase.PAD),
        jnp.zeros((n_lanes,), jnp.float32),
        jnp.asarray(False),
    )


def _sorted_stream_merge(
    table, keys, vals, ds, capacity, state: _SortedStreamState, ops=(),
    final: bool = False,
):
    """One sorted-stream fold step: group the chunk ALONE (O(chunk), no
    capacity-sized work) seeded with the carried boundary partial, emit its
    completed groups, carry the new boundary.

    Bitwise-identical to the resident one-shot build: a group's rows are
    contiguous in the key-sorted stream, and seeding the next chunk's
    build with the boundary partial continues that group's left-fold in
    exactly the resident contribution order (the seed row sits FIRST, so
    ``(…fold so far…) + next row + …`` — never a partial-sum tree).  On
    the ``final`` chunk the boundary is emitted too and the assembled
    unique rows are laid out by one ``assume_sorted`` build at the
    resident capacity — one exact identity-combine per slot."""
    if vals.ndim == 1:
        vals = vals[:, None]
    mult = table.multiplicity()[:, None]
    if dbase.all_sum(ops):
        vals = vals * mult
    else:
        sel = jnp.asarray([o == "sum" for o in ops])
        vals = jnp.where(sel[None, :], vals * mult, vals)
    chunk_valid = (
        table.mask if table.mask is not None
        else jnp.ones(keys.shape, bool)
    )
    cap_chunk = state.out_k.shape[0] - capacity
    mk = jnp.concatenate([state.bk[None], keys])
    mv = jnp.concatenate([state.bv[None, :], vals])
    valid = jnp.concatenate([state.bvalid[None], chunk_valid])
    t = build_dict(
        ds, mk, mv, cap_chunk, valid=valid, assume_sorted=True,
        ops=tuple(ops),
    ).table
    c = t.n if final else jnp.maximum(t.n - 1, 0)
    keep = jnp.arange(cap_chunk, dtype=jnp.int32) < c
    wk = jnp.where(keep, t.keys, dbase.PAD)
    wv = jnp.where(keep[:, None], t.vals, 0.0)
    out_k = jax.lax.dynamic_update_slice(state.out_k, wk, (state.off,))
    out_v = jax.lax.dynamic_update_slice(
        state.out_v, wv, (state.off, jnp.int32(0))
    )
    if final:
        fk = out_k[:capacity]
        return build_dict(
            ds, fk, out_v[:capacity], capacity, valid=fk != dbase.PAD,
            assume_sorted=True, ops=tuple(ops),
        ).table
    has = t.n > 0
    i = jnp.maximum(t.n - 1, 0)
    return _SortedStreamState(
        out_k, out_v, state.off + c,
        jnp.where(has, t.keys[i], dbase.PAD),
        jnp.where(has, t.vals[i], 0.0),
        has,
    )


def _merge_dict_tables(ds, state, partial, capacity, ops=()):
    """Merge a per-chunk partial aggregate dictionary (e.g. from the fused
    kernel) into the carried state — state entries first, same combine
    monoids per lane."""
    sk, sv = state.keys, state.vals
    pk, pv = partial.keys, partial.vals
    v1 = (sk != dbase.PAD) & (sk != dbase.EMPTY)
    v2 = (pk != dbase.PAD) & (pk != dbase.EMPTY)
    mk = jnp.concatenate(
        [jnp.where(v1, sk, dbase.PAD), jnp.where(v2, pk, dbase.PAD)]
    )
    mv = jnp.concatenate([sv, pv])
    return build_dict(
        ds, mk, mv, capacity, valid=jnp.concatenate([v1, v2]),
        assume_sorted=False, ops=tuple(ops),
    ).table


def _empty_dict_state(ds: str, n_lanes: int, capacity: int, ops=()):
    """Jit-stable zero-entry accumulator table (an all-invalid build) to
    seed the streamed fold — its shapes equal every later merge's."""
    return build_dict(
        ds,
        jnp.full((1,), dbase.PAD, jnp.int32),
        jnp.zeros((1, n_lanes), jnp.float32),
        capacity,
        valid=jnp.zeros((1,), bool),
        ops=tuple(ops),
    ).table


# ---------------------------------------------------------------------------
# fused pipeline regions (DESIGN.md §7)
# ---------------------------------------------------------------------------


def _run_pipeline(pipe, env, refs, db, sigma, allow_sorted, params):
    """Execute a fused ``Pipeline`` region as one streaming pass.

    XLA path: the whole region runs as ONE compiled computation (a jitted
    region function cached per region structure — data-centric execution,
    vs. the node-by-node interpretation of the unfused plan) with *pruned*
    probe gathers: only build-side columns that later stages actually read
    are gathered, and the full-width intermediate frames, masks, and unused
    gather columns the materialized executor writes out never exist.  The
    computations that remain are op-for-op identical to the unfused
    executor's, so fused and materialized plans produce bitwise-identical
    results (asserted in tests/test_fusion.py).

    On TPU (or ``REPRO_FORCE_PALLAS=1``), regions whose dictionaries all
    ship resident hooks (``registry.resident`` — every built-in family)
    dispatch to the ``kernels.fused_pipeline`` Pallas kernel: fact tiles
    stream HBM→VMEM through a double-buffered DMA, dictionaries (and their
    gather payloads, re-keyed to slab positions) stay VMEM-resident across
    grid steps in their own family layout, and partial aggregates
    accumulate in VMEM scratch written back only by the final grid step.
    A dictionary over the per-slab residency bound executes
    radix-partitioned when the plan priced it so (``Pipeline.partitions``,
    DESIGN.md §8): fact rows are routed by their probe key's partition and
    each grid step co-resides one slab block.
    """
    from repro.core import plan as P

    need = P.needed_columns(pipe.stages)

    # -- region input: a fresh Scan or an upstream frame (split region) -----
    stages = pipe.stages
    if isinstance(stages[0], P.Scan):
        sc = stages[0]
        if sc.source in env:
            src = env[sc.source]
            if isinstance(src, BuiltDict):
                t, rel = _dict_scan_table(src), None
            elif isinstance(src, _PendingStream):
                if isinstance(stages[-1], P.HashBuild):
                    # index terminals need the materialized rows: spill
                    t, rel = src.force(env, refs, sigma, allow_sorted, params), None
                else:
                    # chain this pipeline's stages onto the pending loop
                    _run_streamed_pipeline(
                        pipe, stages[1:], src, sc.var, None, env, refs,
                        db, sigma, allow_sorted, params, need,
                    )
                    return
            elif isinstance(src, Table) or _is_chunked(src):
                t, rel = src, None
            else:
                raise TypeError(f"cannot scan {sc.source}")
        else:
            t, rel = db[sc.source], sc.source
        if _is_chunked(t):
            if isinstance(stages[-1], P.HashBuild):
                # index terminals need global row ids AND their src serves
                # downstream probe gathers, which may read columns this
                # region itself never touches: decode resident, whole
                # (acceptable for dimension tables — see ROADMAP)
                t = t.decode(None)
            else:
                _run_streamed_pipeline(
                    pipe, stages[1:], t, sc.var, rel, env, refs, db,
                    sigma, allow_sorted, params, need,
                )
                return
        f = Frame({sc.var: t}, (sc.var,), {sc.var: rel})
        rest = stages[1:]
    else:
        f = env[pipe.source]
        assert isinstance(f, Frame), pipe.source
        rest = stages
        p0 = f.tables[f.order[0]]
        if isinstance(p0, _PendingStream):
            p0 = p0.force(env, refs, sigma, allow_sorted, params)
            f = Frame({**f.tables, f.order[0]: p0}, f.order, f.rels)
        if _is_chunked(p0):
            if len(f.order) == 1 and not isinstance(stages[-1], P.HashBuild):
                _run_streamed_pipeline(
                    pipe, rest, p0, f.order[0], f.rels.get(f.order[0]),
                    env, refs, db, sigma, allow_sorted, params, need,
                )
                return
            f = Frame(
                {**f.tables, f.order[0]: p0.decode()}, f.order, f.rels
            )

    # injection point: resident fused-region dispatch (Pallas OR fused-XLA).
    # The materialized node-by-node executor has no Pipeline nodes and the
    # streamed paths returned above, so only the fused rung can fail here —
    # this is what lets tests drive exactly one fused→materialized descent.
    _faults.check("fused-region", detail=pipe.out)
    if _kernel_pipeline(pipe, rest, f, env, refs, sigma, allow_sorted, params, need):
        return
    _record_region(
        pipe.out,
        "xla-radix-planned" if getattr(pipe, "partitions", 0) else "xla",
        family=_terminal_family(rest[-1]),
    )

    # -- referenced dictionaries and pruned gather sources ------------------
    dict_syms = []
    for node in rest:
        if isinstance(node, (P.HashProbe, P.GroupJoin)):
            dict_syms.append(node.build)
        elif isinstance(node, P.Reduce) and node.lookup_sym is not None:
            dict_syms.append(node.lookup_sym)
    dict_syms = tuple(dict.fromkeys(dict_syms))
    builts = {s: env[s] for s in dict_syms}
    src_cols: Dict[str, Dict[str, jax.Array]] = {}
    for node in rest:
        if isinstance(node, P.HashProbe):
            b = builts[node.build]
            want = need.get(node.inner_var, ())
            src_cols[node.out] = {
                c: b.src.col(c) for c in b.src.names() if c in want
            }

    # -- one compiled computation per region structure ----------------------
    statics = (
        repr((pipe.source, pipe.stages)),
        tuple(
            (
                v,
                f.tables[v].sorted_on,
                f.tables[v].nrows,
                f.rels.get(v),
                f.tables[v].mask is not None,
                tuple(sorted(f.tables[v].columns)),
            )
            for v in f.order
        ),
        tuple(
            (s, builts[s].res.ds, builts[s].kind, builts[s].lanes,
             builts[s].choice)
            for s in dict_syms
        ),
        tuple((o, tuple(sorted(cs))) for o, cs in src_cols.items()),
        bool(allow_sorted),
        _sigma_signature(sigma),
    )
    entry = _REGION_CACHE.get(statics)
    if entry is None:
        entry = _make_region_fn(
            rest, f, builts, src_cols, sigma, allow_sorted, need
        )
        if len(_REGION_CACHE) >= _REGION_CACHE_MAX:
            _REGION_CACHE.pop(next(iter(_REGION_CACHE)))
        _REGION_CACHE[statics] = entry
    fn, holder = entry

    frame_cols = {v: dict(f.tables[v].columns) for v in f.order}
    frame_masks = {
        v: f.tables[v].mask for v in f.order if f.tables[v].mask is not None
    }
    dict_tables = {s: builts[s].res.table for s in dict_syms}
    out = fn(frame_cols, frame_masks, dict_tables, src_cols, dict(params or {}))

    term = rest[-1]
    _publish_region_result(term, out, holder[0], holder[1], f, env, refs)


def _publish_region_result(term, out, kind, sorted_on, f, env, refs):
    """Store a region fn's raw terminal value under the terminal's symbol —
    shared by per-query (``_run_pipeline``) and shared-scan region demux."""
    from repro.core import plan as P

    if kind == "refs":
        refs[term.out] = out
    elif kind == "table":
        cols, mask = out
        n = f.tables[f.order[0]].nrows
        env[term.out] = Table(dict(cols), n, mask=mask, sorted_on=sorted_on)
    elif kind == "index":
        env[term.out] = BuiltDict(
            DictResult(term.choice.ds, out), term.choice, kind="index",
            src=f.primary,
        )
    else:  # aggregate dictionary
        lanes = (
            tuple(a for a, _ in term.values)
            if isinstance(term, P.GroupBy)
            else ("_0",)
        )
        env[term.out] = BuiltDict(
            DictResult(term.choice.ds, out), term.choice, lanes=lanes
        )


_REGION_CACHE: Dict[tuple, tuple] = {}
_REGION_CACHE_MAX = 256
#: traces of every region fn (a Python side effect of their bodies, so it
#: fires per trace only); ``StreamedExecutable`` counts its calls' share
_REGION_TRACES = 0


def _make_region_fn(rest, f0, builts, src_cols0, sigma, allow_sorted, need):
    """Build the jitted pure function executing a region's stages.  Static
    structure (stage list, frame layout, dictionary metadata, Σ) is closed
    over; arrays (frame columns/masks, dictionary tables, pruned gather
    sources, params) are traced arguments, so parameter rebinds re-enter
    the compiled computation."""
    from repro.core import plan as P

    order = f0.order
    rels = dict(f0.rels)
    sorted_ons = {v: f0.tables[v].sorted_on for v in order}
    nrows = {v: f0.tables[v].nrows for v in order}
    dict_meta = {
        s: (b.res.ds, b.kind, b.lanes, b.choice) for s, b in builts.items()
    }
    holder = [None, None]

    def run(frame_cols, frame_masks, dict_tables, src_cols, pvals):
        global _REGION_TRACES
        _REGION_TRACES += 1
        f = Frame(
            {
                v: Table(
                    dict(frame_cols[v]),
                    nrows[v],
                    mask=frame_masks.get(v),
                    sorted_on=sorted_ons[v],
                )
                for v in order
            },
            order,
            rels,
        )
        denv = {
            s: BuiltDict(
                DictResult(ds, dict_tables[s]), choice, lanes=lanes, kind=kind
            )
            for s, (ds, kind, lanes, choice) in dict_meta.items()
        }
        return _region_stages(
            rest, f, denv, src_cols, pvals, sigma, allow_sorted, holder
        )

    return jax.jit(run), holder


class _StreamSegment(NamedTuple):
    """One pipeline's worth of a streamed chunk loop: its stage list (after
    the Scan), the var the stages address, and the resident build-side
    inputs (dictionaries, pruned gather sources) captured at the time the
    pipeline was reached — by which point plan order guarantees they
    exist."""

    out: str
    key: str  # repr of (source, stages) — the statics cache key component
    pipe: object  # the Pipeline node (kernel dispatch needs partitions etc.)
    rest: tuple
    var: str
    rel: Optional[str]
    builts: Dict[str, object]
    src_cols: Dict[str, Dict[str, jax.Array]]
    needed: Tuple[str, ...]  # pruned SOURCE columns (segment 0 only)
    need: Dict[str, tuple]


def _stream_segment(pipe, rest, var, rel, env, need, ct) -> _StreamSegment:
    from repro.core import plan as P

    dict_syms = []
    for node in rest:
        if isinstance(node, (P.HashProbe, P.GroupJoin)):
            dict_syms.append(node.build)
        elif isinstance(node, P.Reduce) and node.lookup_sym is not None:
            dict_syms.append(node.lookup_sym)
    dict_syms = tuple(dict.fromkeys(dict_syms))
    builts = {s: env[s] for s in dict_syms}
    src_cols: Dict[str, Dict[str, jax.Array]] = {}
    for node in rest:
        if isinstance(node, P.HashProbe):
            b = builts[node.build]
            wc = need.get(node.inner_var, ())
            src_cols[node.out] = {
                c: b.src.col(c) for c in b.src.names() if c in wc
            }
    want = need.get(var, ())
    needed = tuple(c for c in ct.names() if c in want) or tuple(ct.names())
    return _StreamSegment(
        pipe.out,
        repr((getattr(pipe, "source", None), tuple(rest))),
        pipe, tuple(rest), var, rel, builts, src_cols, needed, dict(need),
    )


class _PendingStream:
    """A streamed region whose Project-terminal output has NOT been
    materialized.  ``env`` holds this placeholder; a downstream single-var
    pipeline that scans it EXTENDS the chain instead — its stages run as
    the next segment of the SAME chunk loop, so e.g. q9's lineitem pass
    chains part-probe → supplier-probe → orders-probe+group-by with no
    host spill in between.  Any consumer that needs the actual rows
    (a bare-node frame access, an index-terminal region, a plan result)
    calls ``force``, which runs the accumulated chain with its Project
    terminal and spills each chunk to a ``HostChunkedTable`` — chaining is
    an optimization, never a semantic dependency.  Each extension builds a
    NEW pending sharing the prefix, so a second consumer of an
    intermediate simply re-streams from the source."""

    def __init__(self, ct, segments: tuple):
        self.ct = ct
        self.segments = segments

    @property
    def out(self) -> str:
        return self.segments[-1].out

    def names(self):  # metadata surface for needed-column pruning
        term = self.segments[-1].rest[-1]
        return tuple(name for name, _ in term.fields)

    def force(self, env, refs, sigma, allow_sorted, params):
        _exec_streamed_chain(
            self.ct, self.segments, env, refs, sigma, allow_sorted, params
        )
        return env[self.out]


def _make_streamed_chain_fn(
    segments, chunk_rows, sorted_on0, spec, sigma, allow_sorted, cap,
    final=False,
):
    """The streamed twin of ``_make_region_fn``: same closure/trace split
    plus (a) the chunk arrives as its ENCODED payload and is decoded inside
    the trace (``decode_traced`` — XLA fuses shift/mask unpack and gathers
    straight into the region compute, no eager per-chunk dispatch),
    (b) chained segments run back to back in the SAME trace — one
    segment's Project output becomes the next segment's input frame, so
    the whole multi-region chain over a chunk is ONE compiled computation
    — and (c) one carried argument: the accumulator state a dict terminal
    folds each chunk into (``None`` for Project/Reduce terminals).
    ``spec`` is the chunk's static decode recipe; full uniformly-encoded
    chunks share one spec, so one compile serves them all (a short final
    chunk or a chunk that encoded differently costs one more)."""
    from repro.kernels import decode as DK

    metas = [
        {
            s: (b.res.ds, b.kind, b.lanes, b.choice)
            for s, b in seg.builts.items()
        }
        for seg in segments
    ]
    n, colspecs = spec
    holders = [[None, None] for _ in segments]

    def run(payloads, dict_tables, src_cols, pvals, state):
        global _REGION_TRACES
        _REGION_TRACES += 1
        cols = {}
        with jax.named_scope(SCOPE_DECODE):
            for c, kind, bits, ref, block in colspecs:
                if kind == "raw":
                    cols[c] = payloads[c]["data"]
                else:
                    cols[c] = DK.decode_traced(
                        kind, payloads[c], bits=bits, ref=ref, block=block,
                        n=n, chunk_rows=chunk_rows,
                    )
        if colspecs and colspecs[0][1] == "raw":
            mask = payloads["__mask__"]["data"]
        else:
            mask = jnp.arange(chunk_rows, dtype=jnp.int32) < n
        srt = sorted_on0
        out = None
        for j, seg in enumerate(segments):
            f = Frame(
                {
                    seg.var: Table(
                        cols, chunk_rows, mask=mask, sorted_on=srt
                    )
                },
                (seg.var,),
                {seg.var: seg.rel},
            )
            denv = {
                s: BuiltDict(
                    DictResult(ds, dict_tables[j][s]), choice,
                    lanes=lanes, kind=kind,
                )
                for s, (ds, kind, lanes, choice) in metas[j].items()
            }
            last = j == len(segments) - 1
            out = _region_stages(
                seg.rest, f, denv, src_cols[j], pvals, sigma, allow_sorted,
                holders[j],
                stream=(
                    (state, cap, final)
                    if last and state is not None else None
                ),
            )
            if not last:  # Project output feeds the next segment's frame
                cols, mask = out
                cols = dict(cols)
                srt = tuple(holders[j][1] or ())
        return out

    return jax.jit(run), holders


def _run_streamed_pipeline(
    pipe, rest, ct, var, rel, env, refs, db, sigma, allow_sorted, params, need
):
    """Entry point for a region whose scanned input is host-resident
    chunked storage (or a pending streamed chain).  A Project terminal does
    NOT run yet: it publishes a ``_PendingStream`` so downstream pipelines
    can chain onto the same chunk loop; a GroupBy/GroupJoin/Reduce terminal
    executes the accumulated chain now (``_exec_streamed_chain``)."""
    from repro.core import plan as P

    if isinstance(ct, _PendingStream):
        segments = ct.segments + (
            _stream_segment(pipe, rest, var, rel, env, need, ct),
        )
        ct = ct.ct
    else:
        segments = (_stream_segment(pipe, rest, var, rel, env, need, ct),)
    if isinstance(rest[-1], P.Project):
        env[pipe.out] = _PendingStream(ct, segments)
        _record_region(pipe.out, "streamed-deferred")
        return
    _exec_streamed_chain(ct, segments, env, refs, sigma, allow_sorted, params)


def _exec_streamed_chain(ct, segments, env, refs, sigma, allow_sorted, params):
    """Run a chain of fused regions as ONE pass over a chunked relation:
    chunks cross the host→device link ENCODED (next chunk's upload
    dispatched before the current chunk's compute — async overlap), decode
    inside the compiled region fn, and flow through every chained segment's
    stages in that same computation.  A GroupBy/GroupJoin terminal folds
    each chunk into a carried accumulator sized for the FULL relation
    (``_merge_groupby`` — bitwise equal to the resident one-shot build); a
    Project terminal (a forced pending) spills each chunk's output back to
    host as a ``HostChunkedTable`` that downstream regions stream the same
    way; a Reduce terminal combines per-chunk scalar partials by each
    lane's monoid.  At no point does a decoded fact-table-sized array
    exist on device."""
    import numpy as np

    from repro.core import plan as P
    from repro.data import storage as STG

    seg0, seg_last = segments[0], segments[-1]
    term = seg_last.rest[-1]
    needed = seg0.needed
    nchunks = ct.n_chunks

    # -- carried accumulator for dict terminals -----------------------------
    is_dict_term = isinstance(term, (P.GroupBy, P.GroupJoin))
    state = None
    cap = 0
    sorted_stream = False
    term_ops: Tuple[str, ...] = ()
    if is_dict_term:
        term_ops = tuple(term.ops) if isinstance(term, P.GroupBy) else ()
        n_lanes = len(term.values) if isinstance(term, P.GroupBy) else 1
        if len(segments) == 1:
            meta_f = Frame(
                {seg_last.var: ct}, (seg_last.var,), {seg_last.var: seg_last.rel}
            )
            cap = _stream_capacity(
                meta_f, term.keyexpr, term.choice.ds, sigma, ct.nrows
            )
            # sorted-family terminal keyed by the stream's sort key: fold
            # via completed-group emission (O(chunk) per chunk) instead of
            # re-scattering a capacity-sized state
            if allow_sorted and term.choice.ds.startswith("st"):
                _, _, _srt = _key_info(meta_f, term.keyexpr)
                sorted_stream = bool(_srt)
        else:
            # chained input is an intermediate (rel=None): Σ has no row for
            # it, so size for the full source row count — exactly what the
            # unchained spill-and-restream path would have picked
            cap = capacity_for(term.choice.ds, ct.nrows)
        state = (
            _sorted_stream_init(cap, ct.chunk_rows, n_lanes)
            if sorted_stream
            else _empty_dict_state(term.choice.ds, n_lanes, cap, term_ops)
        )
        _account_stream(
            peak_state_bytes=sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(state)
            ),
        )

    chunk_dec_bytes = ct.chunk_rows * (4 * len(needed) + 1)
    # two decoded source chunks live at once (current compute + prefetched
    # next) plus each chained segment's intermediate projection of the chunk
    inter_bytes = sum(
        ct.chunk_rows * (4 * len(seg.rest[-1].fields) + 1)
        for seg in segments[:-1]
    )
    _account_stream(
        regions=len(segments),
        peak_chunk_bytes=2 * chunk_dec_bytes + inter_bytes,
    )

    # -- try the fused Pallas kernel per chunk (TPU / forced) ---------------
    if is_dict_term and nchunks and len(segments) == 1:
        kstate = (
            _empty_dict_state(term.choice.ds, n_lanes, cap, term_ops)
            if sorted_stream else state
        )
        if _stream_kernel_chunks(
            seg0, ct, needed, kstate, cap, term_ops, env, refs, sigma,
            allow_sorted, params,
        ):
            return

    # -- XLA streamed loop --------------------------------------------------
    up_next = ct.upload_chunk(0, needed)
    holders = None
    host_chunks: list = []
    host_masks: list = []
    partials: list = []
    statics_base = (
        "streamed",
        tuple(
            (
                seg.key,
                seg.var,
                seg.rel,
                tuple(
                    (s, b.res.ds, b.kind, b.lanes, b.choice)
                    for s, b in seg.builts.items()
                ),
                tuple((o, tuple(sorted(cs))) for o, cs in seg.src_cols.items()),
            )
            for seg in segments
        ),
        (ct.sorted_on, ct.chunk_rows, tuple(sorted(needed))),
        bool(allow_sorted),
        cap,
        _sigma_signature(sigma),
    )
    dict_tables = [
        {s: b.res.table for s, b in seg.builts.items()} for seg in segments
    ]
    src_cols = [seg.src_cols for seg in segments]
    chain_h2d = 0
    for i in range(nchunks):
        up, up_next = up_next, (
            ct.upload_chunk(i + 1, needed) if i + 1 < nchunks else None
        )
        chain_h2d += up[1]
        _account_stream(chunks=1, h2d_bytes=up[1])
        # the chunk's static decode recipe keys the region fn: the encoded
        # payload goes straight into the jit and decodes in-trace (full
        # uniformly-encoded chunks all hit one compiled fn)
        spec = ct.chunk_decode_spec(i, needed)
        final = sorted_stream and i == nchunks - 1
        statics = statics_base + (spec, final)
        entry = _REGION_CACHE.get(statics)
        if entry is None:
            entry = _make_streamed_chain_fn(
                segments, ct.chunk_rows, ct.sorted_on, spec, sigma,
                allow_sorted, cap, final=final,
            )
            if len(_REGION_CACHE) >= _REGION_CACHE_MAX:
                _REGION_CACHE.pop(next(iter(_REGION_CACHE)))
            _REGION_CACHE[statics] = entry
        fn, holders = entry
        out = fn(up[0], dict_tables, src_cols, dict(params or {}), state)
        if is_dict_term:
            state = out
        elif holders[-1][0] == "table":
            cols, mask = out
            host_chunks.append({c: np.asarray(a) for c, a in cols.items()})
            host_masks.append(
                np.asarray(mask) if mask is not None
                else np.ones((ct.chunk_rows,), bool)
            )
        else:  # refs
            partials.append(out)

    for seg in segments[:-1]:
        _record_region(seg.out, f"streamed-chained:{nchunks}", chunks=nchunks)
    _record_region(
        seg_last.out,
        f"streamed:{nchunks}",
        family=_terminal_family(term),
        chunks=nchunks,
        h2d_bytes=chain_h2d,
    )

    # -- publish the terminal -----------------------------------------------
    if is_dict_term:
        lanes = (
            tuple(a for a, _ in term.values)
            if isinstance(term, P.GroupBy)
            else ("_0",)
        )
        env[term.out] = BuiltDict(
            DictResult(term.choice.ds, state), term.choice, lanes=lanes
        )
    elif holders[-1][0] == "table":
        env[term.out] = STG.HostChunkedTable(
            chunks=host_chunks,
            masks=host_masks,
            chunk_rows=ct.chunk_rows,
            nrows=ct.nrows,
            schema={
                c: str(a.dtype) for c, a in host_chunks[0].items()
            },
            sorted_on=tuple(holders[-1][1] or ()),
        )
    else:  # scalar ref record: combine per-lane monoid partials
        fops = term.ops or ("sum",) * len(term.fields)
        total = {}
        for k, (name, _fx) in enumerate(term.fields):
            acc = partials[0][name]
            for p in partials[1:]:
                v = p[name]
                if fops[k] == "sum":
                    acc = acc + v
                elif fops[k] == "min":
                    acc = jnp.minimum(acc, v)
                else:
                    acc = jnp.maximum(acc, v)
            total[name] = acc
        refs[term.out] = total


def _stream_kernel_chunks(
    seg, ct, needed, state, cap, term_ops, env, refs, sigma, allow_sorted,
    params,
):
    """Per-chunk fused Pallas kernel dispatch for a single-segment dict
    terminal (TPU / ``REPRO_FORCE_PALLAS=1``): each chunk's partial
    aggregate merges into the carried state (``_merge_dict_tables``).
    Returns False when the kernel declines the region — the XLA streamed
    loop is the fallback."""
    from repro.core import plan as P

    pipe, rest, var, rel = seg.pipe, seg.rest, seg.var, seg.rel
    term = rest[-1]
    nchunks = ct.n_chunks
    t0 = ct.chunk_device(0, needed, pad=True)
    f0 = Frame({var: t0}, (var,), {var: rel})
    scratch_env, scratch_refs = dict(env), {}
    # a structural decline is ``_kernel_pipeline`` returning False; anything
    # it raises (a kernel that does not lower, an injected fault) propagates
    if not _kernel_pipeline(
        pipe, rest, f0, scratch_env, scratch_refs, sigma,
        allow_sorted, params, seg.need,
    ):
        return False
    up_next = ct.upload_chunk(1, needed) if nchunks > 1 else None
    state = _merge_dict_tables(
        term.choice.ds, state, scratch_env[pipe.out].res.table, cap, term_ops
    )
    _account_stream(chunks=1)
    kern_h2d = 0
    for i in range(1, nchunks):
        up, up_next = up_next, (
            ct.upload_chunk(i + 1, needed) if i + 1 < nchunks else None
        )
        kern_h2d += up[1]
        _account_stream(h2d_bytes=up[1])
        t_i = ct.chunk_device(i, needed, pad=True, uploaded=up[0])
        f_i = Frame({var: t_i}, (var,), {var: rel})
        scratch_env, scratch_refs = dict(env), {}
        assert _kernel_pipeline(
            pipe, rest, f_i, scratch_env, scratch_refs, sigma,
            allow_sorted, params, seg.need,
        )
        state = _merge_dict_tables(
            term.choice.ds, state, scratch_env[pipe.out].res.table, cap,
            term_ops,
        )
        _account_stream(chunks=1)
    _record_region(
        pipe.out,
        f"streamed-kernel:{nchunks}",
        family=_terminal_family(term),
        chunks=nchunks,
        h2d_bytes=kern_h2d,
    )
    lanes = (
        tuple(a for a, _ in term.values)
        if isinstance(term, P.GroupBy)
        else ("_0",)
    )
    env[term.out] = BuiltDict(
        DictResult(term.choice.ds, state), term.choice, lanes=lanes
    )
    return True


def _region_stages(
    rest, f, denv, src_cols, pvals, sigma, allow_sorted, holder, stream=None
):
    """Trace a region's stage list over an input frame — the ONE region body
    shared by the per-query jitted region fn (``_make_region_fn``) and the
    multi-branch shared-scan region fn (``_make_shared_region_fn``).  Sets
    ``holder[0]`` to the terminal kind and returns the terminal's raw value
    (ref record / (cols, mask) / backend table).

    ``stream=(state_table, capacity)`` switches a GroupBy/GroupJoin terminal
    from a one-shot build to one streamed fold step: the chunk's rows merge
    into the carried accumulator (``_merge_groupby``), which the driver
    threads across chunks.  Every non-terminal stage is untouched — the
    per-chunk select/probe/project math is the resident math."""
    from repro.core import llql as L
    from repro.core import plan as P
    from repro.core.lower import compile_rowfn_frame as _rowfn_frame

    def rowfn(x, tables):
        return _rowfn_frame(x, tables, pvals)

    for node in rest:
        if isinstance(node, P.Select):
            m = rowfn(node.pred, f.tables)
            f = f.with_mask(jnp.asarray(m, bool))

        elif isinstance(node, P.HashProbe):
            b = denv[node.build]
            keys = jnp.asarray(rowfn(node.keyexpr, f.tables), jnp.int32)
            _, _, srt = _key_info(f, node.keyexpr)
            srt = srt and allow_sorted
            vals, found = lookup_dict(
                b.res,
                keys,
                valid=f.primary.mask,
                sorted_probes=srt and (node.hinted or b.choice.hinted),
            )
            ridx = jnp.where(found, vals[:, 0].astype(jnp.int32), 0)
            gcols = {
                c: jnp.where(
                    found, _safe_gather(a, ridx), jnp.zeros((), a.dtype)
                )  # pruned: only columns later stages read are gathered
                for c, a in src_cols[node.out].items()
            }
            gathered = Table(gcols, f.primary.nrows, mask=found)
            masked = f.with_mask(found)
            f = Frame(
                {**masked.tables, node.inner_var: gathered},
                masked.order + (node.inner_var,),
                {**masked.rels, node.inner_var: None},
            )

        elif isinstance(node, P.Project):
            n = f.primary.nrows
            cols = {}
            sorted_on: Tuple[str, ...] = ()
            for name, fx in node.fields:
                col = jnp.asarray(rowfn(fx, f.tables))
                cols[name] = jnp.broadcast_to(col, (n,))
                if (
                    not sorted_on
                    and isinstance(fx, L.FieldAccess)
                    and isinstance(fx.rec, L.FieldAccess)
                    and fx.rec.name == "key"
                    and isinstance(fx.rec.rec, L.Var)
                    and fx.rec.rec.name in f.tables
                    and f.tables[fx.rec.rec.name].sorted_on[:1]
                    == (fx.name,)
                ):
                    sorted_on = (name,)
            holder[0], holder[1] = "table", sorted_on
            return cols, f.primary.mask

        elif isinstance(node, P.HashBuild):
            keys = jnp.asarray(rowfn(node.keyexpr, f.tables), jnp.int32)
            _, _, srt = _key_info(f, node.keyexpr)
            srt = srt and allow_sorted
            cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
            d = build_index(
                node.choice.ds,
                keys,
                cap,
                valid=f.primary.mask,
                assume_sorted=srt and (node.choice.hinted or node.hinted),
            )
            holder[0] = "index"
            return d.table

        elif isinstance(node, P.GroupBy):
            n = f.primary.nrows
            keys = jnp.asarray(rowfn(node.keyexpr, f.tables), jnp.int32)
            _, _, srt = _key_info(f, node.keyexpr)
            srt = srt and allow_sorted
            lanes = [
                jnp.broadcast_to(
                    jnp.asarray(rowfn(fx, f.tables), jnp.float32), (n,)
                )
                for _, fx in node.values
            ]
            vals = jnp.stack(lanes, axis=1)
            if stream is not None:
                state, cap, final = stream
                holder[0] = "dict"
                if isinstance(state, _SortedStreamState):
                    return _sorted_stream_merge(
                        f.primary, keys, vals, node.choice.ds, cap, state,
                        ops=tuple(node.ops), final=final,
                    )
                d = _merge_groupby(
                    f.primary, keys, vals, node.choice.ds, cap, state,
                    ops=tuple(node.ops),
                    sorted_merge=srt and node.choice.ds.startswith("st"),
                )
                return d.table
            cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
            d = groupby(
                f.primary,
                keys,
                vals,
                node.choice.ds,
                cap,
                assume_sorted=srt and (node.choice.hinted or node.hinted),
                ops=tuple(node.ops),
            )
            holder[0] = "dict"
            return d.table

        elif isinstance(node, P.GroupJoin):
            b = denv[node.build]
            n = f.primary.nrows
            keys = jnp.asarray(rowfn(node.keyexpr, f.tables), jnp.int32)
            _, _, srt = _key_info(f, node.keyexpr)
            srt = srt and allow_sorted
            f_vals = jnp.broadcast_to(
                jnp.asarray(rowfn(node.f_expr, f.tables), jnp.float32),
                (n,),
            )
            if stream is not None:
                state, cap, final = stream
                g_vals, found = lookup_dict(
                    b.res,
                    keys,
                    valid=f.primary.mask,
                    sorted_probes=srt and (node.hinted or b.choice.hinted),
                )
                holder[0] = "dict"
                if isinstance(state, _SortedStreamState):
                    return _sorted_stream_merge(
                        f.primary.with_mask(found), keys,
                        f_vals[:, None] * g_vals, node.choice.ds, cap,
                        state, final=final,
                    )
                d = _merge_groupby(
                    f.primary.with_mask(found), keys,
                    f_vals[:, None] * g_vals, node.choice.ds, cap, state,
                    sorted_merge=srt and node.choice.ds.startswith("st"),
                )
                return d.table
            cap = _capacity(f, node.keyexpr, node.choice.ds, sigma)
            d = groupjoin(
                f.primary,
                keys,
                f_vals[:, None],
                b.res,
                node.choice.ds,
                cap,
                sorted_probes=srt and (node.hinted or b.choice.hinted),
                assume_sorted=srt and node.choice.hinted,
            )
            holder[0] = "dict"
            return d.table

        elif isinstance(node, P.Reduce):
            lanes: Tuple[str, ...] = ("m", "c", "c_c")
            lookup_vals = None
            if node.lookup_sym is not None:
                b = denv[node.lookup_sym]
                lanes = b.lanes or lanes
                keys = jnp.asarray(
                    rowfn(node.lookup_key, f.tables), jnp.int32
                )
                _, _, srt = _key_info(f, node.lookup_key)
                srt = srt and allow_sorted
                lookup_vals, found = lookup_dict(
                    b.res,
                    keys,
                    valid=f.primary.mask,
                    sorted_probes=srt and b.choice.hinted,
                )
                f = f.with_mask(found)
            fops = node.ops or ("sum",) * len(node.fields)
            total = {}
            for k, (name, fx) in enumerate(node.fields):
                col = _reduce_field(
                    fx, f, node.lookup_var, lookup_vals, lanes,
                    params=pvals,
                )
                total[name] = scalar_aggregate(
                    f.primary, col, ops=(fops[k],)
                )[0]
            holder[0] = "refs"
            return total

        else:  # pragma: no cover
            raise AssertionError(node)
    raise AssertionError("region has no terminal")  # pragma: no cover


KERNEL_SLOTS = 1 << 16  # per-dictionary resident slot bound of the fused
# kernel (mirrors FusionCostModel.kernel_slots — a bigger slab radix-
# partitions instead of de-fusing)

def _kernel_pipeline(pipe, rest, f, env, refs, sigma, allow_sorted, params, need):
    """Try the fused Pallas kernel for the (already input-resolved) region;
    returns True when it ran and stored the terminal's result.

    The kernel is *dictionary-complete*: eligibility is a capability check
    against the registry (``registry.resident`` — the family ships
    ``resident_slabs``/``resident_find`` hooks), never a name compare, so
    every built-in family dispatches and a third-party backend registered
    without hooks falls back explicitly to the XLA region path.  A
    dictionary over the per-slab residency bound executes radix-partitioned
    when the plan priced it so (``pipe.partitions``); remaining fallbacks
    are structural: a non-aggregating terminal (Project/HashBuild), a
    duplicated probe symbol, or a planner/runtime capacity disagreement."""
    from repro.core import plan as P
    from repro.kernels import fused_pipeline as _fp
    from repro.kernels import ops as _kops

    use_pallas, interpret = _kops.fused_pipeline_policy()
    if not use_pallas:
        return False
    term = rest[-1] if rest else None
    if not isinstance(term, (P.GroupBy, P.GroupJoin, P.Reduce)):
        return False
    n_parts = getattr(pipe, "partitions", 0)
    radix_sym = getattr(pipe, "part_sym", "") if n_parts else ""

    def _cap_of(b) -> int:
        mod = registry.get(b.res.ds)
        return int(mod.resident_slabs(b.res.table)[0].shape[0])

    def _resident_ok(b, sym) -> bool:
        if not (isinstance(b, BuiltDict) and registry.resident(b.res.ds)):
            return False
        cap = _cap_of(b)
        if sym == radix_sym:
            return (
                registry.partitionable(b.res.ds)
                and cap % n_parts == 0
                and cap // n_parts >= 256
            )
        return cap <= KERNEL_SLOTS

    # resident slabs are keyed by build symbol: two probes of the same
    # dictionary would alias each other's gather payloads — take the exact
    # XLA path for that (rare) shape instead
    probe_builds = [n.build for n in rest if isinstance(n, P.HashProbe)]
    if len(set(probe_builds)) != len(probe_builds):
        return False

    def _bundle(b, sym, fv, iv):
        if sym == radix_sym:
            return _fp.partitioned_bundle(
                b.res.ds, b.res.table, fv, iv, n_parts
            )
        return _fp.resident_bundle(b.res.ds, b.res.table, fv, iv)

    dicts = {}  # sym -> ResidentDict bundle
    probe_meta = {}  # probe node out -> ((float cols, dtypes), (int cols, dtypes))
    radix_key = None  # LLQL key expression partitioning the fact stream
    for node in rest:
        if isinstance(node, P.HashProbe):
            b = env[node.build]
            if node.build in dicts or not (
                _resident_ok(b, node.build) and b.kind == "index"
            ):
                return False
            src_t = b.src
            want = tuple(c for c in src_t.names() if c in need.get(node.inner_var, ()))
            ks, vs, slot_ok = b.res.arrays()
            cap = ks.shape[0]
            rowidx = jnp.where(slot_ok, vs[:, 0].astype(jnp.int32), 0)
            # gather payload re-keyed to dictionary slab positions: the
            # probe then yields the needed build columns directly,
            # C-bounded in VMEM.  Integer columns ride a separate int32
            # slab — a float32 round-trip would corrupt values above 2^24.
            want_f = tuple(
                c for c in want if jnp.issubdtype(src_t.col(c).dtype, jnp.floating)
            )
            want_i = tuple(c for c in want if c not in want_f)
            gathered = {
                c: jnp.where(
                    slot_ok, src_t.col(c)[rowidx], jnp.zeros((), src_t.col(c).dtype)
                )
                for c in want
            }
            fv = (
                jnp.stack([gathered[c].astype(jnp.float32) for c in want_f], axis=1)
                if want_f
                else jnp.zeros((cap, 0), jnp.float32)
            )
            iv = (
                jnp.stack([gathered[c].astype(jnp.int32) for c in want_i], axis=1)
                if want_i
                else jnp.zeros((cap, 0), jnp.int32)
            )
            dicts[node.build] = _bundle(b, node.build, fv, iv)
            if node.build == radix_sym:
                radix_key = node.keyexpr
            probe_meta[node.out] = (
                (want_f, tuple(src_t.col(c).dtype for c in want_f)),
                (want_i, tuple(src_t.col(c).dtype for c in want_i)),
            )
        elif isinstance(node, P.GroupJoin):
            b = env[node.build]
            if node.build in dicts or not _resident_ok(b, node.build):
                return False
            ks, vs, _ = b.res.arrays()
            dicts[node.build] = _bundle(
                b, node.build, vs, jnp.zeros((ks.shape[0], 0), jnp.int32)
            )
            if node.build == radix_sym:
                radix_key = node.keyexpr
        elif isinstance(node, P.Reduce) and node.lookup_sym is not None:
            b = env[node.lookup_sym]
            if node.lookup_sym in dicts or not _resident_ok(b, node.lookup_sym):
                return False
            ks, vs, _ = b.res.arrays()
            dicts[node.lookup_sym] = _bundle(
                b, node.lookup_sym, vs, jnp.zeros((ks.shape[0], 0), jnp.int32)
            )
            if node.lookup_sym == radix_sym:
                radix_key = node.lookup_key
    if radix_sym and (radix_sym not in dicts or radix_key is None):
        return False  # plan marked a partition target the region never probes

    part_terminal = False
    acc_ds = None
    out_cap = 0
    # per-lane semiring combine monoids of the terminal (() = all-sum)
    term_ops = tuple(getattr(term, "ops", ()) or ())
    if isinstance(term, (P.GroupBy, P.GroupJoin)):
        acc_ds = term.choice.ds
        if acc_ds not in registry.names():
            return False
        out_cap = _capacity(f, term.keyexpr, acc_ds, sigma)
        part_terminal = bool(radix_sym) and term.keyexpr == radix_key
        if out_cap > KERNEL_SLOTS and not part_terminal:
            return False
        n_lanes = len(term.values) if isinstance(term, P.GroupBy) else (
            env[term.build].res.arrays()[1].shape[1]
        )
        if part_terminal:
            b = env[radix_sym]
            mod = registry.get(b.res.ds)
            cp = _cap_of(b) // n_parts
            over = int(getattr(mod, "PARTITION_OVERLAP", 0))
            # a partition's terminal keys ⊆ its dictionary block's live keys
            # (≤ cp + overlap ≤ 2·cp), so 2·cp slots bound the load factor
            # at ~0.5 with no skew exposure — and match EXACTLY what the
            # planner priced (plan._partition_candidate's _pow2cap(cp)),
            # so a region admitted under the byte budget cannot allocate
            # past it at runtime
            cacc = dbase.next_pow2(2 * cp)
            assert cacc >= cp + over
            out_spec = ("dict", cacc, n_lanes)
        else:
            out_spec = ("dict", out_cap, n_lanes)
    else:
        if isinstance(env.get(term.lookup_sym), BuiltDict):
            lanes = env[term.lookup_sym].lanes or ("m", "c", "c_c")
        else:
            lanes = ("m", "c", "c_c")
        out_spec = ("sum", len(term.fields))

    # flatten the streamed columns (pruned to what the region reads)
    cols = {}
    for var in f.order:
        t = f.tables[var]
        for c in t.names():
            if c in need.get(var, ()):
                cols[f"{var}\0{c}"] = t.col(c)
    live = f.primary.live_mask()
    scalars = {
        k: jnp.asarray(v).reshape(1) for k, v in (params or {}).items()
    }

    # radix mode: route fact rows by the partition id of their (oversized)
    # probe key so each grid step co-resides one slab block — computed from
    # the streamed columns (the planner guarantees the key reads only the
    # scan variable)
    radix_plan = None
    if radix_sym:
        from repro.core.lower import _Unsupported
        from repro.core.lower import compile_rowfn_frame as _rf

        b = env[radix_sym]
        mod = registry.get(b.res.ds)
        try:
            kvals = jnp.asarray(_rf(radix_key, f.tables, params), jnp.int32)
        except _Unsupported:
            return False  # key not computable from the stream: XLA path
        part = mod.partition_assign(b.res.table, kvals, n_parts)
        cols, live, radix_plan = _fp.radix_route(
            cols, live, part, n_parts, _fp.ROW_BLOCK
        )
        radix_plan = radix_plan._replace(part_terminal=part_terminal)

    accumulate = None
    if acc_ds is not None and registry.accumulates_resident(acc_ds):
        import functools as _ft

        accumulate = _ft.partial(
            registry.get(acc_ds).resident_accumulate,
            max_probes=_fp.MAX_PROBES,
            ops=term_ops or None,
        )

    def row_fn(tile_cols, tile_live, lookups, tile_scalars):
        from repro.core.lower import compile_rowfn_frame as _rf

        B = tile_live.shape[0]
        tabs = {}
        for var in f.order:
            pre = f"{var}\0"
            tabs[var] = {
                k[len(pre):]: a for k, a in tile_cols.items() if k.startswith(pre)
            }
        cur_live = tile_live

        def frame_tables():
            return {
                v: Table(dict(c), B, mask=cur_live) for v, c in tabs.items()
            }

        def rf(x):
            return _rf(x, frame_tables(), tile_scalars)

        out_keys = out_vals = None
        for node in rest:
            if isinstance(node, P.Select):
                cur_live = cur_live & jnp.asarray(rf(node.pred), bool)
            elif isinstance(node, P.HashProbe):
                qs = jnp.asarray(rf(node.keyexpr), jnp.int32)
                pf, pi, pfound = lookups[node.build](qs)
                cur_live = cur_live & pfound
                (want_f, f_dts), (want_i, i_dts) = probe_meta[node.out]
                tabs[node.inner_var] = {
                    **{
                        c: pf[:, i].astype(dt)
                        for i, (c, dt) in enumerate(zip(want_f, f_dts))
                    },
                    **{
                        c: pi[:, i].astype(dt)
                        for i, (c, dt) in enumerate(zip(want_i, i_dts))
                    },
                }
            elif isinstance(node, P.GroupBy):
                out_keys = jnp.asarray(rf(node.keyexpr), jnp.int32)
                lanes_v = [
                    jnp.broadcast_to(
                        jnp.asarray(rf(fx), jnp.float32), (B,)
                    )
                    for _, fx in node.values
                ]
                out_vals = jnp.stack(lanes_v, axis=1)
            elif isinstance(node, P.GroupJoin):
                out_keys = jnp.asarray(rf(node.keyexpr), jnp.int32)
                g_vals, _, g_found = lookups[node.build](out_keys)
                cur_live = cur_live & g_found
                f_v = jnp.broadcast_to(
                    jnp.asarray(rf(node.f_expr), jnp.float32), (B,)
                )
                out_vals = f_v[:, None] * g_vals
            elif isinstance(node, P.Reduce):
                lookup_vals = None
                if node.lookup_sym is not None:
                    qs = jnp.asarray(rf(node.lookup_key), jnp.int32)
                    lookup_vals, _, lfound = lookups[node.lookup_sym](qs)
                    cur_live = cur_live & lfound
                frame = Frame(frame_tables(), tuple(tabs), {})
                cols_v = [
                    jnp.broadcast_to(
                        _reduce_field(
                            fx, frame, node.lookup_var, lookup_vals,
                            lanes, params=tile_scalars,
                        ),
                        (B,),
                    )
                    for _, fx in node.fields
                ]
                out_vals = jnp.stack(cols_v, axis=1)
        return out_keys, out_vals, cur_live

    out = _fp.fused_pipeline(
        cols,
        live,
        dicts,
        scalars,
        row_fn,
        out_spec,
        accumulate=accumulate,
        radix=radix_plan,
        interpret=interpret,
        lane_ops=term_ops or None,
    )
    _record_region(
        term.out,
        "kernel-radix" if radix_sym else "kernel-resident",
        family=_terminal_family(term),
    )
    if out_spec[0] == "dict":
        tk, tv = out
        if part_terminal:  # [P, Cacc(*V)] per-partition scratches: flatten
            tk = tk.reshape(-1)
            tv = tv.reshape(tk.shape[0], -1)
        if registry.accumulates_resident(acc_ds) and not part_terminal:
            # hash-family terminal: the scratch IS the family's layout
            # (min/max lanes: clear the identity residue off dead slots)
            tv = dbase.finalize_dead(tk, tv, term_ops, dbase.EMPTY)
            table = dbase.HashTable(tk, tv, jnp.int32(_fp.MAX_PROBES))
        else:
            # sort-family (or partition-flattened) terminal: finalize the
            # scratch entries through the family's own build — keys are
            # already unique per entry, so no sums move (exact)
            kw = {} if dbase.all_sum(term_ops) else {"ops": term_ops}
            table = registry.get(acc_ds).build(
                tk, tv, out_cap, valid=tk != dbase.EMPTY, **kw
            )
        res = DictResult(acc_ds, table)
        if isinstance(term, P.GroupBy):
            env[term.out] = BuiltDict(
                res, term.choice, lanes=tuple(a for a, _ in term.values)
            )
        else:
            env[term.out] = BuiltDict(res, term.choice, lanes=("_0",))
    else:
        refs[term.out] = {
            name: out[i] for i, (name, _) in enumerate(term.fields)
        }
    return True


def _reduce_field(fx, frame: Frame, lookup_var, lookup_vals, lane_names, params=None):
    """One field of a scalar-agg record; lookup-value accesses (``ra.m``)
    resolve into the looked-up value lanes by name (Fig. 7b's Ragg record)."""
    from repro.core import llql as L
    from repro.core.lower import _BIN, _UN, compile_rowfn_frame

    lanes = {nm: i for i, nm in enumerate(lane_names)}

    def go(x):
        if (
            isinstance(x, L.FieldAccess)
            and isinstance(x.rec, L.Var)
            and x.rec.name == lookup_var
        ):
            return lookup_vals[:, lanes[x.name]]
        if isinstance(x, L.BinOp):
            return _BIN[x.op](go(x.lhs), go(x.rhs))
        if isinstance(x, L.UnOp):
            return _UN[x.op](go(x.operand))
        if isinstance(x, L.Const):
            return x.value
        return compile_rowfn_frame(x, frame.tables, params)

    return jnp.asarray(go(fx), jnp.float32)


# ---------------------------------------------------------------------------
# cross-plan shared-scan execution (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _make_shared_region_fn(specs, sigma, allow_sorted):
    """Build ONE jitted function executing every branch of a shared-scan
    region over the same fact stream.  Each branch re-frames the shared
    scan columns under its own variable and traces the common region body
    (``_region_stages``); because all branches read the *same* traced
    column arrays, XLA CSE collapses the loads and the fact relation
    streams HBM once no matter how many branches consume it."""
    holders = [[None, None] for _ in specs]

    def run(scan_cols, scan_mask, dict_tables_list, src_cols_list, pvals_list):
        outs = []
        for spec, holder, dts, scs, pv in zip(
            specs, holders, dict_tables_list, src_cols_list, pvals_list
        ):
            var, rel, n, sorted_on, rest, dict_meta = spec
            t = Table(dict(scan_cols), n, mask=scan_mask, sorted_on=sorted_on)
            f = Frame({var: t}, (var,), {var: rel})
            denv = {
                s: BuiltDict(
                    DictResult(ds, dts[s]), choice, lanes=lanes, kind=kind
                )
                for s, (ds, kind, lanes, choice) in dict_meta.items()
            }
            outs.append(
                _region_stages(
                    rest, f, denv, scs, pv, sigma, allow_sorted, holder
                )
            )
        return tuple(outs)

    return jax.jit(run), holders


def _run_shared_region(region, envs, refss, db, sigma, allow_sorted, params_list):
    """Execute one shared-scan region: every branch's filters, probes, and
    semiring terminals run against ONE pass over ``region.source``, then
    results demultiplex into each owning plan's environment.

    Under the Pallas kernel policy each branch dispatches through its own
    ``_run_pipeline`` instead — the fused kernel's per-region residency
    accounting stays honest and the report's region modes name the path
    that actually produced each terminal; the scan dedup is an XLA-path win."""
    from repro.core import plan as P
    from repro.kernels import ops as _kops

    use_pallas, _ = _kops.fused_pipeline_policy()
    if use_pallas:
        for br in region.branches:
            _run_pipeline(
                br.pipe, envs[br.plan_idx], refss[br.plan_idx], db, sigma,
                allow_sorted, params_list[br.plan_idx],
            )
        return

    rel = region.source
    t0 = db[rel]
    union_cols: set = set()
    branch_info = []
    for br in region.branches:
        stages = br.pipe.stages
        sc = stages[0]
        assert isinstance(sc, P.Scan) and sc.source == rel, br
        rest = stages[1:]
        need = P.needed_columns(stages)
        # "__val__"/"__key__" are pseudo-columns (bag multiplicity / whole
        # key) resolved off the frame, not physical fact columns
        union_cols.update(
            c for c in need.get(sc.var, ()) if c in t0.columns
        )
        env = envs[br.plan_idx]
        dict_syms = []
        for node in rest:
            if isinstance(node, (P.HashProbe, P.GroupJoin)):
                dict_syms.append(node.build)
            elif isinstance(node, P.Reduce) and node.lookup_sym is not None:
                dict_syms.append(node.lookup_sym)
        dict_syms = tuple(dict.fromkeys(dict_syms))
        builts = {s: env[s] for s in dict_syms}
        src_cols: Dict[str, Dict[str, jax.Array]] = {}
        for node in rest:
            if isinstance(node, P.HashProbe):
                b = builts[node.build]
                want = need.get(node.inner_var, ())
                src_cols[node.out] = {
                    c: b.src.col(c) for c in b.src.names() if c in want
                }
        branch_info.append((br, sc, rest, dict_syms, builts, src_cols))

    statics = (
        "shared",
        rel,
        t0.nrows,
        t0.sorted_on,
        t0.mask is not None,
        tuple(sorted(union_cols)),
        tuple(
            (
                repr((br.pipe.source, br.pipe.stages)),
                tuple(
                    (s, builts[s].res.ds, builts[s].kind, builts[s].lanes,
                     builts[s].choice)
                    for s in dict_syms
                ),
                tuple((o, tuple(sorted(cs))) for o, cs in src_cols.items()),
            )
            for br, sc, rest, dict_syms, builts, src_cols in branch_info
        ),
        bool(allow_sorted),
        _sigma_signature(sigma),
    )
    entry = _REGION_CACHE.get(statics)
    if entry is None:
        specs = tuple(
            (
                sc.var,
                rel,
                t0.nrows,
                t0.sorted_on,
                rest,
                {
                    s: (b.res.ds, b.kind, b.lanes, b.choice)
                    for s, b in builts.items()
                },
            )
            for br, sc, rest, dict_syms, builts, src_cols in branch_info
        )
        entry = _make_shared_region_fn(specs, sigma, allow_sorted)
        if len(_REGION_CACHE) >= _REGION_CACHE_MAX:
            _REGION_CACHE.pop(next(iter(_REGION_CACHE)))
        _REGION_CACHE[statics] = entry
    fn, holders = entry

    scan_cols = {c: t0.col(c) for c in sorted(union_cols)}
    dict_tables_list = [
        {s: bi[4][s].res.table for s in bi[3]} for bi in branch_info
    ]
    src_cols_list = [bi[5] for bi in branch_info]
    pvals_list = [
        dict(params_list[bi[0].plan_idx] or {}) for bi in branch_info
    ]
    outs = fn(scan_cols, t0.mask, dict_tables_list, src_cols_list, pvals_list)

    n_br = len(region.branches)
    for (br, sc, rest, *_), holder, out in zip(branch_info, holders, outs):
        term = rest[-1]
        # publication frame carries the FULL scan table: an index terminal's
        # ``src`` serves downstream probe gathers, which may read columns
        # the shared region itself never touched
        f = Frame({sc.var: t0}, (sc.var,), {sc.var: rel})
        _publish_region_result(
            term, out, holder[0], holder[1], f,
            envs[br.plan_idx], refss[br.plan_idx],
        )
        _record_region(
            term.out, f"shared:{n_br}", family=_terminal_family(term)
        )


def execute_shared_plan(
    sp,
    db: Dict[str, "Table"],
    sigma=None,
    allow_sorted: bool = True,
    params_list=None,
    exchange_impl=None,
    repartition_impl=None,
):
    """Execute every plan of a ``SharedPlan``, paying each shared-scan
    region's fact pass once.

    A small readiness-driven interleave: each plan advances node by node
    (via ``_exec_node``) until it stalls on a not-yet-run shared region;
    a region runs as soon as every branch's external inputs (build-side
    dictionaries from the owning plan) are available; region-covered nodes
    are skipped — the region publishes their terminal symbols directly.
    Results come back in ``sp.plans`` order, one per plan, each identical
    (bitwise) to what per-query ``execute_plan`` would return."""
    from repro.core import plan as P

    nplans = len(sp.plans)
    if params_list is None:
        params_list = [None] * nplans
    envs: List[Dict[str, object]] = [{} for _ in range(nplans)]
    refss: List[Dict[str, object]] = [{} for _ in range(nplans)]

    rep = _begin_report()
    t_plan = time.perf_counter()
    try:
        return _execute_shared_plan_body(
            sp, db, sigma, allow_sorted, params_list, exchange_impl,
            repartition_impl, envs, refss,
        )
    finally:
        _end_report(rep, time.perf_counter() - t_plan)


def _execute_shared_plan_body(
    sp, db, sigma, allow_sorted, params_list, exchange_impl,
    repartition_impl, envs, refss,
):
    from repro.core import plan as P

    nplans = len(sp.plans)
    region_of: Dict[Tuple[int, str], int] = {}
    for ri, rg in enumerate(sp.regions):
        for b in rg.branches:
            for s in b.covered:
                region_of[(b.plan_idx, s)] = ri
    done = [False] * len(sp.regions)
    pos = [0] * nplans

    def _ready(rg) -> bool:
        for b in rg.branches:
            own = {st.out for st in b.pipe.stages}
            env, refs = envs[b.plan_idx], refss[b.plan_idx]
            for st in b.pipe.stages:
                for r in P._node_refs(st):
                    if r in own or r == b.pipe.source or r in db:
                        continue
                    if r not in env and r not in refs:
                        return False
        return True

    while True:
        progress = False
        for i, p in enumerate(sp.plans):
            while pos[i] < len(p.nodes):
                nd = p.nodes[pos[i]]
                ri = region_of.get((i, nd.out))
                if ri is not None and not done[ri]:
                    break  # stalled on a pending shared region
                if ri is None:
                    _exec_node(
                        nd, envs[i], refss[i], db, sigma, allow_sorted,
                        params_list[i], exchange_impl, repartition_impl,
                    )
                pos[i] += 1
                progress = True
        if all(pos[i] >= len(p.nodes) for i, p in enumerate(sp.plans)):
            break
        for ri, rg in enumerate(sp.regions):
            if not done[ri] and _ready(rg):
                _run_shared_region(
                    rg, envs, refss, db, sigma, allow_sorted, params_list
                )
                done[ri] = True
                progress = True
        if not progress:  # pragma: no cover
            raise RuntimeError(
                "shared-scan scheduler stalled: a region's inputs depend on "
                "nodes the region itself covers"
            )
    return [
        _plan_result(p, envs[i], refss[i]) for i, p in enumerate(sp.plans)
    ]


class SharedExecutable:
    """A compiled multi-query batch: ONE jitted function runs every plan of
    a ``SharedPlan``, shared regions paying the fact-table pass once.
    Output order matches ``sp.plans``; each result is wrapped exactly like
    the single-query ``Executable``'s, so callers demux by position."""

    def __init__(self, sp, db: Dict[str, "Table"], sigma=None):
        self.sp = sp
        self.sigma = sigma
        self.trace_count = 0
        self.calls = 0
        self.last_report: Optional[ExecutionReport] = None
        self._trace_report: Optional[ExecutionReport] = None
        self._metas: Optional[Tuple[Tuple[str, object], ...]] = None
        self._sorted_meta = {rel: t.sorted_on for rel, t in db.items()}

        def _run(cols, masks, pvals_list):
            self.trace_count += 1  # python side effect: fires per trace only
            local = {}
            for rel, rc in cols.items():
                n = next(iter(rc.values())).shape[0]
                local[rel] = Table(
                    rc, n, mask=masks[rel], sorted_on=self._sorted_meta[rel]
                )
            outs = execute_shared_plan(
                self.sp, local, sigma=self.sigma, params_list=list(pvals_list)
            )
            self._trace_report = last_report()
            metas, flat = [], []
            for out in outs:
                if isinstance(out, DictResult):
                    metas.append(("dict", out.ds))
                    flat.append(out.arrays())
                elif isinstance(out, Table):
                    metas.append(("table", out.sorted_on))
                    flat.append((out.columns, out.live_mask()))
                elif isinstance(out, dict):
                    metas.append(("refs", None))
                    flat.append(out)
                else:
                    raise TypeError(
                        f"shared executable supports dictionary, relation, "
                        f"and scalar-record results, got {type(out).__name__}"
                    )
            self._metas = tuple(metas)
            return tuple(flat)

        self._fn = jax.jit(_run)

    def coerce_params(self, params_list=None):
        params_list = params_list or [None] * len(self.sp.plans)
        return tuple(
            coerce_bindings(p, params_list[i])
            for i, p in enumerate(self.sp.plans)
        )

    def __call__(self, db: Dict[str, "Table"], params_list=None):
        self.calls += 1
        cols, masks = Executable._db_arrays(db)
        _faults.check("kernel-launch", detail="shared")
        t0 = time.perf_counter()
        try:
            out = self._fn(cols, masks, self.coerce_params(params_list))
        except Exception as e:  # noqa: BLE001
            _raise_classified(e)
        self.last_report = republish_report(
            self._trace_report, time.perf_counter() - t0, self.trace_count
        )
        res = []
        for (kind, aux), o in zip(self._metas, out):
            if kind == "dict":
                res.append(PlanResult(aux, *o))
            elif kind == "table":
                c, m = o
                n = next(iter(c.values())).shape[0]
                res.append(Table(dict(c), n, mask=m, sorted_on=aux))
            else:
                res.append(o)
        return res


_SHARED_EXEC_CACHE: Dict[tuple, "SharedExecutable"] = {}


def cached_shared_executable(sp, db: Dict[str, "Table"], sigma=None):
    """Shared-batch twin of ``cached_executable``: keyed by the SharedPlan
    fingerprint (plan fingerprints + merged regions), schema, and Σ."""
    key = (sp.fingerprint(), _db_signature(db), _sigma_signature(sigma))
    ex = _SHARED_EXEC_CACHE.get(key)
    if ex is None:
        _faults.check("compile", detail="shared")
        ex = SharedExecutable(sp, db, sigma=sigma)
        if len(_SHARED_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _SHARED_EXEC_CACHE.pop(next(iter(_SHARED_EXEC_CACHE)))
        _SHARED_EXEC_CACHE[key] = ex
    return ex


# ---------------------------------------------------------------------------
# executable cache: compile once per query shape, execute many bindings
# ---------------------------------------------------------------------------
#
# The paper pays synthesis + code generation once per query; with
# parameterization (L.Param) the same split applies per query *shape*: the
# whole plan execution is traced into ONE jitted function of
# (columns, masks, parameter values), cached by
# (plan fingerprint, DictChoice tuple, table schema, Σ signature).  A fresh
# binding is just a new runtime scalar — zero synthesis, zero retracing
# (DESIGN.md §6).


@dataclass
class PlanResult:
    """Array view of a dictionary-valued plan result coming out of the jitted
    executable (the backend table object never crosses the jit boundary)."""

    ds: str
    keys: jax.Array
    vals: jax.Array
    valid: jax.Array

    def arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return self.keys, self.vals, self.valid

    def items_np(self) -> Dict[int, np.ndarray]:
        return host_items(self.keys, self.vals, self.valid)

    def size(self) -> int:
        return int(np.asarray(self.valid).sum())


_KIND_DTYPES = {
    "int": jnp.int32,
    "bool": jnp.bool_,
    "double": jnp.float32,
    "string": jnp.int32,  # dictionary-encoded
}


def _raise_classified(err: BaseException):
    """Executor-boundary error translation: re-raise ``err`` as its typed
    classification (``errors.classify``) chained via ``from``, or unchanged
    when it is none of our business.  Nothing above the executor needs to
    string-match an XLA message."""
    typed = _errors.classify(err)
    if typed is not None and typed is not err:
        raise typed from err
    raise err


def coerce_bindings(plan, params, defaults=None):
    """Validate a parameter binding against ``plan.params`` and coerce every
    value to its declared scalar dtype — stable dtypes keep the jit avals
    identical across rebinds.  Shared by the single-shard executable and the
    sharded executor, so validation semantics can't drift."""
    params = {**(defaults or {}), **(params or {})}
    declared = dict(plan.params)
    unknown = set(params) - set(declared)
    if unknown:
        raise KeyError(f"unknown parameters {sorted(unknown)}")
    missing = set(declared) - set(params)
    if missing:
        raise KeyError(f"missing bindings for {sorted(missing)}")
    return {
        name: jnp.asarray(params[name], _KIND_DTYPES.get(kind, jnp.float32))
        for name, kind in plan.params
    }


def validate_binding(plan, params, defaults=None):
    """API-boundary binding validation (DESIGN.md §12): raises a permanent
    :class:`repro.errors.PlanError` — unknown names, missing bindings, NaN
    floats, and kind-incompatible values are caller bugs that must surface
    *before* tracing, not as a shape error deep inside jit.

    ``coerce_bindings`` (above) keeps its legacy ``KeyError`` contract for
    internal callers; this is the typed front door used by ``Session.query``
    and ``QueryServer``.  Returns the merged plain-python binding dict."""
    merged = {**(defaults or {}), **(params or {})}
    declared = dict(plan.params)
    unknown = sorted(set(merged) - set(declared))
    if unknown:
        raise _errors.PlanError(
            f"unknown parameter(s) {unknown}; "
            f"declared: {sorted(declared)}"
        )
    missing = sorted(set(declared) - set(merged))
    if missing:
        raise _errors.PlanError(f"missing binding(s) for {missing}")
    for name, kind in plan.params:
        v = merged[name]
        if isinstance(v, (jax.Array, np.ndarray, np.generic)):
            if np.ndim(v) != 0:
                raise _errors.PlanError(
                    f"parameter {name!r} must be a scalar, got shape "
                    f"{np.shape(v)}"
                )
            v = np.asarray(v).item()
        if kind == "double":
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise _errors.PlanError(
                    f"parameter {name!r} is double; got "
                    f"{type(v).__name__} {v!r}"
                )
            if isinstance(v, float) and v != v:
                raise _errors.PlanError(f"parameter {name!r} is NaN")
        elif kind in ("int", "string"):
            if isinstance(v, bool) or not isinstance(v, (int, np.integer)):
                raise _errors.PlanError(
                    f"parameter {name!r} is {kind} (integral); got "
                    f"{type(v).__name__} {v!r}"
                )
        elif kind == "bool":
            if not isinstance(v, (bool, np.bool_)):
                raise _errors.PlanError(
                    f"parameter {name!r} is bool; got "
                    f"{type(v).__name__} {v!r}"
                )
    return merged


class Executable:
    """A compiled query shape: one jitted function over (db arrays, params).

    ``trace_count`` increments only when jax actually (re)traces the body —
    the no-retrace-on-rebind guarantee is asserted against it in tests.  A
    vmapped twin serves micro-batched execution (one stacked run for B
    same-shape requests); each batch-size bucket traces once.
    """

    #: batched calls run one stacked vmapped trace per power-of-two bucket
    #: (``QueryServer.warm_up`` pre-traces the buckets when True)
    vmapped_batches = True

    def __init__(self, plan, db: Dict[str, "Table"], sigma=None):
        from repro.core import plan as P

        self._default_params = None
        if isinstance(plan, P.BoundPlan):
            self._default_params = plan.binding_map()
            plan = plan.plan
        self.plan = plan
        self.sigma = sigma
        self.fused_regions = sum(
            isinstance(n, P.Pipeline) for n in plan.nodes
        )
        self.trace_count = 0
        self.calls = 0
        self.last_report: Optional[ExecutionReport] = None
        self._trace_report: Optional[ExecutionReport] = None
        self._meta: Optional[Tuple[str, object]] = None
        self._sorted_meta = {rel: t.sorted_on for rel, t in db.items()}

        def _run(cols, masks, pvals):
            self.trace_count += 1  # python side effect: fires per trace only
            local = {}
            for rel, rc in cols.items():
                n = next(iter(rc.values())).shape[0]
                local[rel] = Table(
                    rc, n, mask=masks[rel], sorted_on=self._sorted_meta[rel]
                )
            out = execute_plan(self.plan, local, sigma=self.sigma, params=pvals)
            self._trace_report = last_report()  # region structure is static
            if isinstance(out, DictResult):
                self._meta = ("dict", out.ds)
                return out.arrays()
            if isinstance(out, Table):
                self._meta = ("table", out.sorted_on)
                return out.columns, out.live_mask()
            if not isinstance(out, dict):
                raise TypeError(
                    f"executable cache supports dictionary, relation, and "
                    f"scalar-record results, got {type(out).__name__}"
                )
            self._meta = ("refs", None)  # scalar ref record (plain pytree)
            return out

        self._fn = jax.jit(_run)
        self._vfn = jax.jit(jax.vmap(_run, in_axes=(None, None, 0)))

    # -- parameter handling -------------------------------------------------
    def coerce_params(self, params: Optional[Dict[str, object]]):
        return coerce_bindings(self.plan, params, defaults=self._default_params)

    @staticmethod
    def _db_arrays(db: Dict[str, "Table"]):
        cols = {rel: dict(t.columns) for rel, t in db.items()}
        masks = {rel: t.live_mask() for rel, t in db.items()}
        return cols, masks

    def _wrap(self, out):
        kind, aux = self._meta
        if kind == "dict":
            return PlanResult(aux, *out)
        if kind == "table":
            c, m = out
            n = next(iter(c.values())).shape[0]
            return Table(dict(c), n, mask=m, sorted_on=aux)
        return out

    # -- execution ----------------------------------------------------------
    def __call__(self, db: Dict[str, "Table"], params=None):
        self.calls += 1
        cols, masks = self._db_arrays(db)
        # injection point: resident whole-plan dispatch.  The streamed
        # executor never passes through here — which is why streaming is the
        # degradation ladder's last rung.  ``fused-region`` is checked here
        # (not only inside ``_run_pipeline``, which runs at trace time) so
        # warm calls hit it too; the materialized node-by-node plan has no
        # Pipeline nodes and skips it — one rung of the ladder.
        _faults.check("kernel-launch")
        if self.fused_regions:
            _faults.check("fused-region")
        # Dispatch stays async (callers force results when they read them;
        # adapt racing blocks explicitly), so wall_s here is dispatch wall.
        t0 = time.perf_counter()
        try:
            out = self._fn(cols, masks, self.coerce_params(params))
        except Exception as e:  # noqa: BLE001 — boundary translation only
            _raise_classified(e)
        self.last_report = republish_report(
            self._trace_report, time.perf_counter() - t0, self.trace_count
        )
        return self._wrap(out)

    def call_batched(self, db: Dict[str, "Table"], params_list):
        """One stacked (vmapped) execution of B same-shape requests.  The
        batch is padded to a power-of-two bucket so the number of distinct
        traces stays logarithmic in the largest batch ever seen."""
        if not params_list:
            return []
        if not self.plan.params:  # nothing to vmap over: one run fits all
            one = self(db, None)
            return [one for _ in params_list]
        b = len(params_list)
        bucket = 1
        while bucket < b:
            bucket *= 2
        coerced = [self.coerce_params(p) for p in params_list]
        coerced += [coerced[-1]] * (bucket - b)  # pad, outputs discarded
        stacked = {
            name: jnp.stack([c[name] for c in coerced])
            for name in coerced[0]
        }
        self.calls += 1
        cols, masks = self._db_arrays(db)
        _faults.check("kernel-launch")
        if self.fused_regions:
            _faults.check("fused-region")
        t0 = time.perf_counter()
        try:
            out = self._vfn(cols, masks, stacked)
        except Exception as e:  # noqa: BLE001
            _raise_classified(e)
        self.last_report = republish_report(
            self._trace_report, time.perf_counter() - t0, self.trace_count
        )
        return [
            self._wrap(jax.tree.map(lambda a: a[i], out)) for i in range(b)
        ]


@dataclass
class BoundExecutable:
    """A cached executable viewed through a ``BoundPlan``'s bindings: the
    underlying ``Executable`` (and its trace) is shared across bindings;
    call-time params override the bound ones."""

    executable: Executable
    bindings: Dict[str, object]

    def __call__(self, db, params=None):
        return self.executable(db, {**self.bindings, **(params or {})})

    def call_batched(self, db, params_list):
        return self.executable.call_batched(
            db, [{**self.bindings, **(p or {})} for p in params_list]
        )

    @property
    def trace_count(self) -> int:
        return self.executable.trace_count

    @property
    def vmapped_batches(self) -> bool:
        return self.executable.vmapped_batches

    @property
    def last_report(self) -> Optional[ExecutionReport]:
        return self.executable.last_report

    @property
    def plan(self):
        return self.executable.plan


class StreamedExecutable:
    """Executable facade for databases holding chunked (out-of-core)
    relations.  The streamed driver is a host-side loop over chunks, so
    there is no whole-plan jit to wrap — each call runs ``execute_plan``
    eagerly; the per-chunk region functions inside are compiled once and
    cached (``_REGION_CACHE``), so repeated calls and parameter rebinds
    re-enter compiled code just like the resident ``Executable``."""

    #: batched calls loop the eager driver — no vmapped buckets to warm
    vmapped_batches = False

    def __init__(self, plan, db: Dict[str, "Table"], sigma=None):
        from repro.core import plan as P

        self._default_params = None
        if isinstance(plan, P.BoundPlan):
            self._default_params = plan.binding_map()
            plan = plan.plan
        self.plan = plan
        self.sigma = sigma
        #: region-fn traces during this executable's calls: the first call
        #: of a shape, or a chunk whose decode recipe is new; never a rebind
        self.trace_count = 0
        self.calls = 0
        self.last_report: Optional[ExecutionReport] = None

    def coerce_params(self, params: Optional[Dict[str, object]]):
        return coerce_bindings(self.plan, params, defaults=self._default_params)

    def __call__(self, db: Dict[str, "Table"], params=None):
        self.calls += 1
        traces = _REGION_TRACES
        try:
            out = execute_plan(
                self.plan, db, sigma=self.sigma,
                params=self.coerce_params(params),
            )
        except Exception as e:  # noqa: BLE001
            _raise_classified(e)
        finally:
            self.trace_count += _REGION_TRACES - traces
        rep = last_report()  # eager driver: the report is per call already
        rep.trace_count = self.trace_count
        self.last_report = rep
        if isinstance(out, DictResult):
            return PlanResult(out.ds, *out.arrays())
        return out

    def call_batched(self, db: Dict[str, "Table"], params_list):
        return [self(db, p) for p in params_list]


_EXEC_CACHE: Dict[tuple, Executable] = {}
_EXEC_CACHE_STATS = {"hits": 0, "misses": 0}
_EXEC_CACHE_MAX = 64  # evict oldest beyond this (long-running servers)


def _db_signature(db: Dict[str, "Table"]) -> tuple:
    sig = []
    for rel, t in sorted(db.items()):
        if _is_chunked(t):
            sig.append((rel, "chunked") + tuple(t.signature()))
        else:
            sig.append(
                (
                    rel,
                    t.nrows,
                    t.mask is None,
                    t.sorted_on,
                    tuple(
                        (c, str(a.dtype))
                        for c, a in sorted(t.columns.items())
                    ),
                )
            )
    return tuple(sig)


def _sigma_signature(sigma) -> tuple:
    if sigma is None:
        return ()
    return tuple(
        (rel, st.rows, tuple(sorted((c, cs.distinct) for c, cs in st.columns.items())))
        for rel, st in sorted(sigma.rels.items())
    )


def cached_executable(plan, db: Dict[str, "Table"], sigma=None):
    """The executable cache: keyed by (plan fingerprint, DictChoice tuple,
    table schema, Σ signature).  A repeated call with a fresh parameter
    binding — or even a freshly re-compiled but structurally identical plan —
    hits the already-jitted function.  A ``BoundPlan`` shares the underlying
    plan's cache entry; its bindings ride along as call-time defaults."""
    from repro.core import plan as P

    bound = None
    if isinstance(plan, P.BoundPlan):
        bound = plan.binding_map()
        plan = plan.plan
    key = (
        plan.fingerprint(),
        plan.choices,
        _db_signature(db),
        _sigma_signature(sigma),
    )
    ex = _EXEC_CACHE.get(key)
    if ex is None:
        _EXEC_CACHE_STATS["misses"] += 1
        # injection point: cold-shape executable construction.  Fires before
        # the cache insert, so a failed compile leaves no entry behind and a
        # retry re-enters the compile from scratch.
        _faults.check("compile", detail=str(plan.fingerprint())[:40])
        cls = (
            StreamedExecutable
            if any(_is_chunked(t) for t in db.values())
            else Executable
        )
        ex = cls(plan, db, sigma=sigma)
        if len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
        _EXEC_CACHE[key] = ex
    else:
        _EXEC_CACHE_STATS["hits"] += 1
    return ex if bound is None else BoundExecutable(ex, bound)


def exec_cache_stats() -> Dict[str, int]:
    return dict(_EXEC_CACHE_STATS, entries=len(_EXEC_CACHE))


def clear_exec_cache() -> None:
    _EXEC_CACHE.clear()
    _SHARED_EXEC_CACHE.clear()
    # the per-region jitted fns survive executable reconstruction; keeping
    # them would let a "cold" rebuild skip trace-time work (dict builds)
    _REGION_CACHE.clear()
    _EXEC_CACHE_STATS.update(hits=0, misses=0)


# ---------------------------------------------------------------------------
# sort-based aggregation via the segment_reduce kernel (direct form)
# ---------------------------------------------------------------------------


def sort_groupby_arrays(
    keys: jax.Array, vals: jax.Array, valid: Optional[jax.Array] = None,
    assume_sorted: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (keys[n], sums[n, V], end_mask[n]) — run totals at run ends.
    The raw sort-aggregate pipeline (sort → segment reduce), used by the
    distributed path and the in-DB ML operator where the dictionary object
    itself is not needed downstream."""
    if vals.ndim == 1:
        vals = vals[:, None]
    if valid is not None:
        keys = jnp.where(valid.astype(bool), keys, dbase.PAD)
        vals = jnp.where(valid.astype(bool)[:, None], vals, 0.0)
        assume_sorted = False
    if not assume_sorted:
        perm = jnp.argsort(keys)
        keys, vals = keys[perm], vals[perm]
    sums, ends = kops.segment_reduce(keys, vals)
    return keys, sums, ends


# ---------------------------------------------------------------------------
# in-DB ML: factorized covariance (paper Fig. 7d)
# ---------------------------------------------------------------------------


def covar_factorized(
    s_table: Table,
    r_table: Table,
    join_col: str = "s",
    i_col: str = "i",
    c_col: str = "c",
    ragg_ds: str = "st_sorted",
    sorted_probes: bool = True,
    ragg_capacity: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """Covariance terms over S ⋈ R without materializing the join.

    S is assumed physically ordered on the join column (the paper's trie
    index): the inner partial aggregates (i·i, i, 1 per group — Fig. 7d's
    ``sagg``) come straight from one segment_reduce pass; R's partial
    aggregates (m, c, c·c — ``Ragg``) are one group-by; the final combine is
    three fused multiplies over the group stream.
    """
    s = s_table.col(join_col)
    i = s_table.col(i_col)
    ones = jnp.ones_like(i)
    sagg_in = jnp.stack([i * i, i, ones], axis=1)  # [n, 3]
    skeys, ssums, sends = sort_groupby_arrays(
        s, sagg_in, valid=s_table.mask,
        assume_sorted=s_table.sorted_on[:1] == (join_col,),
    )

    c = r_table.col(c_col)
    ragg_in = jnp.stack([jnp.ones_like(c), c, c * c], axis=1)  # m, c, c_c
    cap = ragg_capacity or capacity_for(ragg_ds, r_table.nrows)
    ragg = groupby(
        r_table,
        r_table.col(join_col),
        ragg_in,
        ragg_ds,
        cap,
        assume_sorted=r_table.sorted_on[:1] == (join_col,),
    )

    # combine: for each S-group (emitted at run ends, keys sorted) look up
    # Ragg — the probe stream is sorted, so this is the hinted/merge path.
    rvals, found = lookup_dict(ragg, skeys, valid=sends, sorted_probes=sorted_probes)
    m_r, c_r, cc_r = rvals[:, 0], rvals[:, 1], rvals[:, 2]
    i_i = jnp.sum(jnp.where(found, ssums[:, 0] * m_r, 0.0))
    i_c = jnp.sum(jnp.where(found, ssums[:, 1] * c_r, 0.0))
    c_c = jnp.sum(jnp.where(found, ssums[:, 2] * cc_r, 0.0))
    return {"i_i": i_i, "i_c": i_c, "c_c": c_c}


def covar_naive(
    s_table: Table,
    r_table: Table,
    join_col: str = "s",
    i_col: str = "i",
    c_col: str = "c",
    index_ds: str = "ht_linear",
) -> Dict[str, jax.Array]:
    """Fig. 7a baseline: materialize the join (FK gather), then aggregate."""
    cap = capacity_for(index_ds, r_table.nrows)
    idx = build_index(index_ds, r_table.col(join_col), cap, valid=r_table.mask)
    joined = fk_join(
        s_table, s_table.col(join_col), r_table, idx, take=[c_col], prefix="r_"
    )
    i = joined.col(i_col)
    c = joined.col("r_" + c_col)
    vals = jnp.stack([i * i, i * c, c * c], axis=1)
    out = scalar_aggregate(joined, vals)
    return {"i_i": out[0], "i_c": out[1], "c_c": out[2]}
