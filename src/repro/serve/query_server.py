"""Batched analytical serving: compile-once/execute-many over parameterized
plans.

The LM serving loop (``serve_loop.Server``) amortizes one compiled decode
step across a batch of concurrent sequences; this is the same machinery
pointed at the analytical path.  A ``QueryServer`` owns a database and a
request queue; requests are ``(query name, parameter binding)`` pairs.  Per
query *shape* the server pays the paper's pipeline exactly once — Σ stats,
Algorithm 1 synthesis, plan lowering, and the whole-plan jit — via
``engine.cached_executable``; every later request with a fresh binding is a
warm hit: zero synthesis, zero retracing, parameters passed as runtime
scalars (DESIGN.md §6).

The server fronts a :class:`repro.session.Session` — the single planning
funnel (synthesize → fuse → storage plan → cached executable) — instead of
wiring db/Δ/Σ/caches itself: pass ``QueryServer(session)``; passing a raw
``{relation: Table}`` db dict (the pre-Session constructor) still works as
a deprecated shim that opens a session internally.  Adaptive sessions
(``connect(db, adapt=...)``) race near-cost plans once at shape warm-up,
so serving always rides the measured winner with zero per-request
replanning (trace counts stay flat — DESIGN.md §11).

Micro-batching: each ``step()`` drains up to ``max_batch`` queued requests
for the *same* query shape and runs them as a single vmapped execution
(``Executable.call_batched``), padded to power-of-two buckets so the number
of distinct traces stays logarithmic.  Draining is round-based: a step
serves only requests that were queued when its round began, so a stream of
one shape can never starve an earlier request of another (arrival-order
fairness).  With ``share_scans=True`` a round's batch may mix *different*
query shapes whose plans share a fact-table scan: the batch executes as one
``SharedPlan`` pass (``plan.merge_shared_scans`` +
``engine.cached_shared_executable`` — DESIGN.md §9) and responses demux
back to their requests by rid.

Sharded sessions (``connect(db, shards=N)``) serve through the same loop:
``session.shape`` compiles onto ``distributed.cached_sharded_executor``
and the ``ShardedExecutable`` adapter speaks the executable interface, so
admission, deadlines, EWMA shedding, retry, and the ladder all apply
unchanged.  Collectives cannot ride ``vmap``, so a sharded micro-batch
executes as B warm launches of the one cached ``shard_map`` trace
(``vmapped_batches=False`` — batching still amortizes queueing and drain
overhead, not the launch).  Only ``share_scans=True`` stays per-host:
cross-query shared-scan merging is not wired through ``shard_map``, and
that combination raises :class:`UnsupportedSessionError` at construction.

Fault tolerance (DESIGN.md §12) — every submitted request terminates with a
result or a *typed* error, never silence:

* **admission** — the queue is bounded (``max_queue``); beyond it
  ``submit`` raises :class:`AdmissionRejected` carrying the observed depth
  and a retry-after hint derived from warm throughput;
* **deadlines** — ``submit(..., deadline_s=...)``: expired requests are
  swept to :class:`DeadlineExceeded` responses, and a request is never
  *placed* in a round that the shape's warm-latency EWMA predicts will
  miss its deadline (shed early, with the prediction attached);
* **validation** — bindings are checked per request against the shape's
  declared params (typed ``PlanError`` response), so one malformed request
  cannot poison its batch;
* **retry** — transient faults (injected, compile) retry the batch with
  exponential backoff + deterministic jitter, capped per request;
* **degradation** — a device OOM or exhausted retries falls back to
  per-request execution through ``Session.execute_shape``, which walks the
  validated degradation ladder (fused → materialized → streamed) under the
  session's per-(shape, mode) circuit breakers.

Warm/cold latency and throughput counters are exposed through ``stats()``
— ``benchmarks/serve_bench.py`` and ``benchmarks/serve_fault_bench.py``
turn them into the BENCH records the CI perf gates enforce.
"""
from __future__ import annotations

import random
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np

from repro import errors
from repro.core.adapt import block_result, result_items
from repro.exec import engine as E
from repro.exec.queries import QUERIES, Query

#: retry-after hint (seconds) when admission-rejecting before ANY warm
#: latency has been observed — deliberately conservative (one cold compile
#: is tens of ms on CPU, more on device): a client backing off this long
#: cannot re-arrive before the first batch could possibly have drained.
#: Once a shape has served warm traffic the hint uses the measured EWMA.
COLD_RETRY_AFTER_S = 0.05


@dataclass
class QueryRequest:
    rid: int
    qname: str
    params: Dict[str, object]
    t_submit: float = 0.0
    deadline_s: Optional[float] = None  # relative budget given at submit
    t_deadline: Optional[float] = None  # absolute (server-clock) deadline


@dataclass
class QueryResponse:
    rid: int
    qname: str
    params: Dict[str, object]
    result: Optional[Dict[int, np.ndarray]]
    latency_s: float
    warm: bool  # shape was already compiled when this request ran
    batch_size: int = 1
    error: Optional[BaseException] = None  # typed ReproError on failure
    #: ``error.to_dict()`` wire form (kind/transient/message + payload) —
    #: what a network client would receive; None on success
    error_info: Optional[Dict[str, object]] = None
    retries: int = 0  # transient-fault retries consumed
    degraded: str = ""  # ladder rung that produced the result, if not primary

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclass
class _Shape:
    """One compiled query shape: choices + cached executable + bookkeeping."""

    query: Query
    executable: E.Executable
    choices: Dict[str, object]
    compile_s: float  # cold cost actually paid: synthesis + lowering + jit
    plan: object = None  # fused physical plan (shared-scan merge input)
    session_shape: object = None  # repro.session.Shape (ladder entry point)
    served: int = 0
    busy_s: float = 0.0  # batch wall, dispatch to answers, attributed to this shape
    ewma_s: Optional[float] = None  # warm batch-wall EWMA (deadline predictor)


class QueryServer:
    def __init__(
        self,
        session,
        delta=None,
        queries: Optional[Dict[str, Query]] = None,
        max_batch: int = 8,
        share_scans: bool = False,
        max_queue: int = 1024,
        max_retries: int = 3,
        backoff_s: float = 0.001,
        backoff_cap_s: float = 0.05,
        default_deadline_s: Optional[float] = None,
        seed: int = 0,
        clock=None,
    ):
        from repro.session import Session, connect

        if not isinstance(session, Session):
            # deprecated shim: a raw {relation: Table} db dict opens a
            # session on the spot (the old constructor-soup signature)
            session = connect(session, delta=delta, queries=queries)
        if session.mesh is not None and share_scans:
            raise errors.UnsupportedSessionError(
                f"share_scans=True cannot front a sharded session "
                f"({session.shards} shards): cross-query shared-scan "
                f"merging is per-host only; serve sharded sessions with "
                f"share_scans=False"
            )
        self.session = session
        self.db = session.db
        self.delta = session.delta
        self.queries = dict(queries or session.queries or QUERIES)
        self.max_batch = max_batch
        self.share_scans = share_scans
        self.max_queue = max_queue
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.default_deadline_s = default_deadline_s
        self._rng = random.Random(seed)  # deterministic backoff jitter
        #: monotonic clock driving deadlines, latency counters, and the
        #: EWMA shedding predictor — injectable (``clock=``) so tests
        #: advance time instead of sleeping
        self._clock = clock if clock is not None else time.perf_counter
        self.sigma = session.sigma
        self.queue: List[QueryRequest] = []
        self.finished: List[QueryResponse] = []
        self._shapes: Dict[str, _Shape] = {}
        self._round: List[QueryRequest] = []  # current fairness round
        self._compat: Dict[tuple, bool] = {}  # qname pair -> mergeable
        self._next_rid = 0
        self.counters = {
            "requests": 0,
            "responses": 0,
            "batches": 0,
            "shared_batches": 0,
            "cold_compiles": 0,
            "synth_runs": 0,
            "warm_hits": 0,
            # fault-tolerance ledger (DESIGN.md §12)
            "rejected": 0,  # AdmissionRejected at submit
            "shed_deadline": 0,  # expired or predicted-to-miss requests
            "invalid": 0,  # PlanError responses (binding validation)
            "retries": 0,  # transient-fault retry attempts
            "faults": 0,  # typed faults observed while serving
            "degraded": 0,  # responses produced below the primary rung
            "errors": 0,  # responses carrying a typed error
            # seconds served requests waited in the queue: the sum of
            # (their batch's start - submit)
            "queue_wait_s": 0.0,
            # dictionary slots fetched to the host for answers, and the
            # entries those answers kept
            "result_slots": 0,
            "result_entries": 0,
        }
        self._lat = {"warm": [], "cold": []}
        self._busy = {"warm": 0.0, "cold": 0.0}

    # -- cold path: once per query shape ------------------------------------
    def _shape(self, qname: str) -> _Shape:
        shape = self._shapes.get(qname)
        if shape is not None:
            self.counters["warm_hits"] += 1
            return shape
        q = self.queries[qname]
        t0 = self._clock()
        # the session is the planning funnel: synthesize → fuse → cached
        # executable, plus — for adaptive sessions — the warm-up race, so
        # the installed executable is already the measured winner
        ss = self.session.shape(q)
        ex = ss.executable
        # trigger the trace now so the first serve measures warm execution
        ex(self.db, q.bind_defaults({}))
        shape = _Shape(
            q, ex, dict(ss.choices), self._clock() - t0,
            plan=ss.plan, session_shape=ss,
        )
        self._shapes[qname] = shape
        self.counters["cold_compiles"] += 1
        self.counters["synth_runs"] += ss.synth_runs
        return shape

    def warm_up(self, qnames=None, batch_buckets: bool = True) -> None:
        """Precompile shapes so first requests hit the warm path.  With
        ``batch_buckets`` the vmapped power-of-two micro-batch buckets up to
        ``max_batch`` are traced too — after this, no request mix can
        trigger a compile.  Executables that don't vmap their batches
        (``vmapped_batches=False``: sharded, streamed) have exactly one
        trace, already warmed by ``_shape`` — no buckets to pre-trace."""
        for qname in qnames or sorted(self.queries):
            shape = self._shape(qname)
            if not batch_buckets or not getattr(
                shape.executable, "vmapped_batches", True
            ):
                continue
            binding = shape.query.bind_defaults({})
            b = 2
            while b < self.max_batch:
                shape.executable.call_batched(self.db, [binding] * b)
                b *= 2
            # a full batch pads to ceil-pow2(max_batch) — trace that bucket
            # too, so a non-power-of-two max_batch can't compile mid-serve
            if self.max_batch > 1:
                shape.executable.call_batched(
                    self.db, [binding] * self.max_batch
                )

    # -- request intake ------------------------------------------------------
    def submit(
        self, qname: str, deadline_s: Optional[float] = None, **params
    ) -> int:
        """Enqueue a request; returns its rid.  Raises ``KeyError`` for an
        unregistered query name and :class:`AdmissionRejected` (typed, with
        queue depth + retry-after hint) when the bounded queue is full —
        load shedding happens at the door, not by silent starvation."""
        if qname not in self.queries:
            raise KeyError(f"unknown query {qname!r}")
        depth = len(self.queue) + len(self._round)
        if depth >= self.max_queue:
            self.counters["rejected"] += 1
            raise errors.AdmissionRejected(
                f"queue full ({depth}/{self.max_queue})",
                queue_depth=depth,
                retry_after_s=self._retry_after_hint(depth),
            )
        rid = self._next_rid
        self._next_rid += 1
        now = self._clock()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        self.queue.append(
            QueryRequest(
                rid, qname, dict(params), t_submit=now,
                deadline_s=deadline_s,
                t_deadline=(
                    now + deadline_s if deadline_s is not None else None
                ),
            )
        )
        self.counters["requests"] += 1
        return rid

    def _retry_after_hint(self, depth: int) -> float:
        """How long until the queue has likely drained a batch: pending
        rounds × the mean warm batch wall.  Cold start (no shape has served
        warm traffic yet) falls back to :data:`COLD_RETRY_AFTER_S`."""
        walls = [
            s.ewma_s for s in self._shapes.values() if s.ewma_s is not None
        ]
        per_batch = (
            (sum(walls) / len(walls)) if walls else COLD_RETRY_AFTER_S
        )
        return max(1, depth // max(1, self.max_batch)) * per_batch

    # -- serving loop --------------------------------------------------------
    def _mergeable(self, qa: str, qb: str) -> bool:
        """Whether the two shapes' plans share a fused scan prefix — decided
        once per (pair, Σ) by actually running the merge pass on the two
        fused plans and caching whether it produced a region.  A typed
        failure while probing (e.g. an injected compile fault on a cold
        shape) just disables sharing for this round — the head shape's own
        resolution is retried under the batch retry loop."""
        from repro.core import plan as P

        key = tuple(sorted((qa, qb)))
        hit = self._compat.get(key)
        if hit is None:
            try:
                sp = P.merge_shared_scans(
                    [self._shape(qa).plan, self._shape(qb).plan],
                    sigma=self.sigma,
                )
            except errors.ReproError:
                return False  # not cached: probe again next round
            hit = bool(sp.regions)
            self._compat[key] = hit
        return hit

    def _take_batch(self) -> List[QueryRequest]:
        """Drain up to ``max_batch`` requests of the head request's query
        shape (plus, under ``share_scans``, merge-compatible shapes) from
        the current *round*, preserving the arrival order of everything
        else.  A round is the queue snapshot taken when the previous round
        drained: later arrivals cannot ride a round in progress, so a hot
        shape's stream can never starve an earlier request of another shape
        (arrival-order fairness)."""
        if not self._round:
            self._round, self.queue = self.queue, []
        if not self._round:
            return []
        head = self._round[0].qname
        batch, rest = [], []
        for req in self._round:
            ok = req.qname == head or (
                self.share_scans and self._mergeable(head, req.qname)
            )
            if ok and len(batch) < self.max_batch:
                batch.append(req)
            else:
                rest.append(req)
        self._round = rest
        return batch

    # -- fault handling -------------------------------------------------------
    def _fail(self, req: QueryRequest, err: BaseException, warm: bool,
              retries: int = 0) -> QueryResponse:
        """Terminate ``req`` with a typed error response — the no-silence
        guarantee: every submitted request reaches ``finished``."""
        resp = QueryResponse(
            rid=req.rid, qname=req.qname, params=req.params, result=None,
            latency_s=self._clock() - req.t_submit, warm=warm,
            error=err, retries=retries,
            error_info=(
                err.to_dict() if isinstance(err, errors.ReproError)
                else {
                    "kind": type(err).__name__,
                    "transient": errors.is_transient(err),
                    "message": str(err),
                }
            ),
        )
        self.counters["errors"] += 1
        self.counters["responses"] += 1
        self.finished.append(resp)
        return resp

    def _sweep_expired(self, now: float) -> List[QueryResponse]:
        """Expired requests get DeadlineExceeded, not silence."""
        out = []
        for store in (self._round, self.queue):
            keep = []
            for req in store:
                if req.t_deadline is not None and now > req.t_deadline:
                    self.counters["shed_deadline"] += 1
                    out.append(self._fail(
                        req,
                        errors.DeadlineExceeded(
                            f"deadline {req.deadline_s:.3f}s expired before "
                            f"service", deadline_s=req.deadline_s,
                        ),
                        warm=req.qname in self._shapes,
                    ))
                else:
                    keep.append(req)
            store[:] = keep
        return out

    def _shed_predicted_misses(
        self, batch: List[QueryRequest], now: float
    ):
        """Deadline-aware batching: a request is never placed in a round
        that the shape's warm batch-wall EWMA predicts will miss its
        deadline — shed NOW with the prediction attached, rather than
        burning a round to produce a result nobody can use.  Shapes with no
        latency history are admitted (no counters, no prediction).
        Returns ``(kept requests, shed responses)``."""
        kept, shed = [], []
        for req in batch:
            est = None
            shape = self._shapes.get(req.qname)
            if shape is not None:
                est = shape.ewma_s
            if (
                req.t_deadline is not None
                and est is not None
                and now + est > req.t_deadline
            ):
                self.counters["shed_deadline"] += 1
                shed.append(self._fail(
                    req,
                    errors.DeadlineExceeded(
                        f"round predicted to miss deadline "
                        f"({est * 1e3:.2f}ms predicted)",
                        deadline_s=req.deadline_s, predicted_s=est,
                    ),
                    warm=True,
                ))
            else:
                kept.append(req)
        return kept, shed

    def _validate(self, batch: List[QueryRequest]):
        """Per-request binding validation against the shape's declared
        params — a malformed request gets a typed ``PlanError`` response
        and cannot poison the rest of its batch.  Returns
        ``(kept requests, rejected responses)``."""
        kept, bad = [], []
        for req in batch:
            shape = self._shapes.get(req.qname)
            if shape is None:
                try:
                    shape = self._shape(req.qname)
                except Exception:  # noqa: BLE001 — resolution failures are
                    # the batch retry loop's job; keep the request in play
                    kept.append(req)
                    continue
            try:
                E.validate_binding(
                    shape.plan, req.params,
                    defaults=shape.query.bind_defaults({}),
                )
            except errors.PlanError as pe:
                self.counters["invalid"] += 1
                bad.append(self._fail(req, pe, warm=True))
                continue
            kept.append(req)
        return kept, bad

    def _backoff(self, attempt: int) -> None:
        """Exponential backoff with deterministic jitter, capped."""
        base = min(self.backoff_s * (2 ** (attempt - 1)), self.backoff_cap_s)
        time.sleep(base + self._rng.uniform(0.0, base))

    def _execute_batch(self, batch: List[QueryRequest]):
        """One attempt at the fast batched path.  Returns
        ``(shapes, results)``; raises typed errors on failure."""
        qnames = [r.qname for r in batch]
        if len(set(qnames)) == 1:
            shape = self._shape(batch[0].qname)
            bindings = [shape.query.bind_defaults(r.params) for r in batch]
            if len(batch) == 1:
                results = [shape.executable(self.db, bindings[0])]
            else:
                results = shape.executable.call_batched(self.db, bindings)
            return [shape] * len(batch), results
        # cross-query batch: ONE shared pass over the common scan
        # prefix (plan.merge_shared_scans), demuxed by request order
        from repro.core import plan as P

        shapes = [self._shape(q) for q in qnames]
        sp = P.merge_shared_scans([s.plan for s in shapes], sigma=self.sigma)
        ex = E.cached_shared_executable(sp, self.db, sigma=self.sigma)
        bindings = [
            s.query.bind_defaults(r.params) for s, r in zip(shapes, batch)
        ]
        results = ex(self.db, bindings)
        self.counters["shared_batches"] += 1
        return shapes, results

    def _execute_one(self, req: QueryRequest):
        """Per-request fallback: the session's degradation ladder
        (``Session.execute_shape``) with this server's retry/backoff around
        transient faults.  Returns ``(shape, out, retries)``; raises the
        final typed error when the request cannot be served."""
        shape = self._shape(req.qname)
        binding = shape.query.bind_defaults(req.params)
        attempt = 0
        while True:
            try:
                out = self.session.execute_shape(
                    shape.session_shape, binding
                )
                return shape, out, attempt
            except errors.ReproError as e:
                self.counters["faults"] += 1
                if errors.is_transient(e) and attempt < self.max_retries:
                    attempt += 1
                    self.counters["retries"] += 1
                    self._backoff(attempt)
                    continue
                raise

    def step(self) -> List[QueryResponse]:
        """Serve one micro-batch; returns this step's responses, including
        typed-error responses for expired/invalid/failed requests ([] only
        when there is no work at all)."""
        now = self._clock()
        out = self._sweep_expired(now)
        batch = self._take_batch()
        # warm/cold is decided by what was compiled when the round began —
        # validation below may resolve cold shapes as a side effect
        warm = all(r.qname in self._shapes for r in batch) if batch else True
        t0 = self._clock()  # cold batches count compile in busy time
        batch, bad = self._validate(batch)
        out.extend(bad)
        batch, shed = self._shed_predicted_misses(batch, self._clock())
        out.extend(shed)
        if not batch:
            # the step still terminated requests (or was genuinely idle)
            return out
        head = batch[0].qname
        shapes = results = None
        batch_retries = 0
        while results is None:
            try:
                with jax.profiler.TraceAnnotation(E.SPAN_DISPATCH):
                    shapes, results = self._execute_batch(batch)
            except Exception as e:  # noqa: BLE001 — typed triage below
                typed = errors.classified(e)
                if not isinstance(typed, errors.ReproError):
                    raise  # genuine bug: keep original type and traceback
                self.counters["faults"] += 1
                if (
                    errors.is_transient(typed)
                    and batch_retries < self.max_retries
                ):
                    batch_retries += 1
                    self.counters["retries"] += 1
                    self._backoff(batch_retries)
                    continue
                # degradable (OOM) or retries exhausted: isolate requests
                # and walk each down the session's degradation ladder
                out.extend(self._step_degraded(batch, warm, t0))
                self.counters["batches"] += 1
                return out
        rep = E.last_report()
        rep.retries += batch_retries
        self.session._last_report = rep
        # wait for the device before converting, so a trace tells the two
        # apart; the clocks below read once the answers are on the host
        with jax.profiler.TraceAnnotation(E.SPAN_SYNC):
            for res in results:
                block_result(res)
        with jax.profiler.TraceAnnotation(E.SPAN_CONVERT):
            items = [self._convert(res) for res in results]
        done = self._clock()
        self._busy["warm" if warm else "cold"] += done - t0
        uniq = list({id(s): s for s in shapes}.values())
        for s in uniq:
            s.busy_s += (done - t0) / len(uniq)
        if warm:
            self._note_wall(self._shapes[head], done - t0)
        for req, s, res in zip(batch, shapes, items):
            self.counters["queue_wait_s"] += t0 - req.t_submit
            resp = QueryResponse(
                rid=req.rid,
                qname=req.qname,
                params=req.params,
                result=res,
                latency_s=done - req.t_submit,
                warm=warm,
                batch_size=len(batch),
                retries=batch_retries,
            )
            self._lat["warm" if warm else "cold"].append(resp.latency_s)
            self.finished.append(resp)
            out.append(resp)
            s.served += 1
        self.counters["responses"] += len(batch)
        self.counters["batches"] += 1
        return out

    def _step_degraded(
        self, batch: List[QueryRequest], warm: bool, t0: float
    ) -> List[QueryResponse]:
        """The batch path failed hard: serve each request individually
        through the degradation ladder so one poisoned request (or a
        mode-wide OOM) cannot strand the others."""
        out = []
        start = t0
        for req in batch:
            try:
                with jax.profiler.TraceAnnotation(E.SPAN_DISPATCH):
                    shape, res, retries = self._execute_one(req)
            except errors.ReproError as e:
                out.append(self._fail(req, e, warm=warm))
                continue
            rep = E.last_report()
            rep.retries += retries
            if rep.degraded:
                self.counters["degraded"] += 1
            with jax.profiler.TraceAnnotation(E.SPAN_SYNC):
                block_result(res)
            with jax.profiler.TraceAnnotation(E.SPAN_CONVERT):
                items = self._convert(res)
            done = self._clock()
            self.counters["queue_wait_s"] += start - req.t_submit
            resp = QueryResponse(
                rid=req.rid,
                qname=req.qname,
                params=req.params,
                result=items,
                latency_s=done - req.t_submit,
                warm=warm,
                batch_size=1,
                retries=retries,
                degraded=rep.degradation,
            )
            self._lat["warm" if warm else "cold"].append(resp.latency_s)
            self.finished.append(resp)
            out.append(resp)
            shape.served += 1
            self.counters["responses"] += 1
            self._busy["warm" if warm else "cold"] += done - t0
            t0 = done
        return out

    def _convert(self, res):
        """One result's host answer (``result_items``), counting the slots
        a dictionary-valued result fetched and the entries it kept."""
        items = result_items(res)
        keys = getattr(res, "keys", None)
        if isinstance(keys, jax.Array):
            self.counters["result_slots"] += keys.shape[0]
            self.counters["result_entries"] += len(items)
        return items

    def _note_wall(self, shape: _Shape, wall_s: float) -> None:
        shape.ewma_s = (
            wall_s if shape.ewma_s is None
            else 0.3 * wall_s + 0.7 * shape.ewma_s
        )

    def run_until_done(self, max_steps: int = 100_000) -> List[QueryResponse]:
        for _ in range(max_steps):
            if not self.step():
                break
        return self.finished

    # -- observability -------------------------------------------------------
    def stats(self) -> Dict[str, object]:
        def pct(xs: List[float], p: float) -> float:
            return float(np.percentile(xs, p)) if xs else 0.0

        warm_n, cold_n = len(self._lat["warm"]), len(self._lat["cold"])
        return {
            **self.counters,
            "queued": len(self.queue) + len(self._round),
            "warm_p50_ms": pct(self._lat["warm"], 50) * 1e3,
            "warm_p99_ms": pct(self._lat["warm"], 99) * 1e3,
            "cold_p50_ms": pct(self._lat["cold"], 50) * 1e3,
            "cold_p99_ms": pct(self._lat["cold"], 99) * 1e3,
            "busy_s": self._busy["warm"] + self._busy["cold"],
            "warm_rps": warm_n / self._busy["warm"] if self._busy["warm"] else 0.0,
            "cold_rps": cold_n / self._busy["cold"] if self._busy["cold"] else 0.0,
            "shapes": {
                q: {
                    "served": s.served,
                    "compile_s": s.compile_s,
                    "busy_s": s.busy_s,
                    "ewma_ms": (s.ewma_s or 0.0) * 1e3,
                }
                for q, s in self._shapes.items()
            },
        }
