"""The unified Session façade — one planning funnel for the whole system.

``repro.connect(db, memory_budget=..., shards=..., adapt=...)`` returns a
:class:`Session` that fronts the full paper pipeline: every
``session.query(llql_or_name, **params)`` internally runs

    synthesize (Alg. 1) → legalize → fuse (Δ_fuse, chunk-aware) →
    storage plan → cached executable → execute

with the cold half paid once per query *shape* and every later call a warm
cache hit.  The session owns the pieces the old API made callers wire by
hand — ``chunk_db`` + the matching ``FusionCostModel(chunk_rows=...)`` for
out-of-core databases, the mesh + ``Δ_net`` for sharded execution,
``plan.fuse(streamed=...)``, the executable caches — and replaces the
``REGION_MODES``/``STREAM_STATS`` globals with ``session.report()``, the
structured :class:`repro.exec.engine.ExecutionReport` of the last call.

With ``adapt=`` truthy the session plans through
:class:`repro.core.adapt.AdaptivePlanner`: near-cost Alg.-1 candidates are
raced on warm-up traffic, validated bitwise, and the measured winner per
``(plan fingerprint, binding bucket)`` serves steady-state requests with
zero replanning; measured-vs-predicted residuals recalibrate the cost
model online (DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Optional, Tuple, Union

import numpy as np

from repro import errors
from repro.core import llql as L
from repro.core import plan as P
from repro.core.adapt import (
    AdaptConfig, AdaptivePlanner, bitwise_equal, result_items,
)
from repro.core.cost import AnalyticCostModel, FusionCostModel, NetCostModel
from repro.core.lower import compile as compile_plan
from repro.core.synthesis import synthesize
from repro.data import storage as S
from repro.data.table import collect_stats
from repro.exec import engine as E
from repro.exec.queries import FACT_RELS, REGISTRY, Query

#: queries whose fused path differs from the bare reference in the last f32
#: ulp (XLA FMA contraction inside the fused region — DESIGN.md §7), so
#: degraded-result validation uses allclose instead of bitwise for them
ALLCLOSE_QUERIES = ("q9",)

#: tolerance for the ladder rung that crosses the executor boundary
#: (sharded → single-shard replan): cross-shard psum folds floats in a
#: different order than one single-shard pass, so the equivalence check is
#: allclose at the same tolerance the distributed TPC-H suite uses —
#: rungs within one executor family stay bitwise
CROSS_EXECUTOR_RTOL = 3e-3
CROSS_EXECUTOR_ATOL = 3e-2


@dataclass
class Shape:
    """One compiled query shape owned by a session."""

    query: Query
    choices: Dict[str, object]
    plan: object  # fused physical plan (shared-scan merge input)
    executable: object  # E.Executable / StreamedExecutable, or sharded run
    planner: Optional[AdaptivePlanner] = None
    compile_s: float = 0.0
    served: int = 0
    synth_runs: int = 0
    # degradation-ladder state (DESIGN.md §12): lazily-built executables for
    # the lower rungs, keyed by mode name
    mode_ex: Dict[str, tuple] = field(default_factory=dict)


class Session:
    """See module docstring.  Construct via :func:`connect`."""

    def __init__(
        self,
        db,
        memory_budget: Optional[int] = None,
        chunk_rows: int = S.CHUNK_ROWS,
        shards: int = 0,
        adapt: Union[bool, AdaptConfig] = False,
        delta=None,
        queries: Optional[Dict[str, Query]] = None,
        allow_sorted: bool = True,
        clock=None,
    ):
        if memory_budget is not None and shards > 1:
            raise ValueError(
                "out-of-core streaming and sharded execution are separate "
                "executors; open one session per regime"
            )
        self.base_db = db
        self.sigma = collect_stats(db)
        self.delta = delta if delta is not None else AnalyticCostModel()
        self.queries = dict(queries if queries is not None else REGISTRY)
        self.allow_sorted = allow_sorted
        self.adapt_config: Optional[AdaptConfig] = None
        if adapt:
            self.adapt_config = (
                adapt if isinstance(adapt, AdaptConfig) else AdaptConfig()
            )

        # storage plan: chunk what the budget can't keep resident, and tell
        # the fusion model the REAL chunk geometry so Δ_chained prices the
        # spill-vs-chain decision with the n_chunks the engine will run
        self.memory_budget = memory_budget
        self.chunk_rows = chunk_rows
        if memory_budget is not None:
            self.db = S.chunk_db(
                db, memory_budget_bytes=memory_budget, chunk_rows=chunk_rows
            )
            self.fusion = dataclasses.replace(
                FusionCostModel(), chunk_rows=float(chunk_rows)
            )
        else:
            self.db = db
            self.fusion = None
        self.streamed: Tuple[str, ...] = tuple(
            sorted(r for r, t in self.db.items() if S.is_chunked(t))
        )

        # sharded execution: one mesh per session, fact tables row-sharded
        self.shards = int(shards or 0)
        self.mesh = None
        self.axis = "data"
        self.shard_rels: Tuple[str, ...] = ()
        self.net = None
        if self.shards > 1:
            import jax

            from repro import compat

            if jax.device_count() < self.shards:
                raise ValueError(
                    f"need {self.shards} devices, have {jax.device_count()} "
                    "(a CPU rehearsal gets N virtual devices from "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)"
                )
            self.mesh = compat.make_mesh((self.shards,), (self.axis,))
            self.shard_rels = FACT_RELS
            self.net = NetCostModel(n_shards=self.shards)

        self._shapes: Dict[str, Shape] = {}
        self._last_report: Optional[E.ExecutionReport] = None

        # -- fault tolerance (DESIGN.md §12) --------------------------------
        #: monotonic clock driving circuit-breaker cooldowns and adaptive
        #: races' lane timings — injectable (``clock=``) so tests advance
        #: time instead of sleeping, and control what a race measures
        self._clock = clock if clock is not None else time.perf_counter
        #: consecutive transient failures before a mode counts as broken
        self.breaker_threshold = 2
        #: seconds a tripped (shape, mode) breaker stays open
        self.breaker_cooldown_s = 30.0
        self._breaker: Dict[Tuple[str, str], float] = {}  # -> open-until
        self._breaker_fails: Dict[Tuple[str, str], int] = {}
        #: recent primary-mode results per (shape, binding) — the reference
        #: degraded re-executions are equivalence-checked against.  Raw
        #: executor outputs (no forced d2h sync on the hot path); normalized
        #: only when a degraded result actually needs comparing.
        self._ref_results: Dict[tuple, object] = {}
        self._ref_results_max = 32
        #: lazily-built shrunken-budget chunked twin of the database — the
        #: ladder's streamed rung (storage_plan at half the budget)
        self._degraded_storage_cache = None
        #: cumulative ladder telemetry across the session's lifetime
        self.fault_stats = {"faults": 0, "retries": 0, "degraded": 0}

    # -- planning funnel -----------------------------------------------------
    def _resolve(self, q: Union[str, Query, L.Expr]) -> Tuple[str, Query]:
        if isinstance(q, str):
            query = self.queries.get(q)
            if query is None:
                raise KeyError(
                    f"unknown query {q!r}; registered: {sorted(self.queries)}"
                )
            return q, query
        if isinstance(q, Query):
            return q.name, q
        if isinstance(q, L.Expr):
            # ad-hoc LLQL program: key the shape cache by plan fingerprint
            expr = q
            fp = compile_plan(expr, {}).fingerprint()
            name = f"llql:{fp[:12]}"
            return name, Query(name, lambda: expr, None, None)
        raise TypeError(f"cannot plan a {type(q).__name__}")

    def _build(self, expr: L.Expr, choices):
        """choices → (fused plan, executor) through the cached back ends."""
        if self.mesh is not None:
            from repro.exec import distributed as D

            plan = compile_plan(expr, choices)
            run = D.cached_sharded_executor(
                plan, self.db, self.mesh, self.axis,
                shard_rels=self.shard_rels, sigma=self.sigma,
            )
            # Executable-interface adapter: ``ex(db, params)`` — the one
            # calling convention Session/QueryServer drive every rung with
            return plan, D.ShardedExecutable(run, self.db)
        plan = P.fuse(
            compile_plan(expr, choices),
            sigma=self.sigma,
            streamed=self.streamed,
            fusion=self.fusion,
        )
        ex = E.cached_executable(plan, self.db, sigma=self.sigma)
        return plan, ex

    def _call(self, executable, params):
        return executable(self.db, params)

    # -- degradation ladder (DESIGN.md §12, §13) -----------------------------
    #
    # Every rung realizes the SAME LLQL semantics under the same Γ — the
    # paper's equivalence result is what makes descending *legal*:
    #
    #   in-memory:  fused  →  materialized  →  streamed out-of-core
    #   sharded:    fused-sharded  →  materialized-sharded  →  single-shard
    #
    # A DeviceOOMError descends immediately (same mode will OOM again); a
    # transient fault (injected, compile, shard/collective) re-raises for
    # the caller to retry at the same rung, and descends only after
    # `breaker_threshold` consecutive failures ("repeated kernel failure").
    # A descent trips the per-(shape, mode) circuit breaker: until the
    # cooldown expires, new requests skip the broken rung without paying
    # the failure again.  The sharded ladder's last rung re-legalizes the
    # plan with n_shards=1 — the whole mesh being sick must not take the
    # query down while one device can still answer it.

    def _ladder_modes(self) -> Tuple[str, ...]:
        if self.mesh is not None:
            return ("fused-sharded", "materialized-sharded", "single-shard")
        if self.memory_budget is not None:
            # already streaming: the only lower rung is a smaller footprint
            return ("streamed", "streamed-shrunk")
        return ("fused", "materialized", "streamed")

    def _degraded_storage(self):
        """The streamed rung's database: ``chunk_db`` under half the
        session's budget (or half the decoded footprint when fully
        resident), so the rung provably fits where the resident modes
        did not."""
        if self._degraded_storage_cache is None:
            if self.memory_budget is not None:
                budget = max(1, self.memory_budget // 2)
            else:
                budget = max(1, sum(
                    a.nbytes
                    for t in self.base_db.values()
                    for a in t.columns.values()
                ) // 2)
            db = S.chunk_db(
                self.base_db, memory_budget_bytes=budget,
                chunk_rows=self.chunk_rows,
            )
            fusion = dataclasses.replace(
                FusionCostModel(), chunk_rows=float(self.chunk_rows)
            )
            streamed = tuple(
                sorted(r for r, t in db.items() if S.is_chunked(t))
            )
            self._degraded_storage_cache = (db, fusion, streamed)
        return self._degraded_storage_cache

    def _mode_executable(self, shape: Shape, mode: str):
        """(executable, db) realizing ``shape`` at ladder rung ``mode``.
        The primary rung is the shape's installed executable (kept live so
        adaptive reinstalls stay visible); lower rungs build lazily through
        the same executable caches."""
        modes = self._ladder_modes()
        if mode == modes[0]:
            return shape.executable, self.db
        cached = shape.mode_ex.get(mode)
        if cached is not None:
            return cached
        expr = shape.query.llql()
        if mode == "materialized":
            # the same plan, unfused: node-by-node XLA execution — no
            # Pipeline regions, no Pallas kernels, smaller live sets
            plan = compile_plan(expr, shape.choices)
            ex = E.cached_executable(plan, self.db, sigma=self.sigma)
            db = self.db
        elif mode == "materialized-sharded":
            # the same legalized plan, per-shard phase unfused — shard-local
            # fused regions out of play, collectives and placement unchanged
            from repro.exec import distributed as D

            plan = compile_plan(expr, shape.choices)
            run = D.cached_sharded_executor(
                plan, self.db, self.mesh, self.axis,
                shard_rels=self.shard_rels, sigma=self.sigma, fuse=False,
            )
            ex, db = D.ShardedExecutable(run, self.db), self.db
        elif mode == "single-shard":
            # re-legalize with n_shards=1: the full database lives on one
            # device, no collectives at all — same Γ choices, and the
            # executable cache makes the replan a lookup after the first
            # descent (the mesh being sick must not strand the query)
            plan = P.fuse(
                compile_plan(expr, shape.choices), sigma=self.sigma
            )
            ex = E.cached_executable(plan, self.base_db, sigma=self.sigma)
            db = self.base_db
        elif mode in ("streamed", "streamed-shrunk"):
            db, fusion, streamed = self._degraded_storage()
            plan = P.fuse(
                compile_plan(expr, shape.choices),
                sigma=self.sigma, streamed=streamed, fusion=fusion,
            )
            ex = E.cached_executable(plan, db, sigma=self.sigma)
        else:
            raise ValueError(f"unknown ladder mode {mode!r}")
        shape.mode_ex[mode] = (ex, db)
        return ex, db

    def _trip_breaker(self, name: str, mode: str) -> None:
        self._breaker[(name, mode)] = (
            self._clock() + self.breaker_cooldown_s
        )
        self._breaker_fails.pop((name, mode), None)

    def breakers(self) -> Dict[Tuple[str, str], float]:
        """Open circuit breakers: ``{(shape, mode): seconds-left}``."""
        now = self._clock()
        return {
            k: until - now
            for k, until in self._breaker.items()
            if until > now
        }

    def _binding_key(self, name: str, bound) -> tuple:
        return (name,) + tuple(
            sorted((k, repr(v)) for k, v in (bound or {}).items())
        )

    def _validate_degraded(
        self, shape: Shape, key: tuple, out, mode: str = ""
    ) -> None:
        """Equivalence-check a degraded result against the cached primary
        result for the same binding, when one is available — reusing the
        fused==materialized bitwise contract (allclose for the documented
        ulp-level exceptions).  The ``single-shard`` replan rung crosses
        the executor family (its psum fold order differs from the sharded
        primary), so it is held to the cross-executor allclose tolerance
        instead of bitwise."""
        ref = self._ref_results.get(key)
        if ref is None:
            return
        a, b = result_items(out), result_items(ref)
        if bitwise_equal(a, b):
            return
        if mode == "single-shard" and set(a) == set(b):
            if all(
                np.allclose(
                    a[k], b[k],
                    rtol=CROSS_EXECUTOR_RTOL, atol=CROSS_EXECUTOR_ATOL,
                )
                for k in a
            ):
                return
        if shape.query.name in ALLCLOSE_QUERIES and set(a) == set(b):
            if all(
                np.allclose(a[k], b[k], rtol=1e-5, atol=1e-6) for k in a
            ):
                return
        raise errors.ReproError(
            f"degraded execution of {shape.query.name!r} diverged from its "
            f"primary-mode reference — equivalence violation, not noise"
        )

    def execute_shape(self, shape: Shape, bound=None):
        """Execute one bound request for ``shape`` with the degradation
        ladder: start at the lowest rung whose breaker is closed, descend on
        ``DeviceOOMError`` or repeated transient failure, re-raise typed
        transients for the caller (``QueryServer``) to retry with backoff.
        Returns the raw executor output; ``E.last_report()`` is stamped with
        the fault/degradation ledger."""
        name = shape.query.name
        modes = self._ladder_modes()
        now = self._clock()
        idx = 0
        while (
            idx < len(modes) - 1
            and self._breaker.get((name, modes[idx]), 0.0) > now
        ):
            idx += 1
        faults = 0
        while True:
            mode = modes[idx]
            try:
                ex, db = self._mode_executable(shape, mode)
                out = ex(db, bound)
            except Exception as e:  # noqa: BLE001 — typed triage below
                typed = errors.classified(e)
                if not isinstance(typed, errors.ReproError):
                    raise  # genuine bug: keep original type and traceback
                if isinstance(typed, errors.PlanError):
                    raise typed from (e if typed is not e else None)
                faults += 1
                self.fault_stats["faults"] += 1
                degrade = isinstance(typed, errors.DeviceOOMError)
                if not degrade and errors.is_transient(typed):
                    k = (name, mode)
                    fails = self._breaker_fails.get(k, 0) + 1
                    self._breaker_fails[k] = fails
                    degrade = fails >= self.breaker_threshold
                if degrade and idx < len(modes) - 1:
                    self._trip_breaker(name, mode)
                    idx += 1
                    continue
                if typed is e:
                    raise
                raise typed from e
            # success at rung `idx`
            self._breaker_fails.pop((name, mode), None)
            key = self._binding_key(name, bound)
            if idx == 0:
                if len(self._ref_results) >= self._ref_results_max:
                    self._ref_results.pop(next(iter(self._ref_results)))
                self._ref_results[key] = out
            else:
                self.fault_stats["degraded"] += 1
                self._validate_degraded(shape, key, out, mode=mode)
            rep = E.last_report()
            rep.faults += faults
            rep.degraded = idx
            rep.degradation = mode if idx else ""
            return out

    def shape(self, q: Union[str, Query, L.Expr]) -> Shape:
        """The compiled shape for a query — cold pipeline once, cached after.
        Adaptive sessions additionally run the warm-up race here (on the
        query's default binding), so the installed executable is already
        the measured winner when the first request lands."""
        name, query = self._resolve(q)
        shape = self._shapes.get(name)
        if shape is not None:
            return shape
        expr = query.llql()
        t0 = time.perf_counter()
        planner = None
        synth_runs = 1
        if self.adapt_config is not None:
            fp = compile_plan(expr, {}).fingerprint()
            planner = AdaptivePlanner(
                expr, self.sigma, self.delta,
                make_executor=lambda ch: _ParamRunner(self, expr, ch),
                config=self.adapt_config,
                fingerprint=fp,
                net=self.net,
                sharded_rels=self.shard_rels or None,
                clock=self._clock,
            )
            choices = planner.choose(query.bind_defaults({}))
            synth_runs = len(planner.races)  # one enumerate per race round
        else:
            choices = dict(
                synthesize(
                    expr, self.sigma, self.delta,
                    net=self.net, sharded_rels=self.shard_rels or None,
                ).choices
            )
        plan, ex = self._build(expr, choices)
        shape = Shape(
            query, dict(choices), plan, ex,
            planner=planner,
            compile_s=time.perf_counter() - t0,
            synth_runs=synth_runs,
        )
        self._shapes[name] = shape
        return shape

    # -- the public entry point ----------------------------------------------
    def query(
        self, q: Union[str, Query, L.Expr], **params
    ) -> Dict[int, np.ndarray]:
        """Execute ``q`` under this session's planning funnel and return its
        ``{key: np.ndarray}`` result.  ``q`` is a registered query name
        (``queries.REGISTRY``), a ``Query`` object, or a raw LLQL program.

        Bindings are validated at this boundary (typed ``PlanError`` for
        unknown names, kind-incompatible values, and NaN floats — never a
        shape error deep inside jit), and execution runs under the
        degradation ladder: device OOM or repeated kernel failure re-executes
        down fused → materialized → streamed (see ``execute_shape``)."""
        shape = self.shape(q)
        E.validate_binding(
            shape.plan, params, defaults=shape.query.bind_defaults({})
        )
        bound = shape.query.bind_defaults(params)
        if shape.planner is not None:
            choices = shape.planner.choose(bound)
            if choices != shape.choices:
                # a race moved the winner: reinstall (cached — no re-jit)
                shape.choices = dict(choices)
                shape.plan, shape.executable = self._build(
                    shape.query.llql(), choices
                )
            shape.synth_runs = len(shape.planner.races)
        out = self.execute_shape(shape, bound)
        shape.served += 1
        self._last_report = E.last_report()
        return result_items(out)

    # -- observability -------------------------------------------------------
    def report(self) -> Optional[E.ExecutionReport]:
        """The structured ExecutionReport of this session's last query."""
        return self._last_report

    def explain(self, q: Union[str, Query, L.Expr]) -> Dict[str, object]:
        """Planning summary for a shape: chosen Γ, fused plan modes, and —
        for adaptive sessions — the race history."""
        shape = self.shape(q)
        out: Dict[str, object] = {
            "choices": {s: str(c) for s, c in sorted(shape.choices.items())},
            "compile_s": shape.compile_s,
            "served": shape.served,
            "streamed": self.streamed,
            "shards": self.shards,
        }
        if shape.planner is not None:
            out["races"] = [
                {
                    "bucket": rec.bucket,
                    "lanes": [
                        {
                            "swapped": ln.candidate.swapped or "<winner>",
                            "modeled_ms": ln.candidate.modeled_s * 1e3,
                            "measured_ms": (
                                ln.measured_s * 1e3
                                if ln.measured_s < float("inf")
                                else None
                            ),
                            "validated": ln.validated,
                        }
                        for ln in rec.lanes
                    ],
                }
                for rec in shape.planner.races
            ]
        return out


class _ParamRunner:
    """Adapter: AdaptivePlanner's ``run(params)`` contract over a session's
    executor for one fixed Γ (built lazily, reusing the executable caches)."""

    def __init__(self, session: Session, expr: L.Expr, choices):
        self.session = session
        self.expr = expr
        self.choices = choices
        self._ex = None

    def __call__(self, params=None):
        if self._ex is None:
            _, self._ex = self.session._build(self.expr, self.choices)
        return self.session._call(self._ex, params)


#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed directory inside the checkout (gitignored), so every process of this
#: checkout finds the executables an earlier one compiled
COMPILE_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    A directory already configured — ``JAX_COMPILATION_CACHE_DIR``, which
    JAX reads itself, or an earlier ``jax.config`` setting — is kept;
    otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.  Takes effect
    for compiles after the first call in a process."""
    import jax

    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def connect(
    db,
    memory_budget: Optional[int] = None,
    chunk_rows: int = S.CHUNK_ROWS,
    shards: int = 0,
    adapt: Union[bool, AdaptConfig] = False,
    delta=None,
    queries: Optional[Dict[str, Query]] = None,
    allow_sorted: bool = True,
    clock=None,
) -> Session:
    """Open a :class:`Session` over ``db`` (a ``{relation: Table}`` dict).

    * ``memory_budget`` (bytes) — relations the budget can't keep resident
      are compressed + chunked and streamed per region (DESIGN.md §10);
    * ``shards`` — execute over an N-way mesh with the fact tables
      row-sharded (choices synthesized under Δ_net);
    * ``adapt`` — ``True`` or an :class:`AdaptConfig`: race near-cost plans
      on warm-up traffic, validate bitwise, serve the measured winner.

    Turns on the persistent compilation cache (:func:`use_compile_cache`).
    """
    use_compile_cache()
    return Session(
        db,
        memory_budget=memory_budget,
        chunk_rows=chunk_rows,
        shards=shards,
        adapt=adapt,
        delta=delta,
        queries=queries,
        allow_sorted=allow_sorted,
        clock=clock,
    )
