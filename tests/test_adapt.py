"""Adaptive planning loop (DESIGN.md §11): race → validate → recalibrate,
behind the Session façade.

Covers the ISSUE-8 property tests:

* every raced candidate validates **bitwise** against the model-chosen
  plan on all five TPC-H queries (the sharded counterpart lives in
  ``tests/test_distributed_tpch.py`` — subprocess, 8 virtual devices);
* a poisoned cost model (hash ops priced ~absurdly cheap) converges to
  the measured-fast plan within the warm-up rounds, and the residual
  corrections re-rank the model itself;
* warm-cache serving does no per-request replanning: race count and
  executable trace counts stay flat after warm-up;
* the chunk-aware ``FusionCostModel.delta_chained`` makes small-scale
  out-of-core plans SPILL chained streamed regions instead of
  force-chaining them, and the spilled execution stays exact.
"""
import numpy as np
import pytest

from repro.core.adapt import (
    AdaptConfig,
    AdaptivePlanner,
    binding_bucket,
    bitwise_equal,
    choices_key,
    enumerate_candidates,
)
from repro.core.cost import AnalyticCostModel, FusionCostModel
from repro.data import tpch
from repro.data.table import collect_stats
from repro.exec.queries import REGISTRY
from repro.session import connect

SCALE = 0.002


@pytest.fixture(scope="module")
def db():
    return tpch.generate(scale=SCALE, seed=0).tables()


# ---------------------------------------------------------------------------
# unit: binding buckets, candidate keys, candidate enumeration
# ---------------------------------------------------------------------------


def test_binding_bucket_groups_regimes_not_values():
    # same magnitude decade -> same bucket; regime change -> different one
    assert binding_bucket({"threshold": 199.0}) == binding_bucket(
        {"threshold": 201.0}
    )
    assert binding_bucket({"threshold": 200.0}) != binding_bucket(
        {"threshold": 2.0}
    )
    # ints bucket by value (region/color knobs change selectivity per value)
    assert binding_bucket({"region": 1}) != binding_bucket({"region": 2})
    # order-insensitive, None/empty stable
    assert binding_bucket({"a": 1, "b": 2.0}) == binding_bucket(
        {"b": 2.0, "a": 1}
    )
    assert binding_bucket(None) == binding_bucket({}) == ()


def test_choices_key_canonical(db):
    sigma = collect_stats(db)
    delta = AnalyticCostModel()
    q = REGISTRY["q3"]
    cands = enumerate_candidates(q.llql(), sigma, delta, band=50.0, top_k=4)
    assert cands, "winner always enumerated"
    # winner first, keys unique, all within the band of the winner
    keys = [c.key for c in cands]
    assert len(keys) == len(set(keys))
    assert cands[0].swapped == ""
    limit = cands[0].modeled_s * 51.0
    assert all(c.modeled_s <= limit for c in cands)
    assert all(c.swapped for c in cands[1:])  # single-symbol neighbourhood
    assert choices_key(cands[0].choices) == choices_key(dict(cands[0].choices))


def test_enumerate_tight_band_races_nothing(db):
    """When the model is sure (tight band), the roster is the winner alone."""
    sigma = collect_stats(db)
    q = REGISTRY["q1"]
    cands = enumerate_candidates(
        q.llql(), sigma, AnalyticCostModel(), band=0.0, top_k=5
    )
    assert [c.swapped for c in cands] == [""]


def test_bitwise_equal_is_exact():
    a = {1: np.asarray([1.0, 2.0], np.float32)}
    assert bitwise_equal(a, {1: np.asarray([1.0, 2.0], np.float32)})
    one_ulp = np.nextafter(np.float32(2.0), np.float32(3.0))
    assert not bitwise_equal(a, {1: np.asarray([1.0, one_ulp], np.float32)})
    assert not bitwise_equal(a, {1: np.asarray([1.0, 2.0], np.float64)})
    assert not bitwise_equal(a, {2: np.asarray([1.0, 2.0], np.float32)})


# ---------------------------------------------------------------------------
# S4a: every raced candidate validates bitwise, all five queries (1 shard)
# ---------------------------------------------------------------------------


def test_raced_candidates_validate_bitwise_all_queries(db):
    """The core equivalence property: any near-cost candidate the planner
    is willing to race produces the SAME bytes as the model-chosen plan.
    Wide band + top_k=3 so every query actually races >= 2 lanes."""
    session = connect(
        db, adapt=AdaptConfig(band=50.0, top_k=3, warmup=1, repeats=1)
    )
    for qname in sorted(REGISTRY):
        session.query(qname)
        planner = session.shape(qname).planner
        assert planner.races, qname
        for rec in planner.races:
            assert len(rec.lanes) >= 2, (qname, [l.candidate.swapped for l in rec.lanes])
            for lane in rec.lanes:
                assert lane.validated, (qname, lane.candidate.swapped)
            # the installed winner is a validated lane with finite wall time
            assert rec.winner is not None and rec.winner.measured_s < float("inf")


def test_session_query_params_and_report(db):
    """S2: registry-driven `session.query(name, **params)`; report() is the
    structured ExecutionReport of the last call."""
    session = connect(db)
    out = session.query("q18", threshold=200.0)
    ref = REGISTRY["q18"].run(db, {}, threshold=200.0)
    assert bitwise_equal(out, ref)
    rep = session.report()
    assert rep is not None and rep.wall_s > 0.0
    assert rep.modes(), "per-region modes populated"
    # ad-hoc LLQL programs plan through the same funnel (no registry
    # defaults, so the free ?date Param is bound explicitly)
    out2 = session.query(REGISTRY["q1"].llql(), date=0.9)
    assert set(out2) == set(REGISTRY["q1"].run(db, {}, date=0.9))


# ---------------------------------------------------------------------------
# S4b: a poisoned cost model converges to the measured-fast plan
# ---------------------------------------------------------------------------


def test_poisoned_model_converges_to_fast_plan(db, monkeypatch):
    """Price hash ops ~100x under the calibrated truth (the real direction
    of the prior's misprice, exaggerated): Alg. 1 then picks ht_*
    everywhere.  The race measures the st_* swaps faster, installs one as
    the winner immediately, and the residual corrections inflate the
    poisoned coefficients until the MODEL itself re-ranks within the
    warm-up rounds.

    The race's measurements come from the session's injected clock: each
    lane run advances it by 1 ms per sort dictionary and 10 ms per hash
    dictionary, so which lane is fast does not depend on the machine's
    load (wall-clock races of these sub-millisecond lanes are a coin
    toss on a busy CPU)."""
    from repro.core.cost import PRIOR_OP_NS
    from repro.core.synthesis import synthesize
    from repro import session as S

    now = [0.0]
    run_lane = S._ParamRunner.__call__

    def timed_lane(self, params=None):
        now[0] += sum(
            1e-2 if c.ds.startswith("ht") else 1e-3
            for c in self.choices.values()
        )
        return run_lane(self, params)

    monkeypatch.setattr(S._ParamRunner, "__call__", timed_lane)

    poisoned_table = dict(PRIOR_OP_NS)
    for key in poisoned_table:
        poisoned_table[key] = 1.0 if key[0].startswith("ht") else 100.0
    delta = AnalyticCostModel(constants=poisoned_table)
    sigma = collect_stats(db)
    q = REGISTRY["q3"]
    poisoned_choices = dict(synthesize(q.llql(), sigma, delta).choices)
    assert all(
        c.ds.startswith("ht") for c in poisoned_choices.values()
    ), "poison did not take"

    session = connect(
        db,
        adapt=AdaptConfig(
            band=1e6, top_k=6, warmup=4, repeats=2, residual_alpha=1.0
        ),
        delta=delta,
        clock=lambda: now[0],
    )
    N = 5
    for _ in range(N):
        session.query("q3")
    shape = session.shape("q3")

    # (1) the served plan left the poisoned choice for a measured-fast one
    assert shape.choices != poisoned_choices
    served = {s: c.ds for s, c in shape.choices.items()}
    assert any(ds.startswith("st") for ds in served.values()), served
    # (2) the corrections learned that hash ops are underpriced
    assert delta.corrections, "no residuals were applied"
    ht_corr = [v for k, v in delta.corrections.items() if k[0].startswith("ht")]
    assert ht_corr and max(ht_corr) > 10.0, delta.corrections
    # (3) the model itself re-ranked: fresh synthesis under the corrected
    # Δ no longer reproduces the poisoned plan
    assert dict(synthesize(q.llql(), sigma, delta).choices) != poisoned_choices
    # (4) and the winner was reached within the warm-up rounds
    assert len(shape.planner.races) <= N


# ---------------------------------------------------------------------------
# warm-cache serving: no per-request replanning
# ---------------------------------------------------------------------------


def test_warm_cache_no_replanning(db):
    session = connect(
        db, adapt=AdaptConfig(band=50.0, top_k=2, warmup=1, repeats=1)
    )
    session.query("q18")  # shape() warm-up race + first request
    planner = session.shape("q18").planner
    races_after_warmup = len(planner.races)
    ex = session.shape("q18").executable
    traces_after_warmup = ex.trace_count
    for _ in range(5):
        session.query("q18")
    assert len(planner.races) == races_after_warmup, "steady-state re-raced"
    assert session.shape("q18").executable is ex, "executable churned"
    assert ex.trace_count == traces_after_warmup, "steady-state retraced"
    # different binding bucket -> ONE new race, then cached again
    session.query("q18", threshold=2.0)
    session.query("q18", threshold=2.1)
    assert len(planner.races) == races_after_warmup + 1


# ---------------------------------------------------------------------------
# S3: chunk-aware Δ_chained — small-scale plans spill instead of chaining
# ---------------------------------------------------------------------------


def test_delta_chained_scales_with_chunk_count():
    """delta_chained is seconds SAVED by chaining: the per-chunk state
    rewrite (n_chunks × state_bytes) erodes it, so more chunks must make
    chaining strictly worse — and eventually negative (→ spill)."""
    fm = FusionCostModel(chunk_rows=float(1 << 13))
    few = fm.delta_chained(50_000, 4, 1 << 20, n_chunks=2)
    many = fm.delta_chained(50_000, 4, 1 << 20, n_chunks=64)
    assert few > 0.0 > many, (few, many)


def test_small_scale_streamed_spills_not_chains():
    """At small scale the per-chunk merge cost of a chained streamed region
    dominates (~10x measured): the session's chunk-aware fusion model must
    SPILL the downstream aggregation, and the spilled run must stay exact
    (q5 bitwise; q9 allclose — bare-vs-fused XLA FMA contraction already
    differs in the last float ulp on resident data, independent of
    streaming)."""
    db = tpch.generate(scale=0.02, seed=0).tables()
    session = connect(db, memory_budget=1 << 19, chunk_rows=1 << 13)
    assert session.streamed, "budget did not force streaming"

    out5 = session.query("q5")
    rep = session.report()
    modes = rep.modes()
    assert any(m.startswith("streamed") for m in modes.values()), modes
    assert not any(
        m.startswith("streamed-chained") for m in modes.values()
    ), f"chunk-aware delta_chained should spill at this scale: {modes}"
    assert bitwise_equal(out5, REGISTRY["q5"].run(db, {}))

    out9 = session.query("q9")
    ref9 = REGISTRY["q9"].run(db, {})
    assert set(out9) == set(ref9)
    for k in ref9:
        np.testing.assert_allclose(out9[k], ref9[k], rtol=1e-5, atol=1e-2)
