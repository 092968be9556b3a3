"""The names the served path leaves in a profiler trace (the telemetry
section of ``exec/engine.py``), and the server's clocks and reports that
read the same boundaries: device scopes in the compiled HLO's metadata,
host spans nested on the thread that calls ``QueryServer.step()``."""
import glob
import os

import jax
import numpy as np
import pytest

import repro
from repro.core import plan as P
from repro.core.cost import AnalyticCostModel
from repro.core.lower import compile as compile_plan
from repro.core.synthesis import synthesize
from repro.data import storage as S
from repro.data import tpch
from repro.data.table import Table, collect_stats
from repro.exec import engine as E
from repro.exec.queries import QUERIES
from repro.serve import query_server as QS


@pytest.fixture(scope="module")
def db():
    return tpch.generate(scale=0.002, seed=3).tables()


@pytest.fixture(scope="module")
def sigma(db):
    return collect_stats(db)


@pytest.fixture
def fresh_compile():
    """Compile without the persistent cache: its key leaves out metadata, so
    an entry written by a program without the scopes would come back."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _plan(qname, sigma):
    q = QUERIES[qname]
    choices = synthesize(q.llql(), sigma, AnalyticCostModel()).choices
    return P.fuse(compile_plan(q.llql(), choices), sigma=sigma)


def test_q5_hlo_names_its_dictionary_builds_and_probes(db, sigma, fresh_compile):
    ex = E.cached_executable(_plan("q5", sigma), db, sigma=sigma)
    cols, masks = E.Executable._db_arrays(db)
    params = ex.coerce_params(QUERIES["q5"].defaults)
    text = ex._fn.lower(cols, masks, params).compile().as_text()
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in text.splitlines() if 'op_name="' in ln]
    assert any(f"/{E.SCOPE_BUILD}." in n for n in names)
    assert any(f"/{E.SCOPE_PROBE}." in n for n in names)
    assert not any(E.SCOPE_DECODE in n for n in names)  # nothing streams


def test_streamed_q1_chain_names_its_decode(db, sigma, monkeypatch, fresh_compile):
    made = []
    make = E._make_streamed_chain_fn

    def spy(*args, **kw):
        fn, holders = make(*args, **kw)

        def call(*a):
            made.append((fn, a))
            return fn(*a)

        return call, holders

    monkeypatch.setattr(E, "_make_streamed_chain_fn", spy)
    E.clear_exec_cache()
    cdb = S.chunk_db(db, memory_budget_bytes=1 << 18, chunk_rows=1 << 12)
    assert S.is_chunked(cdb["lineitem"])
    plan = _plan("q1", sigma)
    E.execute_plan(plan, cdb, sigma=sigma,
                   params=E.coerce_bindings(plan, QUERIES["q1"].defaults))
    assert made
    fn, args = made[0]
    text = fn.lower(*args).compile().as_text()
    assert f"/{E.SCOPE_DECODE}/" in text
    assert f"/{E.SCOPE_BUILD}." in text  # the carried group-by state


def _trace_events(path):
    from jax.profiler import ProfileData

    (f,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(f)
    for plane in pd.planes:
        for line in plane.lines:
            yield line, [(e.start_ns, e.end_ns, e.name) for e in line.events]


def test_step_spans_nest_on_the_calling_thread(db, tmp_path):
    server = QS.QueryServer(repro.connect(dict(db)), max_batch=2)
    server.warm_up(["q1"])
    for d in (0.6, 0.7):
        server.submit("q1", date=d)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.step"):
            (a, b) = server.step()
    finally:
        jax.profiler.stop_trace()
    assert a.ok and b.ok
    spans = (E.SPAN_DISPATCH, E.SPAN_SYNC, E.SPAN_CONVERT)
    found = None
    for _line, events in _trace_events(str(tmp_path)):
        if any(n == "test.step" for _, _, n in events):
            found = events
    assert found is not None, "the caller's span is in the trace"
    (outer,) = [(s, e) for s, e, n in found if n == "test.step"]
    mine = {n: (s, e) for s, e, n in found if n in spans}
    assert set(mine) == set(spans)  # on the caller's own thread
    for s, e in mine.values():
        assert outer[0] <= s <= e <= outer[1]
    order = [mine[n] for n in spans]
    assert order[0][1] <= order[1][0] and order[1][1] <= order[2][0]


def test_latency_and_ewma_include_the_conversion(db, monkeypatch):
    """The server's clocks read after the answers are on the host: a step
    whose conversion takes 5 s (on the injected clock) reports it in the
    response's latency, the shape's EWMA and busy time."""
    t = [100.0]
    server = QS.QueryServer(
        repro.connect(dict(db)), clock=lambda: t[0], max_batch=2
    )
    server.warm_up(["q1"])
    convert = QS.result_items

    def slow(res):
        t[0] += 5.0
        return convert(res)

    monkeypatch.setattr(QS, "result_items", slow)
    server.submit("q1", date=0.7)
    t[0] += 1.0  # queued for a second before the step starts
    (resp,) = server.step()
    assert resp.ok
    assert resp.latency_s == pytest.approx(6.0)
    shape = server._shapes["q1"]
    assert shape.ewma_s == pytest.approx(5.0)
    assert server.stats()["busy_s"] >= 5.0
    assert server.counters["queue_wait_s"] == pytest.approx(1.0)


def test_queue_wait_sums_each_requests_wait(db):
    t = [0.0]
    server = QS.QueryServer(
        repro.connect(dict(db)), clock=lambda: t[0], max_batch=1
    )
    server.warm_up(["q1"])
    server.submit("q1", date=0.6)
    t[0] += 2.0
    server.submit("q1", date=0.7)
    t[0] += 1.0
    server.step()  # first request: waited 3 s
    t[0] += 4.0
    server.step()  # second: waited 1 + 4 s
    assert server.counters["queue_wait_s"] == pytest.approx(8.0)
    assert server.counters["responses"] == 2


def test_session_report_covers_served_requests(db):
    session = repro.connect(dict(db))
    assert session.report() is None
    server = QS.QueryServer(session, max_batch=1)
    server.submit("q1", date=0.6)
    (resp,) = server.run_until_done()
    assert resp.ok
    rep = session.report()
    assert rep is not None and rep is E.last_report()
    assert rep.modes()


def test_streamed_trace_count_counts_real_traces(db, sigma):
    E.clear_exec_cache()
    cdb = S.chunk_db(db, memory_budget_bytes=1 << 18, chunk_rows=1 << 12)
    plan = _plan("q1", sigma)
    ex = E.cached_executable(plan, cdb, sigma=sigma)
    assert isinstance(ex, E.StreamedExecutable) and ex.trace_count == 0
    ex(cdb, {"date": 0.6})
    first = ex.trace_count
    assert first >= 1
    ex(cdb, {"date": 0.7})  # a rebind re-enters the compiled region fns
    assert ex.trace_count == first
    # the same relation with unique quantities: the column no longer
    # dictionary-encodes, so its chunks bring a new decode recipe
    li = db["lineitem"]
    q = np.asarray(li.columns["quantity"])
    cols = dict(li.columns, quantity=q + np.arange(len(q), dtype=np.float32) * 1e-3)
    db2 = dict(db, lineitem=Table(cols, li.nrows, mask=li.mask, sorted_on=li.sorted_on))
    cdb2 = S.chunk_db(db2, memory_budget_bytes=1 << 18, chunk_rows=1 << 12)
    assert cdb2["lineitem"].chunk_decode_spec(0) != cdb["lineitem"].chunk_decode_spec(0)
    ex(cdb2, {"date": 0.7})
    assert ex.trace_count > first
