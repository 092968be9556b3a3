"""A whole run of each cell on the CPU at a small scale, past the look for
a chip, on as many devices as the cell asks for (``cells``): the served
answers agree with the references, the bfloat16 control fails the
comparison, and so does every fault the cell can have when it is planted
under the timed path.  Beside the cells of ``BENCHMARK.json`` runs a
four-chip cell it does not list, ``cells.SHARDED``, as a later PR would
add one."""
import jax
import pytest

from bench import check, control, spec
from bench.tests.cells import CELLS, SEED, SHARDED, run_cell, small


def cell_of(name: str, request) -> spec.Cell:
    """The small cell ``name``; ``SHARDED`` with its files in place."""
    if name == SHARDED:
        request.getfixturevalue("sharded_bench")
    return small(name)


@pytest.mark.parametrize("name", CELLS + [SHARDED])
def test_served_answers_match_the_references(name, request):
    cell = cell_of(name, request)
    res, counters = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # served by the primary path: a rescue by the degradation ladder is no pass
    assert counters["degraded"] == counters["retries"] == counters["errors"] == 0, counters
    e2e = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == e2e
    assert {"qps", "latency_p50_s", "latency_p95_s", "setup_s"} <= e2e
    # one reading of the peak, under the bound its cell is listed for
    peaks = [m for m in e2e if m.split(".")[0] == "peak_hbm_gb"]
    assert len(peaks) == 1
    assert res["metrics"][peaks[0]]["value"] == res["device"]["memory_peak_bytes"] / 1e9
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == cell.chips
    assert list(res)[-1] == "checks"
    assert jax.device_count() == 1  # a cell of several chips ran in a child process


@pytest.mark.parametrize("name", CELLS + [SHARDED])
def test_the_bfloat16_control_fails(name, request):
    cell = cell_of(name, request)
    values = control.readings(cell, SEED, requests=3)
    assert not check.passed(check.judge(values, cell.config["limits"])), values


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: one value of every result."""
    from repro.exec import distributed, engine

    for cls in (engine.PlanResult, distributed.ShardedDictResult):

        def altered(self, _items=cls.items_np):
            out = _items(self)
            if out:
                k = next(iter(out))
                out[k] = out[k] * 1.5 + 1.0
            return out

        monkeypatch.setattr(cls, "items_np", altered)


def _stale_answer(monkeypatch):
    """State returned unchanged: every executable answers each request with
    the result of its first call, whatever the binding."""
    from repro.exec import distributed, engine

    for cls in (engine.Executable, engine.StreamedExecutable, distributed.ShardedExecutable):

        def first(self, db=None, params=None, _call=cls.__call__):
            if not hasattr(self, "_first_answer"):
                self._first_answer = _call(self, db, params)
            return self._first_answer

        monkeypatch.setattr(cls, "__call__", first)


def _half_batch(monkeypatch):
    """Half of a micro-batch left out: the second half of the requests get
    the answers of the first half."""
    from repro.exec import engine

    batched = engine.Executable.call_batched

    def half(self, db, params_list):
        keep = max(1, len(params_list) // 2)
        out = batched(self, db, params_list[:keep])
        return [out[i % keep] for i in range(len(params_list))]

    monkeypatch.setattr(engine.Executable, "call_batched", half)


def _drop_chunks(monkeypatch):
    """Half of the streamed chunks left out of every scan."""
    from repro.data import storage

    monkeypatch.setattr(
        storage.ChunkedTable, "n_chunks", property(lambda self: max(1, len(self.chunks) // 2))
    )


def _skip_exchange(monkeypatch):
    """The exchange between chips left out: every all-to-all hands each
    shard back its own buffers, so no row and no partial result moves."""
    from repro.exec import distributed

    monkeypatch.setattr(distributed, "_a2a", lambda x, axis: x)


FAULTS = [
    ("tpch-sf1.mix-serial", _alter_answer),
    ("tpch-sf1.mix-serial", _stale_answer),
    ("tpch-sf1.dash-batched", _half_batch),
    ("tpch-sf1-stream.q1-serial", _drop_chunks),
    ("tpch-sf1-stream.q1-serial", _stale_answer),
    (SHARDED, _alter_answer),
    (SHARDED, _stale_answer),
    (SHARDED, _skip_exchange),
]


@pytest.mark.parametrize(
    "name,fault", FAULTS, ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS]
)
def test_a_broken_timed_path_is_not_correct(name, fault, request):
    res, _ = run_cell(cell_of(name, request), fault=fault)
    assert not res["correct"], res["checks"]
