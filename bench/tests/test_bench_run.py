"""A whole run of each cell on the CPU at a small scale, past the look for
a chip: the served answers agree with the references, the bfloat16 control
fails the comparison, and so does every fault the cell can have when it is
planted under the timed path."""
import time

import jax
import pytest

from bench import check, control, run, spec, tpch_gen

SF = 0.004
SEED = 2**31 + 5
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def small(name: str) -> spec.Cell:
    """The cell at scale factor ``SF``; a memory budget keeps its share of
    lineitem, and chunks stay the largest power of two of which two fit."""
    cell = spec.cell(name)
    cfg = dict(cell.config, scale_factor=SF)
    sess = dict(cfg.get("session", {}))
    if "memory_budget" in sess:
        full = tpch_gen.rows(cell.config["scale_factor"])["lineitem"] * 40
        budget = tpch_gen.rows(SF)["lineitem"] * 40 * sess["memory_budget"] // full
        sess["memory_budget"] = budget
        sess["chunk_rows"] = 1 << ((budget // 80).bit_length() - 1)
    cell.config = dict(cfg, session=sess)
    return cell


def run_small(name: str, seconds: float = 1.0) -> dict:
    return run.run(small(name), SEED, seconds, False, jax.devices(), time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_served_answers_match_the_references(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e = {m["name"] for m in spec.cell(name).end_to_end}
    assert set(res["metrics"]) == e2e
    assert {"qps", "latency_p50_s", "latency_p95_s", "setup_s"} <= e2e
    # one reading of the peak, under the bound its cell is listed for
    peaks = [m for m in e2e if m.split(".")[0] == "peak_hbm_gb"]
    assert len(peaks) == 1
    assert res["metrics"][peaks[0]]["value"] == res["device"]["memory_peak_bytes"] / 1e9
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_the_bfloat16_control_fails(name):
    cell = small(name)
    values = control.readings(cell, SEED, requests=3)
    assert not check.passed(check.judge(values, cell.config["limits"])), values


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: one value of every result."""
    from repro.exec import engine

    items = engine.PlanResult.items_np

    def altered(self):
        out = items(self)
        if out:
            k = next(iter(out))
            out[k] = out[k] * 1.5 + 1.0
        return out

    monkeypatch.setattr(engine.PlanResult, "items_np", altered)


def _stale_answer(monkeypatch):
    """State returned unchanged: every executable answers each request with
    the result of its first call, whatever the binding."""
    from repro.exec import engine

    for cls in (engine.Executable, engine.StreamedExecutable):
        call = cls.__call__

        def first(self, db, params=None, _call=call):
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


FAULTS = [
    ("tpch-sf1.mix-serial", _alter_answer),
    ("tpch-sf1.mix-serial", _stale_answer),
    ("tpch-sf1.dash-batched", _half_batch),
    ("tpch-sf1-stream.q1-serial", _drop_chunks),
    ("tpch-sf1-stream.q1-serial", _stale_answer),
]


@pytest.mark.parametrize(
    "name,fault", FAULTS, ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS]
)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(name)
    assert not res["correct"], res["checks"]
