"""Unit tests of the benchmark's own arithmetic: traffic, metrics, the
comparison and the lookup of files by name.  Nothing here touches a TPU."""
import json
import statistics

import ml_dtypes
import numpy as np
import pytest

from bench import check, precision, spec, spreads, stats, tpch_gen
from bench.stats import Completion
from bench.traffic import Clients, queries_of

BENCH = spec.benchmark()


def _stream(mix, seed, n=12):
    binding = lambda q, rng: spec.query(q).binding(rng)  # noqa: E731
    clients = Clients(mix, seed, binding)
    return [clients.next(c) for _ in range(n) for c in range(clients.n)]


@pytest.mark.parametrize("traffic", sorted({w["traffic"] for w in BENCH["workloads"]}))
def test_traffic_is_seeded(traffic):
    mix = spec.traffic(traffic)
    a, b = _stream(mix, 2**31 + 11), _stream(mix, 2**31 + 11)
    assert a == b
    other = _stream(mix, 5)
    # a seed changes the bindings, never the sequence of queries
    assert [q for q, _ in other] == [q for q, _ in a]
    if any(spec.query(q).binding(np.random.default_rng(0)) for q, _ in a):
        assert [bd for _, bd in other] != [bd for _, bd in a]


def test_rounds_hold_each_query_once():
    mix = spec.traffic("mix-serial")
    names = queries_of(mix)
    seq = [q for q, _ in _stream(mix, 1, n=5 * 4)]
    for r in range(4):
        assert sorted(seq[5 * r: 5 * r + 5]) == sorted(names)


def test_weighted_mix_follows_its_weights():
    mix = spec.traffic("dash-batched")
    seq = [q for q, _ in _stream(mix, 1, n=400)]
    share = seq.count("q1") / len(seq)
    assert 0.7 < share < 0.8


def test_bindings_are_float32_exact_and_in_range():
    rng = np.random.default_rng(3)
    for _ in range(50):
        d = spec.query("q1").binding(rng)["date"]
        assert 0.952 <= d <= 0.976 and float(np.float32(d)) == d
        assert 0 <= spec.query("q5").binding(rng)["region"] < 5
        assert 0 <= spec.query("q9").binding(rng)["color"] < 92
        t = spec.query("q18").binding(rng)["threshold"]
        assert 312 <= t <= 315


def _done(lat, t0=100.0, failed=()):
    """One answer per step, steps back to back from ``t0``."""
    out, t = [], t0
    for i, x in enumerate(lat):
        out.append(Completion("q1", {}, t, t + x, t, "error:X" if i in failed else "", {}))
        t += x
    return out


def test_answers_are_weighted_by_their_steps_share_of_the_window():
    done = _done([1.0, 2.0, 4.0, 1.0])  # steps end at 101, 103, 107, 108
    ans = stats.weighted(done, 105.0)
    assert [w for _, w in ans] == [1.0, 1.0, 0.5]
    assert stats.qps(ans, 5.0) == pytest.approx(2.5 / 5.0)
    ans = stats.weighted(_done([1.0, 2.0, 4.0], failed={1}), 105.0)
    assert stats.qps(ans, 5.0) == pytest.approx(1.5 / 5.0)
    assert stats.qps([], 5.0) is None


def test_a_batch_at_the_close_moves_the_rate_by_a_moment():
    """Four answers of one step ending just before or just after the close
    give nearly the same rate (an all-or-nothing count would jump by 4)."""
    batch = [Completion("q1", {}, 100.0, 110.0, 109.0, "", {}) for _ in range(4)]
    before = stats.qps(stats.weighted(batch, 110.001), 10.0)
    after = stats.qps(stats.weighted(batch, 109.999), 10.0)
    assert before == pytest.approx(after, rel=1e-2)


def test_percentiles():
    done = _done([float(x) for x in range(1, 21)])
    ans = [(c, 1.0) for c in done]
    assert stats.latency_percentile(ans, 50, 30.0) == pytest.approx(10.5)
    for q in (5, 50, 95, 100):
        assert stats.latency_percentile(ans, q, 30.0) == pytest.approx(np.percentile(range(1, 21), q))
    # a failed request counts as missing: at least the whole window
    failed = _done([1.0] * 20, failed={19})
    assert stats.latency_percentile([(c, 1.0) for c in failed], 100, 30.0) == 30.0
    # weights: a value of weight w sits w from the next; weight 0 drops it
    assert stats.percentile([1.0, 2.0, 3.0], [1.0, 1.0, 0.0], 100) == 2.0
    assert stats.percentile([1.0, 2.0, 3.0], [1.0, 1.0, 1e-9], 50) == pytest.approx(1.5, abs=1e-6)
    assert stats.percentile([5.0], [0.3], 95) == 5.0


def test_spread():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)


def test_spreads_table_reads_result_lines(tmp_path):
    for name, qps in (("a", [1.0, 1.1, 1.2, 3.0]), ("b", [1.0, 1.0, 1.0, 1.0])):
        d = tmp_path / name
        d.mkdir()
        for i, v in enumerate(qps):
            line = {"correct": True, "metrics": {"qps": {"value": v, "unit": "queries/s"}}}
            (d / f"{i}.out").write_text("log line\n" + json.dumps(line) + "\n")
    sets = [spreads.results(tmp_path / n) for n in ("a", "b")]
    row = spreads.table(sets)["qps"]
    a, b = row["sets"]
    assert a["n"] == 4 and a["median"] == pytest.approx(1.15)
    assert a["spread"] == pytest.approx(stats.spread([1.0, 1.1, 1.2, 3.0]))
    # the run at 3.0 lies farthest from the median and is left out
    assert a["trimmed"] == pytest.approx(stats.spread([1.0, 1.1, 1.2]))
    assert b["spread"] == 0.0 and row["widest"] == a["spread"]


def test_compare_counts_keys_and_relative_gaps():
    want = {1: np.array([100.0, 1.0]), 2: np.array([200.0, 3.0])}
    assert check.compare(want, want) == (0, 0.0)
    kd, rel = check.compare({1: np.array([101.0, 1.0]), 3: np.array([0.0, 0.0])}, want)
    assert kd == 2 and rel == pytest.approx(0.01)
    # a value near zero is measured against its column's median magnitude
    kd, rel = check.compare({1: np.array([100.0, 0.0]), 2: np.array([200.0, 3.0])},
                            {1: np.array([100.0, 1e-9]), 2: np.array([200.0, 3.0])})
    assert kd == 0 and rel < 1e-8
    assert check.compare({1: np.array([1.0])}, {1: np.array([1.0, 2.0])})[1] == check.MALFORMED
    checks = check.judge({"q1.key_diff": 0, "q1.rel_err": 2e-3}, {"q1.rel_err": 1e-3})
    assert not check.passed(checks)
    assert check.passed(check.judge({"q1.key_diff": 0, "q1.rel_err": 5e-4}, {"q1.rel_err": 1e-3}))
    assert not check.passed({})


def test_group_sums():
    keys = np.array([3, 1, 3, 3, 1, 7])
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], np.float32)
    k, s = precision.group_sum(keys, vals)
    assert k.tolist() == [1, 3, 7] and s.tolist() == [7.0, 8.0, 6.0]
    k2, s2 = precision.group_sum(keys, vals, ml_dtypes.bfloat16)
    assert k2.tolist() == [1, 3, 7] and s2.dtype == ml_dtypes.bfloat16
    assert s2.astype(np.float64).tolist() == [7.0, 8.0, 6.0]
    # in bfloat16 a long sum loses what its 8 bits cannot hold
    ones = np.ones(1001, np.float32)
    _, big = precision.group_sum(np.zeros(1001, int), ones, ml_dtypes.bfloat16)
    assert float(big[0]) != 1001.0


@pytest.mark.parametrize("qname", ["q1", "q3", "q5", "q9", "q18"])
def test_required_bytes_are_the_query_columns(qname):
    sf = 0.002
    rels = tpch_gen.generate(sf, 1)
    mod = spec.query(qname)
    want = sum(rels[r][c].nbytes for r, cols in mod.COLUMNS.items() for c in cols)
    assert mod.required_bytes(sf) == want


def test_generator_matches_the_programs():
    from repro.data import tpch

    ours = tpch_gen.generate(0.002, 42)
    theirs = tpch.generate(scale=0.002, seed=42).tables()
    assert set(ours) == set(theirs)
    for rel, cols in ours.items():
        assert theirs[rel].sorted_on == tpch_gen.SORTED_ON[rel]
        for c, a in cols.items():
            np.testing.assert_array_equal(a, np.asarray(theirs[rel].columns[c]))


def test_references_match_the_programs_oracles():
    """The benchmark's copies agree with the repository's numpy oracles."""
    from repro.data import tpch
    from repro.exec.queries import QUERIES

    rels = tpch_gen.generate(0.005, 9)
    db = tpch.generate(scale=0.005, seed=9).tables()
    rng = np.random.default_rng(4)
    for qname in ("q1", "q3", "q5", "q9", "q18"):
        mod = spec.query(qname)
        binding = mod.binding(rng)
        kd, rel = check.compare(QUERIES[qname].reference(db, **binding), mod.reference(rels, **binding))
        assert kd == 0 and rel < 1e-5, qname


def _check_cells(bench: dict) -> None:
    """Every cell of ``bench`` resolves by name and has the shape the
    benchmark's contract asks: one chip or four, as its configuration
    says; a configuration of several chips shards over all of them and
    streams nothing (``Session`` refuses both together); at most half of
    the cells, rounded down, on four chips, though one always may."""
    workloads = bench["workloads"]
    for w in workloads:
        cell = spec.cell(w["name"], bench)
        assert cell.config["scale_factor"] > 0
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for q in queries_of(cell.mix):
            assert f"{q}.rel_err" in cell.config["limits"]
        assert w["chips"] in (1, 4) and cell.chips == cell.config["chips"] == w["chips"], w["name"]
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        if cfg["chips"] > 1:
            session = cfg.get("session", {})
            assert session.get("shards") == cfg["chips"], c["name"]
            assert "memory_budget" not in session, c["name"]
    four = sum(w["chips"] == 4 for w in workloads)
    assert four <= max(1, len(workloads) // 2), four


def test_every_cell_resolves_by_name():
    _check_cells(BENCH)


def test_a_four_chip_cell_resolves_by_name(sharded_bench):
    _check_cells(sharded_bench)
    cfg_file = spec.BENCH / "configs" / "tpch-sf1-shard4.json"
    cfg = json.loads(cfg_file.read_text())
    # sharded and streamed at once: Session would refuse it
    cfg_file.write_text(json.dumps(dict(cfg, session={"shards": 4, "memory_budget": 10**8})))
    with pytest.raises(AssertionError, match="tpch-sf1-shard4"):
        _check_cells(sharded_bench)
    cfg_file.write_text(json.dumps(cfg))
    # one four-chip cell may stand alone, a second beside it is one too many
    four = sharded_bench["workloads"][-1]
    _check_cells(dict(sharded_bench, workloads=[four]))
    with pytest.raises(AssertionError):
        _check_cells(dict(sharded_bench, workloads=[four, dict(four, name="tpch-sf1-shard4.again")]))


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "tpch-new.json").write_text(json.dumps({"name": "tpch-new", "scale_factor": 3}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({"loop": "closed", "clients": 2}))
    (tmp_path / "metrics" / "new.layer_ms.py").write_text("def read(w):\n    return 42.0\n")
    (tmp_path / "peaks.json").write_text(json.dumps({"Chip X": {"hbm_bytes_per_s": 1}}))
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    assert spec.config("tpch-new")["scale_factor"] == 3
    assert spec.traffic("burst")["clients"] == 2
    assert spec.metric_reader("new.layer_ms")(None) == 42.0
    assert spec.peaks("Chip X")["hbm_bytes_per_s"] == 1
    with pytest.raises(KeyError):
        spec.peaks("Chip Y")


def test_without_a_tpu_there_is_no_result(tmp_path):
    """On the CPU the benchmark exits non-zero and prints no result line,
    in the checkout and in a directory holding only the benchmark."""
    import os
    import shutil
    import subprocess
    import sys

    alone = tmp_path / "alone"
    shutil.copytree(spec.BENCH, alone / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", alone)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    for root in (spec.ROOT, alone):
        proc = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "tpch-sf1.mix-serial",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=root, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
