"""The reduction from a profiler trace to metrics, on a small trace recorded
on one TPU v5e: the bench client loop over q1 and q5 at SF 0.02 for 0.3 s
(``xz``-compressed ``.xplane.pb``)."""
import lzma
from pathlib import Path

import pytest

from bench import trace_reduce as T

TRACE = Path(__file__).parent / "data" / "bench_q1_q5.xplane.pb.xz"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(lzma.decompress(TRACE.read_bytes()))


@pytest.fixture(scope="module")
def red(pd):
    return T.reduce(pd)


def _raw_ops(pd, lo, hi):
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in line.events
            if min(e.end_ns, hi) > max(e.start_ns, lo)]


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]) == [(0, 3), (5, 9), (10, 11)]
    assert T.union([(0, 10), (2, 3)]) == [(0, 10)]


def test_busy_is_the_union_of_device_ops(pd, red):
    lo, hi = red.window
    raw = _raw_ops(pd, lo, hi)
    assert raw, "the recorded window holds device operations"
    # independent count: every nanosecond covered by some operation, on a
    # coarse grid of 1 us cells
    covered = set()
    for a, b in raw:
        covered.update(range(int(a) // 1000, (int(b) - 1) // 1000 + 1))
    assert red.busy_s == pytest.approx(len(covered) * 1e-6, rel=0.05, abs=2e-4)
    assert 0 < red.busy_s < red.window_s
    idle = 1 - red.busy_s / red.window_s
    assert 0 < idle < 1


def test_own_time_adds_up_to_busy_time(red):
    """A ``while`` holds its body's operations: own times do not count them
    twice, so on one device they sum to no more than the busy time."""
    own = sum(o.self_ns for o in red.ops[0]) / 1e9
    assert own <= red.busy_s * 1.0001
    assert own >= 0.5 * red.busy_s


def test_op_names_are_short_hlo(red):
    names = {o.name for o in red.ops[0]}
    assert any("/" in n and "=" in n for n in names)
    assert all("{" not in n and "%" not in n and len(n) < 260 for n in names)
    assert any(n.startswith("jit__run(") for n in names)


def test_window_is_the_client_span(red):
    lo, hi = red.window
    steps = [(a, b) for a, b, n in red.client if n == "bench.step"]
    assert steps and all(lo <= a and b <= hi for a, b in steps)
    assert red.window_s == pytest.approx((hi - lo) / 1e9)


def test_gaps_are_labelled_by_the_client_loop(red):
    gaps = red.gaps(0)
    assert gaps and all(b - a >= T.GAP_MIN_NS for a, b in gaps)
    busy = red.busy(0)
    for a, b in gaps:  # no gap overlaps an operation
        assert all(y <= a or x >= b for x, y in busy)
    labels = {red.label((a + b) / 2) for a, b in gaps}
    assert all(lab.startswith("bench.") for lab in labels), labels
    assert any(lab.startswith("bench.step") for lab in labels)


def test_breakdown(red):
    bd = red.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        rows = bd[key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
        assert [s for _, s in rows] == sorted((s for _, s in rows), reverse=True)
    idle_s = sum(b - a for a, b in red.gaps(0)) / 1e9
    assert sum(s for _, s in bd["idle_gaps"]) <= idle_s * 1.0001


def test_transfers_to_the_device_are_found(red):
    # each request's binding goes to the device as scalars
    assert red.h2d_s() > 0 and len(red.transfers) > 0


def test_a_window_over_four_chips():
    """Busy time and device ops are means over the chips, an empty chip
    counting as idle; idle gaps are read on the first chip."""
    Op = T.Op
    ops = {
        0: [Op(0, 40_000, "a", 40_000)],
        1: [Op(0, 40_000, "a", 40_000), Op(50_000, 70_000, "b", 20_000)],
        2: [Op(10_000, 90_000, "c", 80_000)],
    }
    client = [(0, 100_000, "bench.window"), (0, 100_000, "bench.step")]
    red = T.Reduction((0, 100_000), ops, client, [], n_devices=4)
    assert red.window_s == pytest.approx(1e-4)
    assert red.busy_s == pytest.approx((40_000 + 60_000 + 80_000 + 0) / 4 / 1e9)
    bd = red.breakdown()
    assert dict((n, s) for n, s in bd["device_ops"]) == pytest.approx(
        {"a": 80_000 / 4 / 1e9, "c": 80_000 / 4 / 1e9, "b": 20_000 / 4 / 1e9}
    )
    assert red.gaps() == [(40_000, 100_000)]
    assert bd["idle_gaps"] == [["bench.step", pytest.approx(60_000 / 1e9)]]
