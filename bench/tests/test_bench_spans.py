"""The readers of the program's own spans, counters and device scopes: the
raw trace decoder and the attribution on traces recorded on one TPU v5e,
the per-layer metrics on synthetic windows, and traced runs of every cell
on the CPU at a small scale."""
import lzma
import time
from pathlib import Path

import jax
import pytest

from bench import attribute, run, spec, trace_reduce, xplane_meta
from bench.stats import Completion
from bench.tests.test_bench_run import CELLS, SEED, small
from bench.window import Window
from repro.exec import engine as E

DATA = Path(__file__).parent / "data"
#: the bench client loop over q1 and q5 at SF 0.02 for 0.3 s (no scopes)
OLD = DATA / "bench_q1_q5.xplane.pb.xz"
#: ``bench/attribute.py --scale 0.01``: mix-serial for 0.5 s, and the
#: streamed q1 cell for 0.3 s
MIX = DATA / "attr_mix.xplane.pb.xz"
STREAM = DATA / "attr_stream.xplane.pb.xz"
SCOPES = (E.SCOPE_BUILD, E.SCOPE_PROBE, E.SCOPE_DECODE)
#: the per-layer metrics that read the server's spans and counters
SERVER = ("serve.convert_ms_per_query", "serve.queue_ms_per_query")


def _raw(path):
    return lzma.decompress(path.read_bytes())


def test_decoder_reads_each_ops_name_stack():
    ops = xplane_meta.device_ops(_raw(OLD))
    assert set(ops) == {"/device:TPU:0"}
    paths = {o.path for o in ops["/device:TPU:0"]}
    assert any(p.startswith("jit(_run)/jit(run)/jit(<lambda>)/scatter-min") for p in paths)
    assert all(o.end_ns >= o.start_ns for o in ops["/device:TPU:0"])


def test_decoder_keeps_the_profilers_clock():
    """Each op's interval is the one ``ProfileData`` gives, in the same
    order (``ProfileData`` rounds to whole nanoseconds)."""
    from jax.profiler import ProfileData

    raw = _raw(OLD)
    plane = next(p for p in ProfileData.from_serialized_xspace(raw).planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == xplane_meta.OPS_LINE)
    mine = xplane_meta.device_ops(raw)["/device:TPU:0"]
    theirs = [(e.start_ns, e.end_ns) for e in line.events]
    assert len(mine) == len(theirs)
    for o, (a, b) in zip(mine, theirs):
        assert abs(o.start_ns - a) < 2 and abs(o.end_ns - b) < 3


def test_own_time_leaves_out_nested_ops():
    Op = xplane_meta.DeviceOp
    ops = [Op(0, 100, "w"), Op(10, 30, "a"), Op(40, 50, "b"), Op(120, 130, "c")]
    own = {o.start_ns: t for o, t in xplane_meta._nest(ops)}
    assert own == {0: 70, 10: 20, 40: 10, 120: 10}


@pytest.mark.parametrize("path, scope", [
    ("jit(_run)/jit(run)/dict.build.ht_linear/jit(<lambda>)/scatter-add:", "dict.build"),
    ("jit(_run)/vmap(dict.probe.st_sorted)/gather", "dict.probe"),
    ("jit(run)/stream.decode/shift_right_logical", "stream.decode"),
    ("jit(run)/dict.build.ht/dict.probe.st/gather", "dict.probe"),  # the innermost
    ("jit(run)/dict.builder/add", None),
    ("", None),
])
def test_scope_of_a_name_stack(path, scope):
    assert xplane_meta.scope_of(path, SCOPES) == scope


def test_scopes_split_a_chip_recorded_resident_window():
    from jax.profiler import ProfileData

    raw = _raw(MIX)
    red = trace_reduce.reduce(ProfileData.from_serialized_xspace(raw))
    by = attribute.attribute(raw, answers=1)["busy_by_scope"]
    assert by[E.SCOPE_BUILD] > 0 and by[E.SCOPE_PROBE] > 0
    assert by[E.SCOPE_DECODE] == 0  # nothing streams in a resident cell
    # the same own time as the reduction's, split
    own = sum(o.self_ns for o in red.ops[0]) / 1e9
    assert sum(by.values()) == pytest.approx(own, rel=0.02)


def test_scopes_split_a_chip_recorded_streamed_window():
    out = attribute.attribute(_raw(STREAM), answers=1)
    by = out["busy_by_scope"]
    assert by[E.SCOPE_DECODE] > 0 and by[E.SCOPE_BUILD] > 0
    assert sum(by.values()) <= out["busy_s"] * 1.0001
    # what falls under no scope is named by its HLO text, the most first
    top = [t for _, t in out["unscoped_top"]]
    assert top and top == sorted(top, reverse=True) and sum(top) <= by[""] * 1.0001
    assert out["h2d_in_dispatch"] == 1.0  # chunk uploads happen in the enqueue


@pytest.mark.parametrize("fixture", [MIX, STREAM])
def test_server_spans_cover_the_idle_time_in_each_step(fixture):
    out = attribute.attribute(_raw(fixture), answers=1)
    assert out["idle_in_step_s"] > 0
    assert sum(out["idle_by_span"].values()) <= out["idle_in_step_s"] * 1.0001
    assert out["span_cover"] >= 0.9


def test_intersect():
    assert attribute.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert attribute.intersect([(0, 10)], [(10, 20)]) == []


class _Trace:
    def __init__(self, client, window=(0.0, 10e9)):
        self.client, self.window = client, window


def _window(trace=None, before=None, after=None, n=4):
    done = [Completion("q1", {}, 0.0, 1.0, 0.5, "", None) for _ in range(n)]
    return Window(
        completions=done, t0=0.0, t_last=10.0,
        counters_before=before or {}, counters_after=after or {},
        jax_traces=0, scale_factor=1.0, queries={}, peaks={}, trace=trace,
    )


def test_convert_ms_reads_the_union_of_convert_spans():
    read = spec.metric_reader("serve.convert_ms_per_query")
    c = E.SPAN_CONVERT
    client = [(0.0, 10e9, "bench.window"), (1e9, 3e9, c), (2e9, 4e9, c),
              (5e9, 6e9, E.SPAN_SYNC), (9.5e9, 11e9, c)]
    # 3 s, then 0.5 s up to the window's close, over 4 answers
    assert read(_window(_Trace(client))) == pytest.approx(3.5e3 / 4)
    assert read(_window(_Trace(client[:1]))) is None  # no span: nothing read
    assert read(_window(None)) is None


def test_convert_ms_is_silent_for_a_program_without_the_span(monkeypatch):
    read = spec.metric_reader("serve.convert_ms_per_query")
    monkeypatch.delattr(E, "SPAN_CONVERT")
    assert read(_window(_Trace([(1e9, 2e9, "serve.convert")]))) is None


def test_queue_ms_reads_the_counter():
    read = spec.metric_reader("serve.queue_ms_per_query")
    w = _window(before={"responses": 10, "queue_wait_s": 4.0},
                after={"responses": 14, "queue_wait_s": 12.0})
    assert read(w) == pytest.approx(2000.0)
    assert read(_window(before={"responses": 1}, after={"responses": 5})) is None
    assert read(_window(before={"responses": 5, "queue_wait_s": 1.0},
                        after={"responses": 5, "queue_wait_s": 1.0})) is None


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reports_every_metric_of_its_cell(name, monkeypatch):
    """The result line of ``--trace 1`` (peaks of a v5e, since the CPU has
    none) holds the server's metrics the cell lists, and its idle gaps
    name the server's spans."""
    peaks = spec.peaks
    monkeypatch.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
    res = run.run(small(name), SEED, 1.0, True, jax.devices(), time.perf_counter())
    assert res["correct"], res["checks"]
    new = [m["name"] for m in spec.cell(name).per_layer if m["name"] in SERVER]
    assert new
    for m in new:
        assert res["metrics"][m]["value"] > 0, (m, res["metrics"])
    labels = [lab for lab, _ in res["breakdown"]["idle_gaps"]]
    assert any(lab.startswith("bench.step > serve.") for lab in labels), labels
