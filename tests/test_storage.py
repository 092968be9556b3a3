"""Out-of-core storage and streaming (DESIGN.md §10): encoding round-trips
on adversarial columns, device-side decode bitwise vs host decode, the
storage cost model's plan, chunked-streamed execution bitwise-identical to
decoded-resident execution for all five TPC-H queries, and the fused
kernel's in-register encoded decode + carried accumulator state."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import cost as C
from repro.core import plan as P
from repro.core.cost import AnalyticCostModel
from repro.core.lower import compile as compile_plan
from repro.core.synthesis import synthesize
from repro.data import storage as S
from repro.data import tpch
from repro.data.table import collect_stats
from repro.dicts import base as dbase
from repro.exec import engine as E
from repro.exec.queries import QUERIES
from repro.kernels import decode as DK
from repro.kernels import fused_pipeline as fp

DELTA = AnalyticCostModel()
BLOCK = 256  # small tiles so short test columns still span several


def _rng():
    return np.random.default_rng(7)


# adversarial columns: name -> (array, encodings that must apply to it)
def _adversarial():
    rng = _rng()
    n = 1000  # deliberately not a tile multiple — exercises pad trimming
    cases = {
        "all_constant": (np.full(n, 42, np.int32), ("rle", "bitpack", "dict")),
        "all_distinct": (
            rng.permutation(n).astype(np.int32), ("bitpack",),
        ),
        "skewed_runs": (
            np.repeat(rng.integers(0, 5, 40), 25).astype(np.int32),
            ("rle", "bitpack", "dict"),
        ),
        "negatives": (
            (rng.integers(0, 100, n) - 50).astype(np.int32), ("for", "dict"),
        ),
        "wide_frame": (  # straddles 2^24: FOR ref large, deltas small
            ((1 << 24) - 500 + rng.integers(0, 1000, n)).astype(np.int32),
            ("for",),
        ),
        "float_dict": (
            rng.choice(
                np.abs(rng.standard_normal(9)).astype(np.float32), n
            ),
            ("dict", "rle"),
        ),
        "single_row": (np.asarray([-7], np.int32), ("rle", "dict", "for")),
    }
    return cases


@pytest.mark.parametrize("name", sorted(_adversarial()))
def test_encoding_roundtrip_adversarial(name):
    a, modes = _adversarial()[name]
    for mode in ("auto", "plain", *modes):
        enc = S.encode_column(a, block=BLOCK, mode=mode)
        if mode != "auto":
            assert enc.kind == mode
        np.testing.assert_array_equal(enc.decode(), a)
        # device-side decode of the same payload is bitwise identical
        dev = np.asarray(DK.decode_device(
            enc, {k: jnp.asarray(v) for k, v in enc.payload.items()}
        ))
        np.testing.assert_array_equal(dev, a)
        # and the Pallas tile-decode kernel agrees
        pal = np.asarray(DK.pallas_decode(
            enc, {k: jnp.asarray(v) for k, v in enc.payload.items()},
            interpret=True,
        ))
        np.testing.assert_array_equal(pal, a)


def test_encoded_bytes_never_worse_than_plain_auto():
    for name, (a, _) in _adversarial().items():
        enc = S.encode_column(a, block=BLOCK, mode="auto")
        assert enc.nbytes <= a.nbytes or enc.kind == "plain", (name, enc.kind)


def test_chunked_table_roundtrip_and_device_upload():
    rng = _rng()
    n = 3 * (1 << 12) + 77  # short final chunk
    t = tpch.generate(scale=0.002, seed=1).tables()["lineitem"]
    ct = S.chunk_table(t, chunk_rows=1 << 12)
    assert ct.nrows == t.nrows and ct.n_chunks == -(-t.nrows // (1 << 12))
    dec = ct.decode()
    for c in t.names():
        np.testing.assert_array_equal(
            np.asarray(dec.col(c)), np.asarray(t.col(c))
        )
    # per-chunk device decode == host chunk decode, incl. short final chunk
    for i in (0, ct.n_chunks - 1):
        up, nbytes = ct.upload_chunk(i)
        td = ct.chunk_device(i, uploaded=up)
        assert nbytes < sum(4 * td.nrows for _ in t.names())  # compressed
        lo = i * ct.chunk_rows
        hi = min(lo + ct.chunk_rows, ct.nrows)
        for c in t.names():
            np.testing.assert_array_equal(
                np.asarray(td.col(c))[: td.nrows],
                np.asarray(t.col(c))[lo:hi],
            )
    del rng, n


def test_storage_plan_budget_selects_facts():
    db = tpch.generate(scale=0.01, seed=0).tables()
    sigma = collect_stats(db)
    decisions = C.storage_plan(sigma, memory_budget_bytes=1 << 20)
    assert decisions["lineitem"].mode == "streamed"
    # tiny dimensions stay decoded-resident
    assert decisions["supplier"].mode == "resident"
    # an unbounded budget keeps everything resident
    for d in C.storage_plan(sigma, memory_budget_bytes=1 << 40).values():
        assert d.mode == "resident"


# ---------------------------------------------------------------------------
# streamed execution: bitwise vs resident for all five TPC-H queries
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tpch_pair():
    db = tpch.generate(scale=0.01, seed=3).tables()
    cdb = S.chunk_db(db, memory_budget_bytes=1 << 20, chunk_rows=1 << 13)
    assert S.is_chunked(cdb["lineitem"])  # budget forces the fact out of core
    return db, cdb, collect_stats(db)


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_streamed_bitwise_vs_resident(tpch_pair, qname):
    db, cdb, sigma = tpch_pair
    q = QUERIES[qname]
    choices = synthesize(q.llql(), sigma, DELTA).choices
    plan = P.fuse(compile_plan(q.llql(), choices), sigma=sigma)
    params = E.coerce_bindings(plan, q.bind_defaults({}))
    ref = E.execute_plan(plan, db, sigma=sigma, params=params).items_np()
    got = E.execute_plan(plan, cdb, sigma=sigma, params=params).items_np()
    rep = E.last_report()
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k])
    # streaming actually engaged, and only encoded bytes crossed the link
    assert any(
        m.startswith("streamed") for m in rep.modes().values()
    ), rep.modes()
    assert rep.streamed_regions >= 1
    assert rep.chunks >= 2
    assert rep.wall_s > 0.0
    assert rep.peak_chunk_bytes < sum(
        4 * t.nrows * len(t.names())
        for rel, t in db.items()
        if S.is_chunked(cdb[rel])
    )


def test_streamed_executable_dispatch(tpch_pair):
    db, cdb, sigma = tpch_pair
    q = QUERIES["q1"]
    choices = synthesize(q.llql(), sigma, DELTA).choices
    plan = P.fuse(compile_plan(q.llql(), choices), sigma=sigma)
    ex_res = E.cached_executable(plan, db, sigma=sigma)
    ex_str = E.cached_executable(plan, cdb, sigma=sigma)
    assert isinstance(ex_str, E.StreamedExecutable)
    assert not isinstance(ex_res, E.StreamedExecutable)
    got = ex_str(cdb, q.defaults).items_np()
    ref = ex_res(db, q.defaults).items_np()
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-5)


def test_streamed_kernel_failure_propagates(tpch_pair, monkeypatch):
    """A kernel that fails inside a streamed region (here: the TPU
    compiler's refusal of a resident probe) reaches the caller; it is not
    turned into a decline that silently runs the XLA streamed loop."""
    db, cdb, sigma = tpch_pair
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")

    def refuse(*args, **kwargs):
        raise NotImplementedError("Only 2D gather is supported")

    monkeypatch.setattr(E, "_kernel_pipeline", refuse)
    q = QUERIES["q1"]
    choices = synthesize(q.llql(), sigma, DELTA).choices
    plan = P.fuse(compile_plan(q.llql(), choices), sigma=sigma)
    params = E.coerce_bindings(plan, q.bind_defaults({}))
    with pytest.raises(NotImplementedError, match="2D gather"):
        E.execute_plan(plan, cdb, sigma=sigma, params=params)


# ---------------------------------------------------------------------------
# fused kernel: in-register encoded decode, carried accumulator state
# ---------------------------------------------------------------------------


def test_fused_pipeline_encoded_matches_plain():
    rng = np.random.default_rng(11)
    n, block = 4096, 512
    grp = rng.integers(0, 40, n).astype(np.int32)  # bitpack-able
    w = np.repeat(rng.standard_normal(16).astype(np.float32), 256)  # rle
    off = (rng.integers(0, 200, n) + 50000).astype(np.int32)  # for-able
    price = rng.choice(rng.standard_normal(7).astype(np.float32), n)  # dict
    live = rng.random(n) < 0.8

    def row_fn(cols, lv, lookups, scalars):
        lv = lv & (cols["off"] > 50020)
        return cols["g"], (cols["w"] * cols["p"])[:, None], lv

    raw = dict(
        g=jnp.asarray(grp), w=jnp.asarray(w),
        off=jnp.asarray(off), p=jnp.asarray(price),
    )
    tk0, tv0 = fp.fused_pipeline(
        raw, jnp.asarray(live), {}, {}, row_fn, ("dict", 256, 1), block=block
    )
    enc = {}
    for name, arr, mode in (
        ("g", grp, "bitpack"), ("w", w, "rle"),
        ("off", off, "for"), ("p", price, "dict"),
    ):
        e = S.encode_column(arr, block=block, mode=mode)
        assert e.kind == mode, (name, e.kind)
        enc[name] = DK.encoded_stream(e)
    tk1, tv1 = fp.fused_pipeline(
        {}, jnp.asarray(live), {}, {}, row_fn, ("dict", 256, 1),
        block=block, encoded=enc,
    )
    np.testing.assert_array_equal(np.asarray(tk0), np.asarray(tk1))
    np.testing.assert_array_equal(np.asarray(tv0), np.asarray(tv1))


def test_fused_pipeline_init_carry_matches_one_shot():
    rng = np.random.default_rng(11)
    n, block = 4096, 512
    grp = rng.integers(0, 40, n).astype(np.int32)
    w = np.repeat(rng.standard_normal(16).astype(np.float32), 256)
    live = rng.random(n) < 0.8
    h = n // 2

    def rf(cols, lv, lookups, scalars):
        return cols["g"], cols["w"][:, None], lv

    k_full, v_full = fp.fused_pipeline(
        dict(g=jnp.asarray(grp), w=jnp.asarray(w)), jnp.asarray(live),
        {}, {}, rf, ("dict", 256, 1), block=block,
    )
    k_a, v_a = fp.fused_pipeline(
        dict(g=jnp.asarray(grp[:h]), w=jnp.asarray(w[:h])),
        jnp.asarray(live[:h]), {}, {}, rf, ("dict", 256, 1), block=block,
    )
    k_b, v_b = fp.fused_pipeline(
        dict(g=jnp.asarray(grp[h:]), w=jnp.asarray(w[h:])),
        jnp.asarray(live[h:]), {}, {}, rf, ("dict", 256, 1), block=block,
        init=(k_a, v_a),
    )
    ref = {}
    for i in range(n):
        if live[i]:
            ref[int(grp[i])] = ref.get(int(grp[i]), 0.0) + float(w[i])
    got = {
        int(k): float(v_b[i, 0])
        for i, k in enumerate(np.asarray(k_b)) if k != dbase.EMPTY
    }
    gotf = {
        int(k): float(v_full[i, 0])
        for i, k in enumerate(np.asarray(k_full)) if k != dbase.EMPTY
    }
    assert set(got) == set(ref) == set(gotf)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=2e-3, atol=2e-3)
        assert got[k] == gotf[k]  # same accumulation order -> bitwise
