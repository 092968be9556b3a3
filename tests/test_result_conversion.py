"""Host conversion of dictionary results (``engine.host_items``) against the
per-slot comprehension it replaced, and the served answer's contract: a
plain mutable ``dict``, with the slots fetched and entries kept counted by
``QueryServer``."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.data import tpch
from repro.dicts import base as dbase
from repro.exec import engine as E
from repro.exec.distributed import ShardedDictResult
from repro.serve import query_server as QS


def _oracle(keys, vals, valid):
    """The per-slot loop every ``items_np`` ran before ``host_items``."""
    ks, vs, valid = map(np.asarray, (keys, vals, valid))
    return {int(k): vs[i] for i, k in enumerate(ks) if valid[i]}


def _layout(name):
    """(keys, valid) of a slot array; invalid slots hold arbitrary keys."""
    rng = np.random.default_rng(11)
    if name == "empty":
        return np.zeros(0, np.int32), np.zeros(0, bool)
    if name == "all_invalid":
        return rng.integers(0, 100, 16).astype(np.int32), np.zeros(16, bool)
    if name == "all_valid":
        return np.arange(16, dtype=np.int32) * 3 + 1, np.ones(16, bool)
    if name == "last_slot":
        valid = np.zeros(16, bool)
        valid[-1] = True
        return np.arange(16, dtype=np.int32) + 7, valid
    if name == "negative":
        keys = -np.arange(1, 17, dtype=np.int32) * 1000
        return keys, rng.random(16) < 0.5
    if name == "wide_keys":
        keys = (2**24 + np.arange(16) * 2**26).astype(np.int32)
        keys[-1] = 2**31 - 2
        return keys, rng.random(16) < 0.6
    raise KeyError(name)


LAYOUTS = ["empty", "all_invalid", "all_valid", "last_slot", "negative",
           "wide_keys"]
PAYLOADS = [(1, np.float32), (5, np.float32), (1, np.int32), (5, np.int32)]


def _vals(n, width, dtype):
    rng = np.random.default_rng(5)
    return (rng.standard_normal((n, width)) * 1e4).astype(dtype)


def _dict_result(ds, keys, vals, valid):
    """A backend table whose ``items`` yields these slots: the backend's
    sentinel key marks every invalid slot."""
    if ds == "ht_linear":
        k = np.where(valid, keys, dbase.EMPTY).astype(np.int32)
        table = dbase.HashTable(jnp.asarray(k), jnp.asarray(vals),
                                jnp.int32(0))
    else:
        k = np.where(valid, keys, dbase.PAD).astype(np.int32)
        table = dbase.SortedTable(jnp.asarray(k), jnp.asarray(vals),
                                  jnp.int32(int(valid.sum())),
                                  jnp.zeros(1, jnp.int32))
    return E.DictResult(ds, table), k


def _convert(kind, keys, vals, valid):
    """(converted dict, the slot arrays the oracle reads) for one path."""
    if kind == "host_items":
        return E.host_items(jnp.asarray(keys), jnp.asarray(vals),
                            jnp.asarray(valid)), (keys, vals, valid)
    if kind == "PlanResult":
        res = E.PlanResult("ht_linear", jnp.asarray(keys), jnp.asarray(vals),
                           jnp.asarray(valid))
        return res.items_np(), (keys, vals, valid)
    if kind == "ShardedDictResult":
        res = ShardedDictResult("ht_linear", jnp.asarray(keys),
                                jnp.asarray(vals), jnp.asarray(valid))
        return res.items_np(), (keys, vals, valid)
    ds = kind.split(".", 1)[1]
    res, k = _dict_result(ds, keys, vals, valid)
    return res.items_np(), (k, vals, valid)


def _assert_same(got, want):
    assert type(got) is dict
    assert list(got) == list(want)
    assert all(type(k) is int for k in got)
    for k, w in want.items():
        g = got[k]
        assert type(g) is type(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("width,dtype", PAYLOADS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("kind", ["host_items", "PlanResult",
                                  "ShardedDictResult", "DictResult.ht_linear",
                                  "DictResult.st_sorted"])
def test_conversion_equals_the_slot_loop(kind, layout, width, dtype):
    keys, valid = _layout(layout)
    vals = _vals(keys.shape[0], width, dtype)
    got, slots = _convert(kind, keys, vals, valid)
    want = _oracle(*slots)
    assert len(want) == int(valid.sum())
    _assert_same(got, want)


def test_values_do_not_pin_the_slot_array():
    """Each value is a row of the compacted copy: the answer keeps its
    entries' rows, not every slot."""
    keys, valid = _layout("last_slot")
    vals = _vals(16, 5, np.float32)
    (v,) = E.host_items(jnp.asarray(keys), jnp.asarray(vals),
                        jnp.asarray(valid)).values()
    assert v.base is not None and v.base.shape == (1, 5)


@pytest.fixture(scope="module")
def db():
    return tpch.generate(scale=0.002, seed=3).tables()


@pytest.mark.parametrize("qname,binds", [
    ("q1", [{"date": 0.6}, {"date": 0.7}]),
    ("q18", [{"threshold": 150.0}, {"threshold": 200.0}]),
])
def test_served_answer_is_a_mutable_dict_and_counted(db, qname, binds):
    server = QS.QueryServer(repro.connect(dict(db)), max_batch=2)
    server.warm_up([qname])
    shape = server._shape(qname)
    slots = [shape.executable(db, shape.query.bind_defaults(b)).keys.shape[0]
             for b in binds]
    for b in binds:
        server.submit(qname, **b)
    resps = server.step()
    assert len(resps) == 2 and all(r.ok and r.batch_size == 2 for r in resps)
    assert server.counters["result_slots"] == sum(slots)
    assert server.counters["result_entries"] == sum(
        len(r.result) for r in resps)
    stats = server.stats()
    assert stats["result_slots"] == sum(slots)
    assert 0 < stats["result_entries"] <= stats["result_slots"]
    ans = resps[0].result
    assert type(ans) is dict and ans
    k = next(iter(ans))
    new = ans[k] * 1.5 + 1.0
    ans[k] = new
    assert resps[0].result[k] is new
