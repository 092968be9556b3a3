"""The numpy query references against row-at-a-time loops.

``exec.queries``' references are vectorized so they can check results at
scale factors a chip holds (60M lineitems at SF 10).  The loops below are
the plain form of each query's semantics; the vectorized references must
agree with them exactly — both accumulate each group's float32 terms in
row order in float64 — on the default binding and on a second one."""
import numpy as np
import pytest

from repro.data import tpch
from repro.exec.queries import QUERIES, _YEARS


def q3_loop(db, date):
    li, od = db["lineitem"], db["orders"]
    sel = np.asarray(od.col("orderdate")) < date
    ok = set(np.asarray(od.col("orderkey"))[sel].tolist())
    k = np.asarray(li.col("orderkey"))
    v = np.asarray(li.col("extendedprice")) * (1 - np.asarray(li.col("discount")))
    out = {}
    for kk, vv in zip(k, v):
        if int(kk) in ok:
            out[int(kk)] = out.get(int(kk), 0.0) + float(vv)
    return {k2: np.array([v2], np.float32) for k2, v2 in out.items()}


def q5_loop(db, region):
    li, od, cu, su, na = (
        db["lineitem"], db["orders"], db["customer"], db["supplier"], db["nation"]
    )
    reg = np.asarray(na.col("regionkey"))
    cn = np.asarray(cu.col("nationkey"))
    cust_ok = reg[cn] == region
    ord_nat = {}
    for okey, ck in zip(np.asarray(od.col("orderkey")), np.asarray(od.col("custkey"))):
        if cust_ok[ck]:
            ord_nat[int(okey)] = int(cn[ck])
    sn = np.asarray(su.col("nationkey"))
    out = {}
    lk = np.asarray(li.col("orderkey"))
    ls = np.asarray(li.col("suppkey"))
    rv = np.asarray(li.col("extendedprice")) * (1 - np.asarray(li.col("discount")))
    for okey, sk, r in zip(lk, ls, rv):
        nat = ord_nat.get(int(okey))
        if nat is not None and sn[sk] == nat:
            out[nat] = out.get(nat, 0.0) + float(r)
    return {k: np.array([v], np.float32) for k, v in out.items()}


def q9_loop(db, color):
    li, pa, su, od = db["lineitem"], db["part"], db["supplier"], db["orders"]
    pcol = np.asarray(pa.col("color"))
    pprice = np.asarray(pa.col("retailprice"))
    sn = np.asarray(su.col("nationkey"))
    odate = np.asarray(od.col("orderdate"))
    out = {}
    lk = np.asarray(li.col("partkey"))
    lsk = np.asarray(li.col("suppkey"))
    lok = np.asarray(li.col("orderkey"))
    ep = np.asarray(li.col("extendedprice"))
    dc = np.asarray(li.col("discount"))
    qt = np.asarray(li.col("quantity"))
    for i in range(len(lk)):
        if pcol[lk[i]] != color:
            continue
        year = int(odate[lok[i]] * _YEARS)
        key = int(sn[lsk[i]]) * _YEARS + year
        profit = ep[i] * (1 - dc[i]) - qt[i] * pprice[lk[i]] * 0.01
        out[key] = out.get(key, 0.0) + float(profit)
    return {k: np.array([v], np.float32) for k, v in out.items()}


def q18_loop(db, threshold):
    li, od = db["lineitem"], db["orders"]
    tp = np.asarray(od.col("totalprice"))
    agg = {}
    for kk, qq in zip(np.asarray(li.col("orderkey")), np.asarray(li.col("quantity"))):
        agg[int(kk)] = agg.get(int(kk), 0.0) + float(qq)
    return {
        kk: np.array([vv, tp[kk]], np.float32)
        for kk, vv in agg.items()
        if vv > threshold
    }


LOOPS = {
    "q3": (q3_loop, "date", (0.05, 0.3)),
    "q5": (q5_loop, "region", (0, 3)),
    "q9": (q9_loop, "color", (3, 17)),
    "q18": (q18_loop, "threshold", (150.0, 60.0)),
}


@pytest.fixture(scope="module")
def db():
    return tpch.generate(scale=0.004, seed=3).tables()


@pytest.mark.parametrize("which", [0, 1])
@pytest.mark.parametrize("qname", sorted(LOOPS))
def test_reference_equals_row_loop(db, qname, which):
    loop, knob, values = LOOPS[qname]
    binding = {knob: values[which]}
    want = loop(db, **binding)
    got = QUERIES[qname].reference(db, **binding)
    assert want, "binding selects no rows: the check would be empty"
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=f"{qname} key {k}")
