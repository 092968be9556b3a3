"""TPC-H Q9, product type profit: profit per (supplier nation, order year)
over the lineitems of parts of one colour.  Four joins, 175 groups."""
import numpy as np

from bench.precision import as_dtype, group_sum
from bench.tpch_gen import column_bytes

COLUMNS = {
    "part": ("partkey", "color", "retailprice"),
    "lineitem": ("partkey", "suppkey", "orderkey", "quantity", "extendedprice", "discount"),
    "supplier": ("suppkey", "nationkey"),
    "orders": ("orderkey", "orderdate"),
}

#: order dates fall into this many year buckets
YEARS = 7


def binding(rng) -> dict:
    """TPC-H 2.4.9.3: COLOR drawn from the 92 colours of P_NAME."""
    return {"color": int(rng.integers(0, 92))}


def required_bytes(sf: float) -> int:
    return column_bytes(sf, COLUMNS)


def reference(db, dt=np.float64, color: int = 45):
    li, pa, su, od = db["lineitem"], db["part"], db["supplier"], db["orders"]
    lk = li["partkey"]
    hit = pa["color"][lk] == color
    lk = lk[hit]
    lok = li["orderkey"][hit]
    # the year bucket is floor(orderdate * 7) in float32, as the query states
    year = np.floor(od["orderdate"][lok] * np.float32(YEARS)).astype(np.int64)
    key = su["nationkey"][li["suppkey"][hit]].astype(np.int64) * YEARS + year
    ep, dc, qt = (as_dtype(li[c][hit], dt) for c in ("extendedprice", "discount", "quantity"))
    retail = as_dtype(pa["retailprice"][lk], dt)
    profit = ep * (dt(1) - dc) - qt * retail * dt(0.01)
    keys, sums = group_sum(key, profit, dt)
    return {int(kk): np.array([vv], sums.dtype) for kk, vv in zip(keys, sums)}
