"""TPC-H Q3 as the paper runs it (arXiv:2112.13099 §6.3): revenue per order
for the orders placed before a date, a group-join of lineitem with orders.
About 0.7M groups at SF 1."""
import numpy as np

from bench.precision import as_dtype, group_sum
from bench.tpch_gen import column_bytes

COLUMNS = {
    "lineitem": ("orderkey", "extendedprice", "discount"),
    "orders": ("orderkey", "orderdate"),
}


def binding(rng) -> dict:
    """TPC-H 2.4.3.3: DATE uniform in [1995-03-01, 1995-03-31]."""
    return {"date": float(np.float32(rng.uniform(0.480, 0.493)))}


def required_bytes(sf: float) -> int:
    return column_bytes(sf, COLUMNS)


def reference(db, dt=np.float64, date: float = 0.486):
    li, od = db["lineitem"], db["orders"]
    placed = np.zeros(int(od["orderkey"].max()) + 1, bool)
    placed[od["orderkey"][od["orderdate"] < np.float32(date)]] = True
    k = li["orderkey"]
    hit = placed[k]
    ep, dc = as_dtype(li["extendedprice"][hit], dt), as_dtype(li["discount"][hit], dt)
    keys, sums = group_sum(k[hit], ep * (dt(1) - dc), dt)
    return {int(kk): np.array([vv], sums.dtype) for kk, vv in zip(keys, sums)}
