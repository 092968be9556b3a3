"""TPC-H Q5, local supplier volume: revenue per nation of one region, where
customer and supplier share the nation.  A chain of four joins feeding a
five-group aggregate."""
import numpy as np

from bench.precision import as_dtype, group_sum
from bench.tpch_gen import column_bytes

COLUMNS = {
    "nation": ("nationkey", "regionkey"),
    "customer": ("custkey", "nationkey"),
    "orders": ("orderkey", "custkey"),
    "lineitem": ("orderkey", "suppkey", "extendedprice", "discount"),
    "supplier": ("suppkey", "nationkey"),
}


def binding(rng) -> dict:
    """TPC-H 2.4.5.3: REGION drawn from the five regions."""
    return {"region": int(rng.integers(0, 5))}


def required_bytes(sf: float) -> int:
    return column_bytes(sf, COLUMNS)


def _row_of(table_keys, probes):
    """(row of each probe in ``table_keys``, found mask)."""
    order = np.argsort(table_keys, kind="stable")
    sk = table_keys[order]
    at = np.minimum(np.searchsorted(sk, probes), max(len(sk) - 1, 0))
    found = sk[at] == probes
    return order[at], found


def reference(db, dt=np.float64, region: int = 2):
    li, od, cu, su, na = (db[r] for r in ("lineitem", "orders", "customer", "supplier", "nation"))
    reg = na["regionkey"]
    cn = cu["nationkey"]
    pos, found = _row_of(od["orderkey"], li["orderkey"])
    ck = od["custkey"][pos]
    nat = np.where(found & (reg[cn[ck]] == region), cn[ck], -1)
    keep = (nat >= 0) & (su["nationkey"][li["suppkey"]] == nat)
    ep, dc = as_dtype(li["extendedprice"][keep], dt), as_dtype(li["discount"][keep], dt)
    keys, sums = group_sum(nat[keep], ep * (dt(1) - dc), dt)
    return {int(kk): np.array([vv], sums.dtype) for kk, vv in zip(keys, sums)}
