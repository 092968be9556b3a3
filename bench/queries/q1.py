"""TPC-H Q1, pricing summary: five aggregates per (returnflag, linestatus)
over the lineitems shipped by a date.  A scan with a six-group group-by."""
import numpy as np

from bench.precision import as_dtype, group_sums
from bench.tpch_gen import column_bytes

#: the columns the query reads once, at the least
COLUMNS = {
    "lineitem": (
        "shipdate", "returnflag", "linestatus", "quantity",
        "extendedprice", "discount", "tax",
    ),
}


def binding(rng) -> dict:
    """TPC-H 2.4.1.3: DELTA uniform in [60, 120] days before 1998-12-01, on
    the generator's unit dates; float32-exact, as the engine holds it."""
    return {"date": float(np.float32(rng.uniform(0.952, 0.976)))}


def required_bytes(sf: float) -> int:
    return column_bytes(sf, COLUMNS)


def reference(db, dt=np.float64, date: float = 0.964):
    li = db["lineitem"]
    m = li["shipdate"] <= np.float32(date)
    key = (li["returnflag"] * 2 + li["linestatus"])[m]
    qty, ep, dc, tx = (as_dtype(li[c][m], dt) for c in ("quantity", "extendedprice", "discount", "tax"))
    one = dt(1)
    disc_price = ep * (one - dc)
    charge = disc_price * (one + tx)
    keys, sums = group_sums(key, [qty, ep, disc_price, charge, np.ones(len(key), dt)], dt)
    table = np.stack(sums, axis=1)
    return {int(k): table[i] for i, k in enumerate(keys)}
