"""TPC-H Q18, large volume customer: the orders whose total quantity exceeds
a threshold, with their total price.  A group-by over every order (1.5M
groups at SF 1), a HAVING and a join back to orders."""
import numpy as np

from bench.precision import as_dtype, group_sum
from bench.tpch_gen import column_bytes

COLUMNS = {
    "lineitem": ("orderkey", "quantity"),
    "orders": ("orderkey", "totalprice"),
}


def binding(rng) -> dict:
    """TPC-H 2.4.18.3: QUANTITY uniform in [312, 315]."""
    return {"threshold": float(np.float32(rng.uniform(312.0, 315.0)))}


def required_bytes(sf: float) -> int:
    return column_bytes(sf, COLUMNS)


def reference(db, dt=np.float64, threshold: float = 313.5):
    li, od = db["lineitem"], db["orders"]
    keys, sums = group_sum(li["orderkey"], as_dtype(li["quantity"], dt), dt)
    big = sums > threshold
    tp = as_dtype(od["totalprice"], dt)
    return {
        int(k): np.array([s, tp[k]], sums.dtype)
        for k, s in zip(keys[big], sums[big])
    }
