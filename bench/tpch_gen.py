"""The benchmark's own copy of the repository's TPC-H-shaped generator.

Kept here so that a change to the program cannot move the data a cell
measures.  The draws are those of ``repro.data.tpch.generate`` in the same
order, so the same seed gives the same relations: ~4 lineitems per order
(lineitem rows draw their order uniformly, so lines per order follow
Poisson(4)), 10 orders per customer, dense integer keys, dates as unit
floats, no strings.  Every column is 4 bytes wide (int32 or float32).
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np

Relations = Dict[str, Dict[str, np.ndarray]]

#: physical sort order of each relation, as the program's tables declare it
SORTED_ON = {
    "lineitem": ("orderkey",),
    "orders": ("orderkey",),
    "customer": ("custkey",),
    "part": ("partkey",),
    "supplier": ("suppkey",),
    "nation": ("nationkey",),
}

N_NATION = 25


def rows(sf: float) -> Dict[str, int]:
    """Rows of each relation at scale factor ``sf``."""
    return {
        "lineitem": int(6_000_000 * sf),
        "orders": int(1_500_000 * sf),
        "customer": int(150_000 * sf),
        "part": max(int(200_000 * sf), 64),
        "supplier": max(int(10_000 * sf), 16),
        "nation": N_NATION,
    }


def column_bytes(sf: float, columns: Mapping[str, Sequence[str]]) -> int:
    """Bytes of the named columns at ``sf``: every column is 4 bytes a row."""
    n = rows(sf)
    return sum(4 * n[rel] * len(cols) for rel, cols in columns.items())


def generate(sf: float, seed: int) -> Relations:
    """The six relations at scale factor ``sf`` from ``seed``, as numpy."""
    rng = np.random.default_rng(seed)
    n = rows(sf)
    n_li, n_ord, n_cust = n["lineitem"], n["orders"], n["customer"]
    n_part, n_supp = n["part"], n["supplier"]

    orders = {
        "orderkey": np.arange(n_ord, dtype=np.int32),
        "custkey": rng.integers(0, n_cust, n_ord).astype(np.int32),
        "orderdate": rng.random(n_ord).astype(np.float32),
        "shippriority": rng.integers(0, 5, n_ord).astype(np.int32),
        "totalprice": (rng.random(n_ord) * 1e4).astype(np.float32),
    }
    lineitem = {
        "orderkey": np.sort(rng.integers(0, n_ord, n_li)).astype(np.int32),
        "partkey": rng.integers(0, n_part, n_li).astype(np.int32),
        "suppkey": rng.integers(0, n_supp, n_li).astype(np.int32),
        "quantity": rng.integers(1, 51, n_li).astype(np.float32),
        "extendedprice": (rng.random(n_li) * 1e3 + 1).astype(np.float32),
        "discount": (rng.random(n_li) * 0.1).astype(np.float32),
        "tax": (rng.random(n_li) * 0.08).astype(np.float32),
        "returnflag": rng.integers(0, 3, n_li).astype(np.int32),
        "linestatus": rng.integers(0, 2, n_li).astype(np.int32),
        "shipdate": rng.random(n_li).astype(np.float32),
    }
    customer = {
        "custkey": np.arange(n_cust, dtype=np.int32),
        "nationkey": rng.integers(0, N_NATION, n_cust).astype(np.int32),
        "mktsegment": rng.integers(0, 5, n_cust).astype(np.int32),
        "acctbal": (rng.random(n_cust) * 1e4).astype(np.float32),
    }
    part = {
        "partkey": np.arange(n_part, dtype=np.int32),
        "brand": rng.integers(0, 25, n_part).astype(np.int32),
        "color": rng.integers(0, 92, n_part).astype(np.int32),
        "retailprice": (rng.random(n_part) * 2e3).astype(np.float32),
    }
    supplier = {
        "suppkey": np.arange(n_supp, dtype=np.int32),
        "nationkey": rng.integers(0, N_NATION, n_supp).astype(np.int32),
    }
    nation = {
        "nationkey": np.arange(N_NATION, dtype=np.int32),
        "regionkey": np.arange(N_NATION, dtype=np.int32) % 5,
    }
    return {
        "lineitem": lineitem,
        "orders": orders,
        "customer": customer,
        "part": part,
        "supplier": supplier,
        "nation": nation,
    }
