"""Group sums for the references: exact in float64, or held in a lower
precision for the control.

``float64`` sums each group in row order with ``np.bincount``.  Any other
dtype (the control uses ``ml_dtypes.bfloat16``) keeps every partial sum in
that dtype and adds pairwise, as a tree reduction would: neighbours within a
group are added level by level, each sum rounded to the dtype.  That is the
most accurate a reduction held in that precision can be, so a control that
fails with it fails with any other order too.
"""
from __future__ import annotations

import numpy as np


def group_sum(keys: np.ndarray, vals: np.ndarray, dt=np.float64):
    """(distinct keys ascending, per-key sum of ``vals``) computed in ``dt``."""
    uniq, (sums,) = group_sums(keys, [vals], dt)
    return uniq, sums


def group_sums(keys: np.ndarray, lanes, dt=np.float64):
    """(distinct keys ascending, [per-key sum of each lane]) in ``dt``."""
    keys = np.asarray(keys, np.int64)
    if np.dtype(dt) != np.float64:
        uniq, inv = np.unique(keys, return_inverse=True)
        return uniq, [_pairwise_segment_sum(inv, np.asarray(v).astype(dt), len(uniq)) for v in lanes]
    if len(keys) and keys.min() >= 0 and keys.max() < 1 << 26:
        # dense keys: bincount in one pass each, no sort
        present = np.flatnonzero(np.bincount(keys))
        return present, [np.bincount(keys, weights=np.asarray(v, np.float64))[present] for v in lanes]
    uniq, inv = np.unique(keys, return_inverse=True)
    n = len(uniq)
    return uniq, [np.bincount(inv, weights=np.asarray(v, np.float64), minlength=n) for v in lanes]


def _pairwise_segment_sum(seg: np.ndarray, v: np.ndarray, n_groups: int):
    order = np.argsort(seg, kind="stable")
    seg, v = seg[order], v[order]
    while len(seg) > 1:
        same_next = seg[1:] == seg[:-1]
        if not same_next.any():
            break
        idx = np.arange(len(seg))
        start = np.r_[True, ~same_next]
        pos = idx - np.maximum.accumulate(np.where(start, idx, 0))
        even = pos % 2 == 0
        pair = np.flatnonzero(even & np.r_[same_next, False])
        v = v.copy()
        v[pair] = v[pair] + v[pair + 1]  # rounded to the dtype
        seg, v = seg[even], v[even]
    out = np.zeros(n_groups, v.dtype)
    out[seg] = v
    return out


def as_dtype(a: np.ndarray, dt) -> np.ndarray:
    """A float column in the working precision ``dt``; keys stay integer."""
    return a.astype(dt) if a.dtype.kind == "f" else a
