"""The comparison that decides ``correct``: every answer served in the
window against the benchmark's own reference for the same query, binding
and data.

Two numbers per query of the cell, each with a limit from the
configuration file (``limits``):

* ``<q>.key_diff``: keys missing from or extra in the served answer, over
  every answer of that query (exact: limit 0);
* ``<q>.rel_err``: the widest relative gap of a served value from the
  reference's, over every key, value and answer of that query.  A value's
  gap is taken against the larger of its own reference magnitude and the
  median magnitude of its column in that answer, so a sum near zero cannot
  inflate it.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Mapping, Tuple

import numpy as np

#: reading of an answer whose shape differs from the reference's
MALFORMED = 1e9


def compare(got: Mapping[int, np.ndarray], want: Mapping[int, np.ndarray]) -> Tuple[int, float]:
    """(keys missing or extra, widest relative gap over the common keys)."""
    gk, wk = set(got), set(want)
    common = sorted(gk & wk)
    key_diff = len(gk ^ wk)
    if not common:
        return key_diff, 0.0
    try:
        g = np.stack([np.asarray(got[k], np.float64).reshape(-1) for k in common])
        w = np.stack([np.asarray(want[k], np.float64).reshape(-1) for k in common])
    except ValueError:
        return key_diff, MALFORMED
    if g.shape != w.shape or not np.isfinite(g).all():
        return key_diff, MALFORMED
    floor = np.median(np.abs(w), axis=0)
    den = np.maximum(np.abs(w), floor)
    den = np.where(den > 0, den, 1.0)
    return key_diff, float(np.max(np.abs(g - w) / den))


def readings(
    answers: Iterable[Tuple[str, dict, Mapping[int, np.ndarray]]],
    reference: Callable[[str, dict], Mapping[int, np.ndarray]],
) -> Dict[str, float]:
    """``{"<q>.key_diff": n, "<q>.rel_err": x}`` over (query, binding,
    served answer) triples; queries with no answer have no reading."""
    out: Dict[str, float] = {}
    for qname, binding, got in answers:
        kd, rel = compare(got, reference(qname, binding))
        out[f"{qname}.key_diff"] = max(out.get(f"{qname}.key_diff", 0), kd)
        out[f"{qname}.rel_err"] = max(out.get(f"{qname}.rel_err", 0.0), rel)
    return out


def judge(values: Mapping[str, float], limits: Mapping[str, float]) -> Dict[str, dict]:
    """Each reading beside its limit; a reading with no limit is an error in
    the configuration, not a pass."""
    out = {}
    for name in sorted(values):
        q, kind = name.split(".", 1)
        limit = 0 if kind == "key_diff" else limits[name]
        out[name] = {"value": values[name], "limit": limit}
    return out


def passed(checks: Mapping[str, dict]) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
