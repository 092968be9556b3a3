"""Arithmetic of the end-to-end metrics, on the host clock's readings.

A window closes while a step is running: the server answers a micro-batch
of requests at once, and no step starts after the close.  Each answer
carries a weight: 1 when its step ended within the window, and for the step
that straddles the close, the share of that step's time that lay within the
window.  Rates and percentiles count answers by weight, so a batch that ends
a moment before or after the close moves them by a moment's worth, not by
a whole batch.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Completion:
    """One request whose answer came back to its client."""

    qname: str
    binding: Dict[str, object]
    t_send: float  # host clock when the client submitted it
    t_done: float  # host clock when its answer was back on the host
    t_step: float  # host clock when the step that answered it began
    failed: str  # "" when served at the primary rung without retries
    result: Optional[dict]

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_send


def weighted(done: Sequence[Completion], t1: float) -> List[Tuple[Completion, float]]:
    """Each answer with its weight in a window that closes at ``t1``;
    answers of steps that began after ``t1`` are left out."""
    out = []
    for c in done:
        if c.t_done <= t1:
            out.append((c, 1.0))
        elif c.t_step < t1:
            out.append((c, (t1 - c.t_step) / (c.t_done - c.t_step)))
    return out


def qps(answers: Sequence[Tuple[Completion, float]], seconds: float) -> Optional[float]:
    """Correct answers per second of the window, by weight."""
    n = sum(w for c, w in answers if not c.failed)
    return n / seconds if n > 0 else None


def percentile(values: Sequence[float], weights: Sequence[float], q: float) -> float:
    """The ``q``-th percentile of ``values`` counted by ``weights``.  With
    unit weights it is numpy's linear interpolation: the sorted values sit
    at positions 0, 1, ..., n-1 and the percentile at (n-1)*q/100; a weight
    w spaces its value w from the next."""
    v = np.asarray(values, np.float64)
    w = np.asarray(weights, np.float64)
    keep = w > 0
    v, w = v[keep], w[keep]
    order = np.argsort(v, kind="stable")
    v, w = v[order], w[order]
    pos = np.cumsum(w) - w
    return float(np.interp(max(w.sum() - 1.0, 0.0) * q / 100.0, pos, v))


def latency_percentile(
    answers: Sequence[Tuple[Completion, float]], q: float, window_s: float
) -> Optional[float]:
    """The ``q``-th percentile of send-to-answer time over the window's
    answers.  A failed request counts as missing: its latency is taken as at
    least the whole window."""
    if not answers:
        return None
    lat = [max(c.latency_s, window_s) if c.failed else c.latency_s for c, _ in answers]
    return percentile(lat, [w for _, w in answers], q)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles`` with n=4)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
