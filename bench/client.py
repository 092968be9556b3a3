"""The benchmark's closed-loop client over ``QueryServer``.

Each client has one request out at a time: it submits, and submits its next
request when the answer to the last one is back on the host.  The server is
driven from this one thread, so ``step()`` runs whenever a client waits.
Every call into the server sits inside a ``jax.profiler.TraceAnnotation``
(``bench.submit``, ``bench.step``), so a traced run can tell what the host
was doing while the device sat idle.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

from bench.stats import Completion
from bench.traffic import Clients


def failure(resp) -> str:
    """Why a response does not count as served, or "" when it does: an
    error (shed requests are errors too), a lower rung of the degradation
    ladder, or retries."""
    if not resp.ok:
        return f"error:{type(resp.error).__name__}"
    if resp.degraded:
        return f"degraded:{resp.degraded}"
    if resp.retries:
        return f"retries:{resp.retries}"
    return ""


def closed_loop(server, clients: Clients, t_end: float) -> Tuple[List[Completion], int]:
    """Serve until ``t_end``: no step starts after it.  Returns every
    completion, those of the step that straddles ``t_end`` too, and how many
    requests were still queued when the loop stopped."""
    out: dict = {}
    done: List[Completion] = []

    def send(c: int) -> None:
        qname, binding = clients.next(c)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = server.submit(qname, **binding)
        out[rid] = (c, qname, binding, time.perf_counter())

    for c in range(clients.n):
        send(c)
    while out and time.perf_counter() < t_end:
        t_step = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            responses = server.step()
        t = time.perf_counter()
        for r in responses:
            c, qname, binding, t_send = out.pop(r.rid)
            done.append(Completion(
                qname=qname, binding=binding, t_send=t_send, t_done=t,
                t_step=t_step, failed=failure(r), result=r.result,
            ))
            if t < t_end:
                send(c)
    return done, len(out)
