"""Reads what ``jax.profiler.ProfileData`` leaves out of a trace: each
device operation's JAX name stack.

A device plane's ``XLA Ops`` events point at event metadata, and the
metadata's ``tf_op`` stat holds the op's ``op_name`` (for example
``jit(_run)/jit(run)/dict.build.ht_linear/jit(<lambda>)/scatter-add``), so
the program's ``jax.named_scope``s name the device time they enclose.
``ProfileData`` exposes only an event's own stats, so this decodes the raw
``.xplane.pb`` from the protobuf wire format (XSpace -> XPlane -> XLine ->
XEvent, with XEventMetadata and XStatMetadata maps; field numbers of
``tsl/profiler/protobuf/xplane.proto``).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

from bench.trace_reduce import _nest

OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"/device:[A-Z]+:\d+$")


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields and fixed-width values."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, v


@dataclass
class DeviceOp:
    start_ns: float
    end_ns: float
    path: str  # the ``tf_op`` name stack, "" where the op carries none
    name: str = ""  # the HLO instruction's text
    stats = ()  # none of its own: ``trace_reduce.op_name`` reads ``name``

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    d = dict(fields(b))
    return d.get(1, 0), d.get(2, b"")


def _tf_op(meta: bytes, stat_names: Dict[int, str]) -> str:
    """The ``tf_op`` stat of one XEventMetadata, as a string or a reference
    to a stat-metadata name."""
    for f, stat in fields(meta):
        if f != 5:
            continue
        s = dict(fields(stat))
        if stat_names.get(s.get(1)) != "tf_op":
            continue
        if 5 in s:
            return s[5].decode()
        if 7 in s:
            return stat_names.get(s[7], "")
    return ""


def device_ops(raw: bytes) -> Dict[str, List[DeviceOp]]:
    """Device plane name -> the events of its ``XLA Ops`` line, each with
    its name stack."""
    out: Dict[str, List[DeviceOp]] = {}
    for f, plane in fields(raw):
        if f != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for g, v in fields(plane):
            if g == 2:
                name = v.decode()
            elif g == 3:
                lines.append(v)
            elif g == 4:
                k, m = _map_entry(v)
                metas[k] = m
            elif g == 5:
                k, m = _map_entry(v)
                stat_names[k] = dict(fields(m)).get(2, b"").decode()
        if not _DEVICE.match(name):
            continue
        paths = {k: _tf_op(m, stat_names) for k, m in metas.items()}
        texts = {k: dict(fields(m)).get(2, b"").decode() for k, m in metas.items()}
        ops: List[DeviceOp] = []
        for line in lines:
            ln = list(fields(line))
            if dict(ln).get(2, b"").decode() != OPS_LINE:
                continue
            t0 = dict(ln).get(3, 0)
            for g, ev in ln:
                if g != 4:
                    continue
                e = dict(fields(ev))
                start = t0 + e.get(2, 0) / 1e3
                k = e.get(1)
                ops.append(DeviceOp(start, start + e.get(3, 0) / 1e3, paths.get(k, ""), texts.get(k, "")))
        out[name] = ops
    return out


def scope_of(path: str, scopes: Tuple[str, ...]) -> Optional[str]:
    """The innermost of ``scopes`` in a name stack, or None.  A scope
    matches a component of the stack that is the scope or starts with it
    and a dot, also inside a transform's parentheses (``vmap(...)``)."""
    best = None
    for part in path.split("/"):
        part = re.sub(r"^(\w+\()+|\)+$", "", part)
        for s in scopes:
            if part == s or part.startswith(s + "."):
                best = s
    return best


def own_in_window(raw: bytes, window: Tuple[float, float]) -> List[Tuple[DeviceOp, float]]:
    """(op, own time in s divided by the number of chips) of every device op
    whose interval meets ``window`` (host-clock ns, as the trace's other
    planes).  Own time is an op's duration less that of the ops nested in
    it (a ``while`` holds its body's ops)."""
    lo, hi = window
    planes = device_ops(raw)
    return [
        (o, own / 1e9 / len(planes))
        for ops in planes.values()
        for o, own in _nest(ops)
        if o.end_ns > lo and o.start_ns < hi
    ]

