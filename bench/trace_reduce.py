"""Reduces a JAX profiler trace (``.xplane.pb``) of one window to the
numbers the per-layer metrics and ``breakdown`` read.

* Device operations: the events of each device plane's ``XLA Ops`` line
  (on the CPU backend, the events that carry an ``hlo_op`` stat).  On a TPU
  an event's name is the HLO instruction's text; a ``while`` encloses the
  events of its body, so an operation's own time is its duration less that
  of the events nested in it.  Busy time is the union of the intervals
  within the window, averaged over the chips; the idle share is one minus
  busy over the window.
* Names: ``<module>/<instruction> = <result> <opcode>(<operand shapes>)``
  with layouts and operand names dropped, so a breakdown line says which
  query's program ran what.  A TPU trace does not say what a ``kCustom``
  fusion computes: scatters and gathers share that name.
* Window: the client loop's ``bench.window`` span, from the first request to
  the end of the last step (the one that straddles the run's close).
* Idle gaps: the intervals of the window in which no operation ran on the
  first device, each labelled by what the client loop's thread was doing at its
  middle: the outermost ``bench.*`` span and the innermost event under it.
* Host-to-device transfers: the runtime's ``TransferToDevice`` events on
  any host thread.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: an event that moves bytes from the host to a device
H2D = re.compile(r"TransferToDevice$")
SPAN = "bench."  # prefix of the client loop's span names
GAP_MIN_NS = 10_000  # gaps shorter than 10 us are dispatch jitter, not idleness

Interval = Tuple[float, float]


@dataclass
class Op:
    start: float
    end: float
    name: str
    self_ns: float  # duration less that of the operations nested in it


@dataclass
class Reduction:
    window: Interval
    ops: Dict[int, List[Op]]  # device -> operations within the window (clipped)
    client: List[Tuple[float, float, str]]  # events on the client loop's thread
    transfers: List[Interval]  # host->device transfers within the window
    n_devices: int = 1
    _busy: Dict[int, List[Interval]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device: int) -> List[Interval]:
        if device not in self._busy:
            self._busy[device] = union((o.start, o.end) for o in self.ops.get(device, ()))
        return self._busy[device]

    @property
    def busy_s(self) -> float:
        devs = range(self.n_devices)
        return sum(sum(b - a for a, b in self.busy(d)) for d in devs) / 1e9 / self.n_devices

    def h2d_s(self) -> Optional[float]:
        if not self.transfers:
            return None
        return sum(b - a for a, b in union(self.transfers)) / 1e9

    def gaps(self, device: int = 0) -> List[Interval]:
        out, at = [], self.window[0]
        for a, b in self.busy(device) + [(self.window[1], self.window[1])]:
            if a - at >= GAP_MIN_NS:
                out.append((at, a))
            at = max(at, b)
        return out

    def label(self, t: float) -> str:
        """What the client loop's thread was doing at ``t``."""
        around = [e for e in self.client if e[0] <= t <= e[1]]
        if not around:
            return "outside the client loop's spans"
        outer = [e for e in around if e[2].startswith(SPAN) and e[2] != SPAN + "window"]
        inner = min(around, key=lambda e: e[1] - e[0])
        head = min(outer, key=lambda e: e[0])[2] if outer else "bench.window"
        return head if inner[2] == head or inner[2].startswith(SPAN) else f"{head} > {inner[2]}"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        by_op: Dict[str, float] = defaultdict(float)
        for d in range(self.n_devices):
            for o in self.ops.get(d, ()):
                by_op[o.name] += o.self_ns / 1e9 / self.n_devices
        by_gap: Dict[str, float] = defaultdict(float)
        for a, b in self.gaps(0):
            by_gap[self.label((a + b) / 2)] += (b - a) / 1e9
        rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
        return {"device_ops": rank(by_op), "idle_gaps": rank(by_gap)}


def union(intervals) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _stat(event, key: str) -> Optional[str]:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return None


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r" %[\w.-]+")


def op_name(event, module: str = "") -> str:
    """``module/instruction = result opcode(operand shapes)``: the HLO text
    without layouts, operand names or called computations."""
    text = _stat(event, "hlo_op") or event.name
    text = _OPERAND.sub("", _LAYOUT.sub("", _LAYOUT.sub("", text.lstrip("%"))))
    text = re.sub(r", (condition|body|calls|to_apply)=\S+", "", text)
    text = text if len(text) <= 160 else text[:157] + "..."
    return f"{module}/{text}" if module else text


def _nest(events) -> List[Tuple[object, float]]:
    """(event, own time): each event's duration less that of the events
    that lie inside it on the same line."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    own = {id(e): e.duration_ns for e in evs}
    stack: list = []
    for e in evs:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= stack[-1].end_ns:
            own[id(stack[-1])] -= e.duration_ns
        stack.append(e)
    return [(e, own[id(e)]) for e in evs]


def _module_of(modules: List[Tuple[float, float, str]], t: float) -> str:
    for a, b, name in modules:
        if a <= t <= b:
            return name
    return ""


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:[A-Z]+:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def reduce(pd, n_devices: int = 1) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` to the window's numbers."""
    client_line, window = None, None
    host_planes = [p for p in pd.planes if p.name.startswith("/host:")]
    for plane in host_planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == SPAN + "window":
                    client_line, window = line, (e.start_ns, e.end_ns)
    if client_line is None:
        raise ValueError("no bench.window span in the trace")
    client = [(e.start_ns, e.end_ns, e.name) for e in client_line.events]
    lo, hi = window

    def clip(e) -> Optional[Interval]:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        return (a, b) if b > a else None

    ops: Dict[int, List[Op]] = defaultdict(list)
    transfers: List[Interval] = []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        lines = {line.name: line for line in plane.lines}
        modules = []
        if "XLA Modules" in lines:
            modules = sorted(
                (e.start_ns, e.end_ns, e.name) for e in lines["XLA Modules"].events
            )
        for line in plane.lines:
            if dev is not None and line.name == "XLA Ops":
                events = list(line.events)
            elif dev is None:
                for e in line.events:
                    iv = clip(e)
                    if iv is not None and H2D.search(e.name):
                        transfers.append(iv)
                events = [e for e in line.events if _stat(e, "hlo_op") is not None]
            else:
                continue
            for e, own in _nest(events):
                iv = clip(e)
                if iv is not None:
                    name = op_name(e, _module_of(modules, e.start_ns))
                    ops[dev or 0].append(Op(iv[0], iv[1], name, own))
    return Reduction(window, dict(ops), client, transfers, n_devices=n_devices)


def reduce_dir(path: str, n_devices: int = 1) -> Reduction:
    """Reduce the one ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {path}, found {len(files)}")
    return reduce(ProfileData.from_file(files[0]), n_devices=n_devices)
