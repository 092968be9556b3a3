"""What one measured window leaves for the per-layer metric readers."""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List, Optional

from bench.stats import Completion


@dataclass
class Window:
    completions: List[Completion]  # answers of the steps that began in the window
    t0: float  # host clock at the window's start
    t_last: float  # host clock when the last of those steps ended
    counters_before: Dict[str, int]  # QueryServer counters at t0
    counters_after: Dict[str, int]  # ... at t_last
    jax_traces: int  # functions JAX traced within the window
    scale_factor: float
    queries: Dict[str, ModuleType]
    peaks: Dict[str, object]
    trace: Optional[object] = None  # trace_reduce.Reduction of a traced run

    def counter_delta(self, name: str) -> int:
        return self.counters_after.get(name, 0) - self.counters_before.get(name, 0)
