"""The benchmark's cells at a small scale on the CPU, for its tests.

A cell runs on as many devices as it asks for: a cell of one chip in the
test's own process, a cell of several in a child process given that many
virtual CPU devices, so that the test process keeps seeing one.  Either
way the run goes through ``run.run`` past the look for a chip, and comes
back as its result line and the server's counters.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import run, spec, tpch_gen

SF = 0.004
SEED = 2**31 + 5
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
#: a four-chip cell that ``BENCHMARK.json`` does not list (``add_sharded``)
SHARDED = "tpch-sf1-shard4.mix-serial"
#: seconds a child process may take for one run
CHILD_TIMEOUT = 300


def small(name: str) -> spec.Cell:
    """The cell at scale factor ``SF``; a memory budget keeps its share of
    lineitem, and chunks stay the largest power of two of which two fit."""
    cell = spec.cell(name)
    cfg = dict(cell.config, scale_factor=SF)
    sess = dict(cfg.get("session", {}))
    if "memory_budget" in sess:
        full = tpch_gen.rows(cell.config["scale_factor"])["lineitem"] * 40
        budget = tpch_gen.rows(SF)["lineitem"] * 40 * sess["memory_budget"] // full
        sess["memory_budget"] = budget
        sess["chunk_rows"] = 1 << ((budget // 80).bit_length() - 1)
    cell.config = dict(cfg, session=sess)
    return cell


def add_sharded(tmp_path, monkeypatch) -> dict:
    """``BENCHMARK.json`` with ``SHARDED`` added the way a later PR adds a
    four-chip cell, over a copy of the benchmark's files: ``tpch-sf1`` with
    lineitem and orders row-sharded on four chips, as a configuration file
    of its own, and the cell listed by every metric that lists mix-serial.
    ``spec`` finds both by name until ``monkeypatch`` is undone."""
    copy = tmp_path / "bench"
    shutil.copytree(spec.BENCH, copy, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    name = "tpch-sf1-shard4"
    cfg = dict(
        spec.config("tpch-sf1"), name=name, chips=4, session={"shards": 4},
        placement="lineitem and orders row-sharded over four chips, the other relations replicated",
    )
    (copy / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    bench = spec.benchmark()
    source = next(c for c in bench["configs"] if c["name"] == "tpch-sf1")
    bench["configs"].append(dict(source, name=name, file=f"bench/configs/{name}.json"))
    bench["workloads"].append({
        "name": SHARDED, "config": name, "traffic": "mix-serial", "chips": 4,
        "why": "mix-serial over four shards: repartitions, exchanges and the sharded ladder",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tpch-sf1.mix-serial" in m.get("workloads", ()):
            m["workloads"].append(SHARDED)
    monkeypatch.setattr(spec, "BENCH", copy)
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    return bench


def run_here(cell: spec.Cell, seconds: float, trace: bool, fault=None):
    """``run.run`` of ``cell`` at ``SEED`` on this process's devices, with
    ``fault`` (a function given a ``MonkeyPatch``) planted first; with
    ``trace``, a v5e's peaks stand in for the CPU's, which has none."""
    from repro.serve.query_server import QueryServer

    counters = []
    init = QueryServer.__init__

    def kept(self, *args, **kwargs):
        init(self, *args, **kwargs)
        counters.append(self.counters)

    peaks = spec.peaks
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(QueryServer, "__init__", kept)
        if trace:
            mp.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
        if fault is not None:
            fault(mp)
        res = run.run(cell, SEED, seconds, trace, jax.devices(), time.perf_counter())
    return res, dict(counters[-1])


_CHILD = """
import importlib, json, sys
from bench import spec
from bench.tests import cells

a = json.loads(sys.stdin.read())
fault = None
if a["fault"]:
    module, name = a["fault"].split(":")
    fault = getattr(importlib.import_module(module), name)
res, counters = cells.run_here(spec.Cell(**a["cell"]), a["seconds"], a["trace"], fault)
print(json.dumps([res, counters]))
"""


def run_cell(cell: spec.Cell, seconds: float = 1.0, trace: bool = False, fault=None):
    """(result line, server counters) of ``run_here`` on ``cell.chips``
    devices: in this process for one, else in a child process with that
    many virtual CPU devices.  A fault for the child is a module-level
    function, which it imports by name."""
    if cell.chips == 1:
        return run_here(cell, seconds, trace, fault)
    root = spec.ROOT
    flags = f"--xla_force_host_platform_device_count={cell.chips}"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} {flags}".strip(),
        PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]),
    )
    args = {
        "cell": dataclasses.asdict(cell), "seconds": seconds, "trace": trace,
        "fault": fault and f"{fault.__module__}:{fault.__name__}",
    }
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], input=json.dumps(args), cwd=root, env=env,
        capture_output=True, text=True, timeout=CHILD_TIMEOUT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    res, counters = json.loads(proc.stdout.strip().splitlines()[-1])
    return res, counters
