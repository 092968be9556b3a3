"""Compile checks for one TPU v5e chip, made without the chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``topologies.get_topology_desc``) rather than attached, and
raises what the chip's compiler would raise.  These tests hold
``kernels.ops.TPU_KERNELS`` — which ops run their Pallas kernel on a TPU and
which run XLA — to what the compiler accepts at real widths (64k-slot
dictionaries, 1M-row streams), and check that a whole query executable at
SF 1 fits the chip's HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.exec import engine as E
from repro.kernels import ops

V5E_HBM_BYTES = 16 * 10**9  # one v5e chip's HBM (Google Cloud, "TPU v5e")
ROWS = 1 << 20  # stream length of the kernel compiles
SLOTS = E.KERNEL_SLOTS  # resident dictionary width (64k slots)
LANES = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip; the persistent compile cache is off meanwhile
    (an entry compiled for an absent chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lookup_case(module, sharding):
    fn = functools.partial(module, interpret=False)
    return fn, (
        _spec(sharding, (SLOTS,), jnp.int32),
        _spec(sharding, (SLOTS, LANES), jnp.float32),
        _spec(sharding, (ROWS,), jnp.int32),
    )


def _fused_case(ds, sharding):
    """One fused region at real widths: a 1M-row stream probes a 64k-slot
    resident dictionary of family ``ds`` and aggregates into a hash
    terminal — the kernel ``exec.engine._kernel_pipeline`` dispatches."""
    from repro.dicts import registry
    from repro.kernels import fused_pipeline as fp

    mod = registry.get(ds)

    def region(bk, bv, q, g, w):
        t = mod.build(bk, bv, SLOTS)
        b = fp.resident_bundle(ds, t, t.vals, jnp.zeros((SLOTS, 0), jnp.int32))

        def row_fn(cols, live, lookups, scalars):
            pf, _, found = lookups["D"](cols["q"])
            return cols["g"], pf[:, :1] * cols["w"][:, None], live & found

        return fp.fused_pipeline(
            {"q": q, "g": g, "w": w}, jnp.ones((ROWS,), bool), {"D": b}, {},
            row_fn, ("dict", 1024, 1), interpret=False,
        )

    return region, (
        _spec(sharding, (SLOTS // 2,), jnp.int32),
        _spec(sharding, (SLOTS // 2, 1), jnp.float32),
        _spec(sharding, (ROWS,), jnp.int32),
        _spec(sharding, (ROWS,), jnp.int32),
        _spec(sharding, (ROWS,), jnp.float32),
    )


def _case(op, variant, sharding):
    from repro.kernels import flash_attention as fa
    from repro.kernels import hash_probe as hp
    from repro.kernels import merge_lookup as ml
    from repro.kernels import segment_reduce as sr
    from repro.kernels import sorted_lookup as sl

    if op == "fused_pipeline":
        return _fused_case(variant, sharding)
    if op in ("hash_probe", "sorted_lookup", "merge_lookup"):
        mod = {"hash_probe": hp.hash_probe, "sorted_lookup": sl.sorted_lookup,
               "merge_lookup": ml.merge_lookup}[op]
        return _lookup_case(mod, sharding)
    if op == "segment_reduce":
        fn = functools.partial(sr.segment_reduce, interpret=False)
        return fn, (
            _spec(sharding, (ROWS,), jnp.int32),
            _spec(sharding, (ROWS, LANES), jnp.float32),
        )
    assert op == "flash_attention", op
    causal, window = variant
    fn = functools.partial(
        fa.flash_attention, causal=causal, window=window, interpret=False
    )
    q = _spec(sharding, (1, 8, 2048, 128), jnp.bfloat16)
    kv = _spec(sharding, (1, 2, 2048, 128), jnp.bfloat16)  # GQA, 4 q per kv head
    return fn, (q, kv, kv)


# every kernel ops.py can select, with the variants the executor reaches
CASES = [
    ("fused_pipeline", "ht_linear"),
    ("fused_pipeline", "st_sorted"),
    ("hash_probe", None),
    ("sorted_lookup", None),
    ("merge_lookup", None),
    ("segment_reduce", None),
    ("flash_attention", (True, 0)),
    ("flash_attention", (True, 512)),
    ("flash_attention", (False, 0)),
]


def test_cases_cover_every_policy_entry():
    assert {op for op, _ in CASES} == set(ops.TPU_KERNELS)


@pytest.mark.parametrize("op,variant", CASES, ids=[f"{o}-{v}" for o, v in CASES])
def test_tpu_policy_matches_compiler(one_chip, op, variant):
    """An op the TPU policy runs as Pallas compiles for v5e; an op it routes
    to XLA is still refused — when that changes, the policy entry is stale
    and should be flipped to the kernel."""
    fn, args = _case(op, variant, one_chip)
    if ops.TPU_KERNELS[op]:
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
    else:
        with pytest.raises((NotImplementedError, ValueError)):
            jax.jit(fn).lower(*args).compile()


def test_tpu_policy_routes_refused_kernels_to_xla(monkeypatch):
    """On a TPU (steered here by the platform probe), the fused region and
    every refused op take their XLA implementation; forcing Pallas compiles
    the kernels instead of emulating them."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.fused_pipeline_policy() == (False, False)
    for op, use in ops.TPU_KERNELS.items():
        assert ops._use_pallas(op) is use, op
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert ops.fused_pipeline_policy() == (True, False)


@pytest.mark.parametrize("scale", [1, 10])
def test_q1_executable_fits_v5e_hbm(one_chip, scale):
    """q1's whole-query executable (the jitted ``cached_executable`` body)
    compiles for v5e at SF 1 and SF 10 shapes — 6M and 60M lineitem rows —
    and its argument, output and temporary buffers fit one chip's HBM next
    to the resident lineitem table."""
    import repro
    from repro.data import tpch

    db = tpch.generate(scale=0.002, seed=0).tables()
    session = repro.connect(db)
    shape = session.shape("q1")
    ex = shape.executable
    cols, masks = ex._db_arrays(db)
    params = ex.coerce_params(shape.query.bind_defaults({}))
    rows = {rel: t.nrows for rel, t in db.items()}
    rows["lineitem"] = 6_000_000 * scale

    def scaled(rel, a):
        return _spec(one_chip, (rows[rel],) + a.shape[1:], a.dtype)

    cols_s = {r: {c: scaled(r, a) for c, a in rc.items()} for r, rc in cols.items()}
    masks_s = {r: scaled(r, m) for r, m in masks.items()}
    params_s = {k: _spec(one_chip, jnp.shape(v), jnp.asarray(v).dtype)
                for k, v in params.items()}
    compiled = ex._fn.lower(cols_s, masks_s, params_s).compile()
    mem = compiled.memory_analysis()
    lineitem_bytes = sum(rows["lineitem"] * a.dtype.itemsize
                         for a in cols["lineitem"].values())
    assert mem.argument_size_in_bytes >= rows["lineitem"] * 4 * 7  # scanned columns
    total = (lineitem_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
