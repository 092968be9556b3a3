"""Jit'd kernel dispatch — the single entry point the rest of the system uses.

Selects between the Pallas kernels (TPU target; ``interpret=True`` emulation
on CPU) and the pure-jnp oracles in ``ref.py``.  Policy:

* on TPU: per op, from :data:`TPU_KERNELS` — the Pallas kernel, compiled,
  where the TPU compiler (Mosaic) lowers it, and the XLA implementation
  where it refuses (DESIGN.md §14);
* on CPU: the **ref** path by default (XLA-CPU is faster than interpret-mode
  emulation; interpret mode is for validation, which the tests do);
* ``REPRO_FORCE_PALLAS=1`` forces every kernel: interpret mode on CPU (the
  kernels' test path), compiled on TPU, where a kernel that does not lower
  fails loudly.

All functions keep the (vals, found)-style contracts of ``ref.py``.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import hash_probe as _hp
from . import merge_lookup as _ml
from . import ref
from . import segment_reduce as _sr


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: Which implementation each op takes on a TPU: True runs the Pallas kernel,
#: False the XLA implementation.  Each False names the refusal of the TPU
#: compiler (jax 0.9.0, v5e) at real widths — 64k-slot dictionaries, 1M-row
#: streams; ``tests/test_tpu_compile.py`` compiles every kernel for a
#: described v5e and fails when an entry no longer matches the compiler.
TPU_KERNELS = {
    # "Only 2D gather is supported": the families' resident_find probes are
    # 1-D jnp.take gathers over VMEM slabs (dicts/*.resident_find)
    "fused_pipeline": False,
    # "Only 2D gather is supported": the linear-probe rounds (the same
    # ht_linear.resident_find) gather from a 1-D VMEM key array
    "hash_probe": False,
    # "Only 2D gather is supported": the binary-search rounds' jnp.take
    "sorted_lookup": False,
    # "block shape ... divisible by 8 and 128": the (1, WINDOW) table-row
    # blocks; the binary search behind them is the same 1-D gather
    "merge_lookup": False,
    # "Unimplemented primitive in Pallas TPU lowering ...: cumsum": the
    # per-tile prefix sum (then lax.cummax and a 1-D row gather)
    "segment_reduce": False,
    "flash_attention": True,
}


def _use_pallas(op: str) -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return _on_tpu() and TPU_KERNELS[op]


def _interpret() -> bool:
    return not _on_tpu()


def fused_pipeline_policy() -> Tuple[bool, bool]:
    """(use_pallas, interpret) for the fused Pipeline-region kernel — the
    executor (``exec.engine._kernel_pipeline``) consults this before
    dispatching a region to ``kernels.fused_pipeline``; on CPU the pruned
    XLA path is both the oracle and the faster choice, and on TPU the
    kernel does not lower (:data:`TPU_KERNELS`)."""
    return _use_pallas("fused_pipeline"), _interpret()


def hash_probe(table_keys, table_vals, queries) -> Tuple[jax.Array, jax.Array]:
    if _use_pallas("hash_probe"):
        return _hp.hash_probe(
            table_keys, table_vals, queries, interpret=_interpret()
        )
    return ref.hash_probe(table_keys, table_vals, queries)


def sorted_lookup(table_keys, table_vals, queries) -> Tuple[jax.Array, jax.Array]:
    if _use_pallas("sorted_lookup"):
        from . import sorted_lookup as _sl

        return _sl.sorted_lookup(
            table_keys, table_vals, queries, interpret=_interpret()
        )
    return ref.sorted_lookup(table_keys, table_vals, queries)


def merge_lookup(table_keys, table_vals, queries) -> Tuple[jax.Array, jax.Array]:
    """Probes MUST be non-decreasing (the hinted-lookup contract)."""
    if _use_pallas("merge_lookup") and table_keys.shape[0] >= 2 * _ml.WINDOW:
        return _ml.merge_lookup(
            table_keys, table_vals, queries, interpret=_interpret()
        )
    return ref.merge_lookup(table_keys, table_vals, queries)


def segment_reduce(keys, vals) -> Tuple[jax.Array, jax.Array]:
    if _use_pallas("segment_reduce"):
        return _sr.segment_reduce(keys, vals, interpret=_interpret())
    return ref.segment_reduce(keys, vals)


def flash_attention(q, k, v, *, causal=True, window=0, kv_valid=None) -> jax.Array:
    if _use_pallas("flash_attention") and kv_valid is None:
        # dynamic kv_valid masks take the XLA path (the Pallas kernel has no
        # scalar-prefetch mask; only the serve path passes kv_valid).  The
        # fallback's contract — masking kv slots >= kv_valid is identical to
        # attending over k[:, :, :kv_valid] — is pinned against the kernel
        # path by tests/test_kernels.py::test_kv_valid_fallback_matches_kernel
        # so the two paths cannot silently diverge.
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, interpret=_interpret()
        )
    if k.shape[2] > 2048:
        # bounded-memory XLA flash formulation (dry-run / long-context path);
        # GQA-native — K/V are never materialized at H heads
        return ref.flash_attention_chunked(
            q, k, v, causal=causal, window=window, kv_valid=kv_valid
        )
    g = q.shape[1] // k.shape[1]
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    return ref.flash_attention(
        q, k, v, causal=causal, window=window, kv_valid=kv_valid
    )
