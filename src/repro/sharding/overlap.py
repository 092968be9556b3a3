"""Compute/communication overlap helpers (DESIGN.md §4).

``ring_allgather_matmul``: computes ``all_gather(x, axis) @ w`` as a ring —
each step matmuls the chunk already in hand while ``collective_permute``
moves the next chunk around the ring, hiding (steps−1)/steps of the gather
latency behind the MXU.  This is the standard TP-overlap primitive used
where a column-parallel layer consumes row-sharded activations.

Numerically validated against the unoverlapped form on a multi-device mesh
(tests/test_distributed.py); on the dry-run meshes it lowers to a
collective-permute chain the scheduler can overlap, replacing a blocking
all-gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def ring_allgather_matmul(x_local: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    """x_local: this shard's [m_loc, K] rows of a row-sharded X; w: [K, N]
    local weight.  Returns all_gather(X) @ w = [m_loc * n_shards, N], with
    the gather pipelined against the matmuls."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    m_loc = x_local.shape[0]
    out = jnp.zeros((n * m_loc, w.shape[1]), w.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        out, chunk = carry
        # the chunk currently held was produced by shard (idx - i) mod n
        src = jnp.mod(idx - i, n)
        y = chunk @ w
        out = lax.dynamic_update_slice(out, y.astype(out.dtype), (src * m_loc, 0))
        chunk = lax.ppermute(chunk, axis, perm)  # overlaps with next matmul
        return out, chunk

    out, _ = lax.fori_loop(0, n, body, (out, x_local))
    return out


def allgather_matmul_reference(x_local: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    xg = lax.all_gather(x_local, axis, axis=0, tiled=True)
    return xg @ w
