"""Dictionary runtime — the paper's Fig. 4 API re-derived for TPU execution.

CPU DBFlex plugs in pointer-based C++ containers; on a TPU every dictionary
operation is a *whole-batch* vector operation over fixed-capacity
struct-of-array state.  All backends implement:

    build(keys, vals, capacity, **hints)      -> table (a pytree)
    lookup(table, queries, **hints)           -> (vals[n, V], found[n])
    update_add(table, keys, vals, **hints)    -> table'
    items(table)                              -> (keys[C], vals[C, V], valid[C])
    size(table)                               -> scalar int32

Conventions
-----------
* keys are ``int32``; ``EMPTY`` (int32 min) and ``PAD`` (int32 max) are
  reserved sentinels (compound keys are packed upstream, ``data.table``).
* values are ``float32 [*, V]`` with static arity V ≥ 1; bag multiplicities
  are just a V=1 value column, exactly the paper's ``row -> multiplicity``.
* duplicate keys in a batch **aggregate** (sum), matching LLQL's ``+=``
  semantics — an insert is the paper's find-then-emplace.
* everything is jit-/vmap-/shard_map-compatible; capacities are static.

The generic round-based insertion in this module is shared by both hash
families: a probing scheme is just a function ``slot(keys, t)`` giving the
t-th probe position — linear probing and two-choice bucketized probing are
two instances (see ht_linear / ht_twochoice).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Plain Python ints: safe to close over inside Pallas kernels (no captured
# tracers), and weak-typed in jnp expressions.
EMPTY = -(2**31)  # hash-table empty slot
PAD = 2**31 - 1  # sorted-array tail padding

# Knuth multiplicative hashing constants (distinct streams).
_H1 = 2654435761
_H2 = 2246822519

# ---------------------------------------------------------------------------
# Semiring lane combines.  A dictionary value row is V lanes; each lane
# combines duplicate-key contributions under its own monoid ("sum" | "min" |
# "max" — identities 0 / +inf / -inf).  ``ops`` empty or None means all-sum,
# which takes the EXACT historical vectorized path (bitwise stability).
# ---------------------------------------------------------------------------

OP_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def all_sum(ops) -> bool:
    return not ops or all(o == "sum" for o in ops)


def lane_identity_row(ops, V: int, dtype=jnp.float32) -> jax.Array:
    """[V] per-lane combine identities (zeros when all-sum)."""
    if all_sum(ops):
        return jnp.zeros((V,), dtype)
    return jnp.asarray([OP_IDENTITY[o] for o in ops], dtype)


def combine_at(tv: jax.Array, idx: jax.Array, vs: jax.Array, ops) -> jax.Array:
    """Scatter-combine value rows into ``tv`` at ``idx`` (drop-mode), each
    lane under its own monoid (all-sum when ``ops`` is empty).  Lane by lane:
    each scatter's update is a 1-D ``[n]`` column, where a whole-row update
    would be a row-major ``[n, V]`` array that a TPU tiles (8, 128), padding
    V lanes to 128 in HBM.  Each element still lands in the same order."""
    for j, op in enumerate(ops or ("sum",) * vs.shape[1]):
        col = vs[:, j]
        if op == "sum":
            tv = tv.at[idx, j].add(col, mode="drop")
        elif op == "min":
            tv = tv.at[idx, j].min(col, mode="drop")
        else:
            tv = tv.at[idx, j].max(col, mode="drop")
    return tv


def neutralize_rows(vs: jax.Array, live: jax.Array, ops) -> jax.Array:
    """Replace dead rows with the per-lane combine identity (zeros when
    all-sum — the historical masking)."""
    if all_sum(ops):
        return jnp.where(live[:, None], vs, 0.0)
    ident = lane_identity_row(ops, vs.shape[1], vs.dtype)
    return jnp.where(live[:, None], vs, ident[None, :])


def finalize_dead(keys: jax.Array, vals: jax.Array, ops, sentinel) -> jax.Array:
    """Zero the value rows of unoccupied slots after an ops-aware build —
    min/max accumulation leaves ±inf identities there, and downstream
    consumers (items(), dict scans) expect dead rows to read as zeros."""
    if all_sum(ops):
        return vals
    return jnp.where((keys != sentinel)[:, None], vals, 0.0)


def check_ops_update(ops) -> None:
    """Incremental ``update_add`` after an ops-aware build is unsupported:
    the build zero-fills dead slots, so a later insert claiming one would
    combine against 0 instead of the lane identity.  All current update
    paths (cross-shard Exchange merges) are sum-only by construction."""
    if not all_sum(ops):
        raise NotImplementedError(
            "update_add on min/max semiring lanes is not supported"
        )


def _mix(x: jax.Array, mult: int) -> jax.Array:
    h = x.astype(jnp.uint32) * jnp.uint32(mult)
    h ^= h >> 15
    h *= jnp.uint32(2654435769)
    h ^= h >> 13
    return h


def hash1(keys: jax.Array, capacity: int) -> jax.Array:
    return (_mix(keys, _H1) & jnp.uint32(capacity - 1)).astype(jnp.int32)


def hash2(keys: jax.Array, capacity: int) -> jax.Array:
    return (_mix(keys, _H2) & jnp.uint32(capacity - 1)).astype(jnp.int32)


class HashTable(NamedTuple):
    """Open-addressing hash table (both probing families)."""

    keys: jax.Array  # [C] int32, EMPTY where unoccupied
    vals: jax.Array  # [C, V] float32
    max_t: jax.Array  # scalar int32: longest probe distance used at build

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


ProbeFn = Callable[[jax.Array, jax.Array], jax.Array]
# (keys[n], t scalar) -> slot[n]


# ---------------------------------------------------------------------------
# Generic round-based vectorized insertion
# ---------------------------------------------------------------------------


def generic_insert(
    table: HashTable,
    ks: jax.Array,
    vs: jax.Array,
    probe: ProbeFn,
    max_probes: int,
    valid: Optional[jax.Array] = None,
    ops: Optional[Tuple[str, ...]] = None,
) -> HashTable:
    """Insert/aggregate a batch.  Each round is one full-width vector step:

      1. gather the current slot's key for every pending element;
      2. elements whose key is already there scatter-add their value;
      3. elements facing EMPTY race to claim it (deterministic scatter-max
         arbitration); winners write key + value;
      4. after winners are written, losers re-check the slot (this catches
         duplicate keys that raced for the same empty slot);
      5. survivors advance to their next probe position.

    Rounds ≈ longest probe chain; every step is gather/scatter over the whole
    batch — the TPU-shaped replacement for per-element pointer chasing.
    """
    n = ks.shape[0]
    C = table.capacity
    if vs.ndim == 1:
        vs = vs[:, None]
    ids = jnp.arange(n, dtype=jnp.int32)

    def round_body(state):
        tk, tv, t, pending, max_t = state
        slot = probe(ks, t)
        cur = tk[slot]
        # (2) aggregate into existing key
        hit = pending & (cur == ks)
        # (3) claim empty slots — scatter-max arbitration on element id
        want = pending & (cur == EMPTY)
        claim = jnp.full((C,), -1, jnp.int32).at[
            jnp.where(want, slot, C)
        ].max(ids, mode="drop")
        won = want & (claim[slot] == ids)
        tk = tk.at[jnp.where(won, slot, C)].set(ks, mode="drop")
        # (4) losers re-check after winners wrote (duplicate-key race)
        cur2 = tk[slot]
        hit2 = pending & ~hit & ~won & (cur2 == ks)
        write = hit | won | hit2
        tv = combine_at(tv, jnp.where(write, slot, C), vs, ops)
        new_pending = pending & ~write
        max_t = jnp.where(jnp.any(write), jnp.maximum(max_t, t), max_t)
        return tk, tv, t + 1, new_pending, max_t

    def cond(state):
        _, _, t, pending, _ = state
        return jnp.any(pending) & (t < max_probes)

    pending0 = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    tk, tv, _, pending, max_t = lax.while_loop(
        cond,
        round_body,
        (table.keys, table.vals, jnp.int32(0), pending0, table.max_t),
    )
    # Overflow (load factor too high / max_probes exceeded) is a sizing bug in
    # the lowering; callers can assert via `hash_size(t) == n_distinct`.
    del pending
    return HashTable(tk, tv, max_t)


def generic_lookup(
    table: HashTable,
    qs: jax.Array,
    probe: ProbeFn,
    max_probes: int,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batch lookup: probe until key found or EMPTY reached (miss).  The probe
    bound is ``min(max_probes, build max_t + 1)`` — two-choice tables thus get
    their fast-miss property automatically."""
    n = qs.shape[0]

    def round_body(state):
        t, active, found_slot = state
        slot = probe(qs, t)
        cur = table.keys[slot]
        hit = active & (cur == qs)
        miss = active & (cur == EMPTY)
        found_slot = jnp.where(hit, slot, found_slot)
        active = active & ~hit & ~miss
        return t + 1, active, found_slot

    def cond(state):
        t, active, _ = state
        return jnp.any(active) & (t <= table.max_t) & (t < max_probes)

    _, _, found_slot = lax.while_loop(
        cond,
        round_body,
        (jnp.int32(0), jnp.ones((n,), bool), jnp.full((n,), -1, jnp.int32)),
    )
    found = found_slot >= 0
    if valid is not None:
        found = found & valid.astype(bool)
    vals = table.vals[jnp.where(found, found_slot, 0)]
    vals = jnp.where(found[:, None], vals, 0.0)
    return vals, found


def hash_items(table: HashTable) -> Tuple[jax.Array, jax.Array, jax.Array]:
    valid = table.keys != EMPTY
    return table.keys, table.vals, valid


def hash_size(table: HashTable) -> jax.Array:
    return jnp.sum(table.keys != EMPTY).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Sorted-array machinery shared by st_sorted / st_blocked
# ---------------------------------------------------------------------------


class SortedTable(NamedTuple):
    keys: jax.Array  # [C] int32 ascending, PAD tail
    vals: jax.Array  # [C, V] float32 (zeros on pad rows)
    n: jax.Array  # scalar int32 — number of live (unique) keys
    block_max: jax.Array  # [NB] int32 per-block max (st_blocked index); [0] dummy


def dedupe_sorted(
    ks: jax.Array,
    vs: jax.Array,
    capacity: int,
    ops: Optional[Tuple[str, ...]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Aggregate duplicate keys of a sorted-with-holes sequence; returns
    padded unique arrays.

    Contract: the non-PAD subsequence of ``ks`` is nondecreasing.  PAD rows
    may appear anywhere (tail padding after a sort, or in-place holes from a
    masked hinted build); each live key starts a new segment iff it differs
    from the previous *live* key — a running max over the live keys, exact
    because the live subsequence is sorted — so a hole inside an equal-key
    run cannot split the run into duplicate table entries."""
    n = ks.shape[0]
    if vs.ndim == 1:
        vs = vs[:, None]
    V = vs.shape[1]
    live = ks != PAD
    prev_live = jnp.concatenate(
        [
            jnp.full((1,), EMPTY, jnp.int32),
            lax.cummax(jnp.where(live, ks, EMPTY))[:-1],
        ]
    )
    head = live & (ks != prev_live)
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1  # [n] segment id per element
    seg = jnp.where(live, seg, capacity)  # route pads off-table
    uk = jnp.full((capacity,), PAD, jnp.int32).at[seg].min(
        jnp.where(live, ks, PAD), mode="drop"
    )
    if all_sum(ops):
        uv = jnp.zeros((capacity, V), vs.dtype).at[seg].add(
            jnp.where(live[:, None], vs, 0.0), mode="drop"
        )
    else:
        ident = lane_identity_row(ops, V, vs.dtype)
        uv0 = jnp.zeros((capacity, V), vs.dtype) + ident[None, :]
        uv = combine_at(uv0, seg, neutralize_rows(vs, live, ops), ops)
        uv = finalize_dead(uk, uv, ops, PAD)
    n_unique = jnp.sum(head).astype(jnp.int32)
    return uk, uv, n_unique


def build_sorted(
    ks: jax.Array,
    vs: jax.Array,
    capacity: int,
    *,
    assume_sorted: bool = False,
    block: int = 0,
    valid: Optional[jax.Array] = None,
    ops: Optional[Tuple[str, ...]] = None,
) -> SortedTable:
    """Sort (skipped when the input is known ordered — the paper's hinted
    insert / O(n) build), aggregate duplicates, pad to capacity.

    A ``valid`` mask does NOT force a re-sort: masked keys become PAD
    *holes* in place, and ``dedupe_sorted`` already segments on key change
    and routes PAD rows off-table, so a sorted-with-holes sequence dedupes
    exactly like its sorted compaction — same per-key contribution order,
    same sums.  ``assume_sorted`` therefore means "the live subsequence is
    nondecreasing", which masking preserves.  (Earlier revisions re-sorted
    under a mask; that silently threw away the paper's hinted-insert O(n)
    win on every filtered build — the dominant cost of sort-dictionary
    group-bys over selective scans.)"""
    if vs.ndim == 1:
        vs = vs[:, None]
    if valid is not None:
        ks = jnp.where(valid.astype(bool), ks, PAD)  # pads drop in dedupe
    if not assume_sorted:
        perm = jnp.argsort(ks)
        ks, vs = ks[perm], vs[perm]
    uk, uv, n = dedupe_sorted(ks, vs, capacity, ops)
    bm = _block_index(uk, block)
    return SortedTable(uk, uv, n, bm)


def _block_index(keys: jax.Array, block: int) -> jax.Array:
    if block <= 0:
        return jnp.full((1,), PAD, jnp.int32)
    C = keys.shape[0]
    nb = max(1, C // block)
    usable = nb * block
    return jnp.max(keys[:usable].reshape(nb, block), axis=1)


def sorted_lookup(
    table: SortedTable, qs: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Vectorized binary search (PAD tail keeps searchsorted in-range)."""
    idx = jnp.searchsorted(table.keys, qs, side="left")
    idx = jnp.minimum(idx, table.keys.shape[0] - 1)
    found = table.keys[idx] == qs
    vals = jnp.where(found[:, None], table.vals[idx], 0.0)
    return vals, found


def blocked_lookup(
    table: SortedTable, qs: jax.Array, block: int
) -> Tuple[jax.Array, jax.Array]:
    """Two-level search: tiny block-max index first (VMEM-resident on TPU),
    then a within-block search — the flattened B+-tree of DESIGN.md."""
    nb = table.block_max.shape[0]
    blk = jnp.searchsorted(table.block_max, qs, side="left")
    blk = jnp.minimum(blk, nb - 1)
    base = blk * block
    # within-block: branchless binary search for the count of keys < q,
    # log2(block) rounds of one [n] gather — an [n, block] gather of whole
    # block rows would hold n·block keys at once.  The count saturates at
    # block-1; a query above every key of its block misses either way.
    lt = jnp.zeros_like(qs)
    bit = block >> 1
    while bit:
        cand = lt + bit
        lt = jnp.where(table.keys[base + cand - 1] < qs, cand, lt)
        bit >>= 1
    idx = jnp.minimum(base + lt, table.keys.shape[0] - 1)
    found = table.keys[idx] == qs
    vals = jnp.where(found[:, None], table.vals[idx], 0.0)
    return vals, found


def merge_update_sorted(
    table: SortedTable,
    ks: jax.Array,
    vs: jax.Array,
    *,
    assume_sorted: bool = False,
    block: int = 0,
) -> SortedTable:
    """``update_add`` for sorted dictionaries: merge batch into table.

    Capacity is static; the lowering sizes tables so live + batch unique keys
    always fit (overflow keys would land on the PAD tail and be dropped)."""
    if vs.ndim == 1:
        vs = vs[:, None]
    cat_k = jnp.concatenate([table.keys, ks])
    cat_v = jnp.concatenate([table.vals, jnp.broadcast_to(vs, (*vs.shape,))])
    perm = jnp.argsort(cat_k)  # pads (PAD=max) sort to the tail
    uk, uv, n = dedupe_sorted(cat_k[perm], cat_v[perm], table.keys.shape[0])
    return SortedTable(uk, uv, n, _block_index(uk, block))


def sorted_items(table: SortedTable) -> Tuple[jax.Array, jax.Array, jax.Array]:
    valid = table.keys != PAD
    return table.keys, table.vals, valid


# ---------------------------------------------------------------------------
# Resident (in-kernel) execution machinery — shared by the per-family
# ``resident_*`` hooks (DESIGN.md §8).  Everything here must be kernel-safe:
# ``jnp.take`` gathers, compares, scatter ``.at[]`` updates, and statically
# bounded loops only — no ``searchsorted``, no dynamic shapes.
# ---------------------------------------------------------------------------


def lower_bound_pow2(keys: jax.Array, qs: jax.Array) -> jax.Array:
    """Vectorized branchless lower bound over a sorted power-of-two slab:
    returns ``min(count of keys < q, L-1)`` per query — the kernel-safe twin
    of ``jnp.searchsorted(keys, qs, side="left")`` with the same tail clamp
    ``sorted_lookup`` applies.  log2(L) rounds of one gather + compare."""
    L = keys.shape[0]
    assert L & (L - 1) == 0, "slab length must be a power of two"
    pos = jnp.zeros_like(qs)
    bit = L >> 1
    while bit:
        cand = pos + bit
        below = jnp.take(keys, cand - 1, axis=0) < qs
        pos = jnp.where(below, cand, pos)
        bit >>= 1
    return pos


def resident_insert_rounds(
    probe: ProbeFn,
    tk: jax.Array,
    tv: jax.Array,
    ks: jax.Array,
    vs: jax.Array,
    pending: jax.Array,
    max_probes: int,
    ops: Optional[Tuple[str, ...]] = None,
):
    """``generic_insert``'s round loop over kernel-local arrays: claim via
    scatter-max arbitration, aggregate duplicates, advance survivors — the
    ONE accumulate loop shared by the hash families' ``resident_accumulate``
    hooks and (through ``ht_linear``) the sort families' scratch
    accumulation.  Early-terminating, so the deep ``max_probes`` bound is
    free on healthy tables."""
    B = ks.shape[0]
    C = tk.shape[0]
    ids = lax.broadcasted_iota(jnp.int32, (B,), 0)

    def round_body(carry):
        t, tk, tv, pending = carry
        slot = probe(ks, t)
        cur = jnp.take(tk, slot, axis=0)
        hit = pending & (cur == ks)
        want = pending & (cur == EMPTY)
        claim = jnp.full((C,), -1, jnp.int32).at[
            jnp.where(want, slot, C)
        ].max(ids, mode="drop")
        won = want & (jnp.take(claim, slot, axis=0) == ids)
        tk = tk.at[jnp.where(won, slot, C)].set(ks, mode="drop")
        cur2 = jnp.take(tk, slot, axis=0)
        hit2 = pending & ~hit & ~won & (cur2 == ks)
        write = hit | won | hit2
        tv = combine_at(tv, jnp.where(write, slot, C), vs, ops)
        return t + 1, tk, tv, pending & ~write

    def cond(carry):
        t, _, _, pending = carry
        return jnp.any(pending) & (t < max_probes)

    _, tk, tv, _ = lax.while_loop(
        cond, round_body, (jnp.int32(0), tk, tv, pending)
    )
    return tk, tv


def slot_partition_plan(
    capacity: int, n_parts: int, overlap: int
) -> Tuple[jax.Array, jax.Array]:
    """Slot-range partitioning of a ``capacity``-slot table into ``n_parts``
    resident blocks of ``capacity//n_parts + overlap`` slots each, the
    overlap wrapping modulo capacity (hash probe chains run past a block's
    end by at most ``max_probes`` slots; sorted slabs use overlap 0).
    Returns ``(gather_idx [P, Lp], base [P])`` — ``gather_idx`` maps every
    resident-slab position to its global slot (keys AND payload slabs
    partition through the same map, so probed positions stay aligned), and
    ``base[p]`` is the global slot of block p's position 0."""
    assert capacity % n_parts == 0
    cp = capacity // n_parts
    lp = cp + min(overlap, capacity - cp) if overlap else cp
    base = jnp.arange(n_parts, dtype=jnp.int32) * cp
    idx = (base[:, None] + jnp.arange(lp, dtype=jnp.int32)[None, :]) % capacity
    return idx, base


def next_pow2(x: int) -> int:
    c = 1
    while c < x:
        c <<= 1
    return c


def default_capacity(n_distinct: int) -> int:
    """The static capacity rule — 2× slack over the estimated distinct
    count, 256-slot floor, power of two.  The ONE definition shared by the
    executor (``engine.capacity_for``) and the fusion cost model
    (``plan.fuse``'s VMEM estimates), so planning footprints cannot drift
    from the capacities the executor actually allocates."""
    return next_pow2(max(2 * int(n_distinct), 256))
