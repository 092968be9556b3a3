"""Pallas TPU kernel: data-centric pipeline fusion (DESIGN.md §7/§8).

One kernel executes a whole ``Pipeline`` region — the paper's data-centric
codegen story (rows flow scan → filter → probe → aggregate without
materializing intermediates) mapped onto the TPU grid:

* **fact tiles stream HBM→VMEM once per grid step through a manually
  double-buffered DMA** — while the kernel probes tile *i*, tile *i+1*'s
  copy is already in flight, so gather latency overlaps the next tile's DMA
  instead of serializing with it;
* **predicates evaluate to in-register masks** — no mask column ever
  round-trips through HBM;
* **probed dictionaries stay VMEM-resident across grid steps** in their own
  family layout: every registered dictionary family supplies
  ``resident_slabs``/``resident_find`` hooks (``dicts/*`` — linear probing,
  two-choice buckets, binary search, block-directory search), so the kernel
  is *dictionary-complete*: whatever Algorithm 1 picked executes fused.
  Join gathers ride *payload* slabs aligned to the family's slab positions,
  so a probe yields the needed build-side columns directly;
* **dictionaries too big for VMEM radix-partition instead of de-fusing**
  (``radix_route``): fact rows are routed by the partition id of their probe
  key into tile-aligned runs, and a scalar-prefetched per-tile partition
  index makes each grid step co-resident with exactly the one slab block
  those rows probe — capacity-unbounded fused execution;
* **partial aggregates accumulate into VMEM scratch** via the terminal
  family's ``resident_accumulate`` hook (hash families accumulate in their
  own layout; sort families accumulate in hash scratch and the executor
  finalizes through their ``build``), written back by the final grid step —
  or per partition, when the terminal's key is the partition key.

The region's row-level semantics arrive as ``row_fn`` — a traced callable
the executor assembles from the plan stages (``exec.engine._kernel_pipeline``)
— so this module stays a pure execution substrate: it owns tiling,
residency, routing, probing, and accumulation, nothing query- or
family-specific.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.dicts import base as dbase
from repro.dicts import ht_linear
from repro.dicts.ht_linear import MAX_PROBES  # the XLA builder's probe bound:
# tables arrive built by the dicts backends (chains up to MAX_PROBES), so the
# kernel must probe at least as deep or it would silently miss displaced
# keys.  Early termination makes the deep bound free on healthy tables.
from .decode import EncodedStream, decode_tile, words_per_tile
from .hash_probe import gather_slots  # the ONE miss-zeroing payload gather

ROW_BLOCK = 1024


class ResidentDict(NamedTuple):
    """One probed dictionary's VMEM-resident bundle.

    ``find(slabs, qs, base_slot)`` is the family hook (partially applied by
    the executor with capacity/max_probes); ``slabs`` are the key-side
    arrays from ``resident_slabs`` and ``fvals``/``ivals`` the payload slabs
    aligned to ``slabs[0]``'s positions (float and int32 lanes — integer
    build columns ride the int slab so gathered values stay exact past
    2^24).  When ``n_parts > 0`` every array is stacked ``[P, ...]`` (one
    leading partition axis, slabs from ``partition_slabs``) and ``cp`` is
    the global slot stride between blocks (``capacity // n_parts``)."""

    find: Callable
    slabs: Tuple[jax.Array, ...]
    fvals: jax.Array
    ivals: jax.Array
    n_parts: int = 0
    cp: int = 0


class RadixPlan(NamedTuple):
    """Routing of the fact stream for a radix-partitioned region: built by
    :func:`radix_route`, consumed by :func:`fused_pipeline`."""

    n_parts: int
    tile_part: jax.Array  # [T] partition id per fact tile (nondecreasing)
    visited: jax.Array  # [P] bool — partitions that own at least one tile
    part_terminal: bool = False  # terminal accumulator partitioned too


def resident_bundle(
    ds: str,
    table,
    fvals: jax.Array,
    ivals: jax.Array,
    *,
    max_probes: int = MAX_PROBES,
) -> ResidentDict:
    """Fully-resident bundle for a built dictionary: the family's slabs and
    its ``resident_find`` partially applied with the table capacity."""
    from repro.dicts import registry

    mod = registry.get(ds)
    slabs = mod.resident_slabs(table)
    find = functools.partial(
        mod.resident_find, capacity=slabs[0].shape[0], max_probes=max_probes
    )
    return ResidentDict(find, slabs, fvals, ivals)


def partitioned_bundle(
    ds: str,
    table,
    fvals: jax.Array,
    ivals: jax.Array,
    n_parts: int,
    *,
    max_probes: int = MAX_PROBES,
) -> ResidentDict:
    """Radix-partitioned bundle: stacked ``[P, ...]`` slab blocks from the
    family's ``partition_slabs``, payload slabs gathered through the same
    slot map so probed positions stay aligned."""
    from repro.dicts import registry

    mod = registry.get(ds)
    slabs, gidx, _ = mod.partition_slabs(table, n_parts)
    capacity = mod.resident_slabs(table)[0].shape[0]
    find = functools.partial(
        mod.resident_find, capacity=capacity, max_probes=max_probes
    )
    fv = jnp.take(fvals, gidx, axis=0)
    iv = jnp.take(ivals, gidx, axis=0)
    return ResidentDict(
        find, slabs, fv, iv, n_parts=n_parts, cp=capacity // n_parts
    )


def radix_route(
    cols: Dict[str, jax.Array],
    live: jax.Array,
    part: jax.Array,
    n_parts: int,
    block: int,
) -> Tuple[Dict[str, jax.Array], jax.Array, RadixPlan]:
    """Route fact rows into tile-aligned partition runs.

    Rows are stably ordered by partition id and scattered into a padded
    stream where every partition starts on a tile boundary, so each grid
    step's rows probe exactly one partition's resident slab.  The padded
    length is static: ``ceil(n/block) + n_parts`` tiles bound the alignment
    waste regardless of skew.  Returns the routed columns, the routed live
    mask (padding rows dead), and the :class:`RadixPlan`."""
    n = live.shape[0]
    order = jnp.argsort(part)  # stable: equal ids keep row order
    sp = part[order]
    counts = jnp.zeros((n_parts,), jnp.int32).at[part].add(1)
    tiles_per = (counts + block - 1) // block
    tile_start = jnp.cumsum(tiles_per) - tiles_per  # [P] first tile per part
    row_start = jnp.cumsum(counts) - counts  # [P] first sorted row per part
    pos = tile_start[sp] * block + jnp.arange(n, dtype=jnp.int32) - row_start[sp]

    n_tiles = n // block + ((n % block) > 0) + n_parts  # static bound
    n_pad = n_tiles * block
    routed = {
        name: jnp.zeros((n_pad,), a.dtype).at[pos].set(a[order])
        for name, a in cols.items()
    }
    live_r = jnp.zeros((n_pad,), bool).at[pos].set(live[order])
    # partition id per tile: filler tiles past the last busy one ride the
    # final partition (their rows are dead)
    t_ids = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_part = (
        jnp.sum(
            (tile_start[None, :] <= t_ids[:, None]).astype(jnp.int32), axis=1
        )
        - 1
    )
    tile_part = jnp.clip(tile_part, 0, n_parts - 1)
    return routed, live_r, RadixPlan(n_parts, tile_part, counts > 0)


def _kernel(
    part_ref,
    *refs,
    col_meta,  # ((name, dtype, elems_per_tile, enc), ...) — DMA streams;
    # enc None for raw columns, ("bitpack"|"for", bits, ref) or
    # ("dict", bits, 0) for encoded word streams; live mask stream last
    aux_meta,  # ((name, kind), ...) — pipelined decode aux inputs: "dict"
    # -> 1 ref (value slab), "rle" -> 2 refs (per-tile values, run ends)
    dict_meta,  # ((sym, find, n_slabs, n_parts, cp), ...) in dict order
    scalar_names,
    row_fn,
    out_spec,
    accumulate,
    n_tiles,
    block,
    part_terminal,
    lane_ops,
    has_init,
):
    nc = len(col_meta)
    nd = sum(2 + m[2] for m in dict_meta)
    na = sum(1 if k == "dict" else 2 for _, k in aux_meta)
    ni = 2 if has_init else 0
    ns = len(scalar_names)
    hbm_refs = refs[:nc]
    dict_refs = refs[nc : nc + nd]
    aux_refs = refs[nc + nd : nc + nd + na]
    init_refs = refs[nc + nd + na : nc + nd + na + ni]
    scalar_refs = refs[nc + nd + na + ni : nc + nd + na + ni + ns]
    # remaining refs: outputs | col buffers [2, epb] ×nc | col sems | acc
    rest = list(refs[nc + nd + na + ni + ns :])
    n_out = 2 if out_spec[0] == "dict" else 1
    out_refs = rest[:n_out]
    buf_refs = rest[n_out : n_out + nc]
    sem_ref = rest[n_out + nc]
    acc_refs = rest[n_out + nc + 1 :]

    i = pl.program_id(0)

    # -- double-buffered fact stream: start i+1's DMA before waiting on i ---
    # encoded word streams copy ``elems_per_tile`` < block int32 words per
    # step (the compression win crosses the HBM link too)
    def dma(c, slot, t):
        epb = col_meta[c][2]
        return pltpu.make_async_copy(
            hbm_refs[c].at[pl.ds(t * epb, epb)],
            buf_refs[c].at[slot],
            sem_ref.at[c, slot],
        )

    @pl.when(i == 0)
    def _warm():
        for c in range(nc):
            dma(c, 0, 0).start()

    @pl.when(i + 1 < n_tiles)
    def _prefetch():
        nxt = (i + 1) % 2
        for c in range(nc):
            dma(c, nxt, i + 1).start()

    cur = i % 2
    for c in range(nc):
        dma(c, cur, i).wait()

    aux_by_name = {}
    a = 0
    for name, kind in aux_meta:
        take = 1 if kind == "dict" else 2
        aux_by_name[name] = aux_refs[a : a + take]
        a += take

    cols = {}
    for c, (name, _dt, _epb, enc) in enumerate(col_meta[:-1]):
        tile = buf_refs[c][cur]
        if enc is None:
            cols[name] = tile
        elif enc[0] == "dict":  # in-register unpack + slab gather
            cols[name] = decode_tile(
                "dict", words_tile=tile,
                values=aux_by_name[name][0][...], bits=enc[1], block=block,
            )
        else:  # bitpack / frame-of-reference: shift+mask (+ ref add)
            cols[name] = decode_tile(
                enc[0], words_tile=tile, bits=enc[1], ref=enc[2],
                block=block,
            )
    for name, kind in aux_meta:
        if kind == "rle":  # no word stream at all: per-tile run tables
            vr, er = aux_by_name[name]
            cols[name] = decode_tile(
                "rle", values=vr[...][0], ends_row=er[...][0], block=block
            )
    live = buf_refs[nc - 1][cur] != 0

    # -- resident dictionaries: family find + payload gathers ---------------
    lookups: Dict[str, Callable] = {}
    r = 0
    for sym, find, n_slabs, n_parts, cp in dict_meta:
        slab_vals = tuple(dict_refs[r + k][...] for k in range(n_slabs))
        fv = dict_refs[r + n_slabs][...]
        iv = dict_refs[r + n_slabs + 1][...]
        r += n_slabs + 2
        if n_parts:  # one partition block resident: drop the leading axis
            slab_vals = tuple(s[0] for s in slab_vals)
            fv, iv = fv[0], iv[0]
            base_slot = part_ref[i] * cp
        else:
            base_slot = 0

        def lk(qs, _s=slab_vals, _f=fv, _i=iv, _b=base_slot, _find=find):
            slot, found = _find(_s, qs, base_slot=_b)
            return gather_slots(_f, slot, found), gather_slots(_i, slot, found), found

        lookups[sym] = lk
    scalars = {name: r_[0] for name, r_ in zip(scalar_names, scalar_refs)}

    keys, vals, live = row_fn(cols, live, lookups, scalars)

    # -- terminal accumulation ---------------------------------------------
    if out_spec[0] == "dict":
        out_keys_ref, out_vals_ref = out_refs
        tk_scr, tv_scr = acc_refs

        if part_terminal:
            fresh = (i == 0) | (part_ref[i] != part_ref[jnp.maximum(i - 1, 0)])
        else:
            fresh = i == 0

        @pl.when(fresh)
        def _init():
            if has_init:
                # streamed chunk fold: seed the accumulator with the carried
                # state instead of an empty table
                tk_scr[...] = init_refs[0][...]
                tv_scr[...] = init_refs[1][...]
            else:
                tk_scr[...] = jnp.full_like(tk_scr, dbase.EMPTY)
                # per-lane combine identities (zeros when every lane sums)
                tv_scr[...] = (
                    jnp.zeros_like(tv_scr)
                    + dbase.lane_identity_row(lane_ops, tv_scr.shape[1])[
                        None, :
                    ]
                )

        ks = jnp.where(live, keys, dbase.PAD)
        tk, tv = accumulate(tk_scr[...], tv_scr[...], ks, vals, live)
        tk_scr[...] = tk
        tv_scr[...] = tv

        if part_terminal:
            # written every step; the block index map flushes each partition
            # block when the grid moves to the next partition
            out_keys_ref[0] = tk_scr[...]
            out_vals_ref[0] = tv_scr[...]
        else:

            @pl.when(i == n_tiles - 1)
            def _finish():
                out_keys_ref[...] = tk_scr[...]
                out_vals_ref[...] = tv_scr[...]

    else:  # scalar reduce: running [1, V] per-lane combine in scratch
        (out_ref,) = out_refs
        (sum_scr,) = acc_refs
        ident = dbase.lane_identity_row(lane_ops, sum_scr.shape[1])

        @pl.when(i == 0)
        def _init_sum():
            sum_scr[...] = jnp.zeros_like(sum_scr) + ident[None, :]

        if dbase.all_sum(lane_ops):
            sum_scr[...] += jnp.sum(
                jnp.where(live[:, None], vals, 0.0), axis=0, keepdims=True
            )
        else:
            acc = sum_scr[...]
            masked = jnp.where(live[:, None], vals, ident[None, :])
            lanes = []
            for j, op in enumerate(lane_ops):
                col = masked[:, j : j + 1]  # [block, 1] — stays 2D for TPU
                if op == "sum":
                    lanes.append(
                        acc[:, j : j + 1]
                        + jnp.sum(col, axis=0, keepdims=True)
                    )
                elif op == "min":
                    lanes.append(
                        jnp.minimum(
                            acc[:, j : j + 1],
                            jnp.min(col, axis=0, keepdims=True),
                        )
                    )
                else:
                    lanes.append(
                        jnp.maximum(
                            acc[:, j : j + 1],
                            jnp.max(col, axis=0, keepdims=True),
                        )
                    )
            sum_scr[...] = jnp.concatenate(lanes, axis=1)

        @pl.when(i == n_tiles - 1)
        def _finish_sum():
            out_ref[...] = sum_scr[...]


def fused_pipeline(
    cols: Dict[str, jax.Array],  # [n] aligned streamed (pruned) columns
    live: jax.Array,  # [n] bool initial row mask
    dicts: Dict[str, ResidentDict],  # resident bundles (see ResidentDict)
    scalars: Dict[str, jax.Array],  # param name -> [1] runtime scalar
    row_fn: Callable,  # (cols, live, lookups, scalars) -> (keys, vals, live)
    out_spec: Tuple,  # ("dict", capacity, V) | ("sum", V)
    *,
    accumulate: Optional[Callable] = None,  # terminal family hook
    radix: Optional[RadixPlan] = None,
    block: int = ROW_BLOCK,
    interpret: bool = True,
    lane_ops: Optional[Tuple[str, ...]] = None,  # per-lane combine monoids
    encoded: Optional[Dict[str, EncodedStream]] = None,  # compressed streams
    init: Optional[Tuple[jax.Array, jax.Array]] = None,  # carried dict state
):
    """Run one fused region.  Returns ``(table_keys [C], table_vals [C, V])``
    for dictionary terminals (the ``accumulate`` hook's layout — duplicate
    keys aggregated; ``[P, Cp]``/``[P, Cp, V]`` when the terminal is
    partitioned) or ``sums [V]`` for scalar Reduce terminals.  With
    ``radix``, ``cols``/``live`` must already be tile-aligned by
    :func:`radix_route`.

    ``encoded`` maps column names (disjoint from ``cols``) to
    :class:`~repro.kernels.decode.EncodedStream` payloads: those columns
    cross HBM→VMEM *compressed* — bit-packed word windows ride the same
    double-buffered DMA at ``block//vpw`` words per tile, dictionary slabs
    and RLE run tables arrive as pipelined per-tile blocks — and decode
    in-register before ``row_fn`` sees them.  ``init=(keys, vals)`` seeds a
    (non-partitioned) dictionary terminal's accumulator with carried state,
    turning one call into one fold step of a chunked out-of-core stream.
    """
    n = live.shape[0]
    accumulate = accumulate or functools.partial(
        ht_linear.resident_accumulate, max_probes=MAX_PROBES, ops=lane_ops
    )
    encoded = dict(encoded or {})
    assert not (encoded and radix is not None), (
        "encoded streams are tile-positional — radix routing operates on "
        "decoded rows"
    )
    assert not set(encoded) & set(cols), "a column is either raw or encoded"
    col_names = tuple(sorted(cols))
    if radix is None:
        pad = -n % block
        cols_p = [jnp.pad(jnp.asarray(cols[c]), (0, pad)) for c in col_names]
        live_p = jnp.pad(live.astype(jnp.int32), (0, pad))
        n_tiles = (n + pad) // block
        tile_part = jnp.zeros((n_tiles,), jnp.int32)
        part_terminal = False
    else:
        assert n % block == 0, "radix_route emits tile-aligned streams"
        cols_p = [jnp.asarray(cols[c]) for c in col_names]
        live_p = live.astype(jnp.int32)
        n_tiles = n // block
        tile_part = radix.tile_part
        assert tile_part.shape[0] == n_tiles
        part_terminal = radix.part_terminal

    col_meta = tuple(
        (c, cols_p[k].dtype, block, None) for k, c in enumerate(col_names)
    )
    streams = list(cols_p)
    aux_meta = []
    aux_args = []
    aux_specs = []
    for name in sorted(encoded):
        es = encoded[name]
        assert es.block == block, (name, es.block, block)
        if es.kind in ("bitpack", "for", "dict"):
            wpt = words_per_tile(es.bits, block)
            assert es.words.shape[0] == n_tiles * wpt, (
                name, es.words.shape, n_tiles, wpt,
            )
            col_meta += (
                (name, es.words.dtype, wpt,
                 (es.kind, es.bits, es.ref)),
            )
            streams.append(es.words)
            if es.kind == "dict":
                aux_meta.append((name, "dict"))
                aux_args.append(es.values)
                aux_specs.append(
                    pl.BlockSpec(es.values.shape, lambda i, pr: (0,))
                )
        else:  # rle: no word stream — per-tile run tables only
            assert es.kind == "rle", es.kind
            assert es.values.shape[0] == n_tiles, (name, es.values.shape)
            R = es.values.shape[1]
            aux_meta.append((name, "rle"))
            aux_args += [es.values, es.ends]
            aux_specs += [
                pl.BlockSpec((1, R), lambda i, pr: (i, 0)),
                pl.BlockSpec((1, R), lambda i, pr: (i, 0)),
            ]
    col_meta += (("__live__", live_p.dtype, block, None),)
    streams.append(live_p)
    stream_specs = [
        pl.BlockSpec(memory_space=pl.ANY) for _ in streams
    ]

    dict_syms = tuple(sorted(dicts))
    dict_args = []
    dict_specs = []
    dict_meta = []
    for sym in dict_syms:
        d = dicts[sym]
        fv, iv = d.fvals, d.ivals
        if d.n_parts:
            P = d.n_parts
            lp = d.slabs[0].shape[1]
            # per-part block: leading axis selected by the prefetched tile id
            if fv.shape[-1] == 0:  # pallas rejects zero-width blocks
                fv = jnp.zeros((P, lp, 1), fv.dtype)
            if iv.shape[-1] == 0:
                iv = jnp.zeros((P, lp, 1), iv.dtype)
            for s in d.slabs:
                dict_specs.append(
                    pl.BlockSpec(
                        (1,) + s.shape[1:],
                        lambda i, pr, _nd=s.ndim: (pr[i],) + (0,) * (_nd - 1),
                    )
                )
            dict_specs += [
                pl.BlockSpec((1, lp, fv.shape[2]), lambda i, pr: (pr[i], 0, 0)),
                pl.BlockSpec((1, lp, iv.shape[2]), lambda i, pr: (pr[i], 0, 0)),
            ]
            dict_meta.append((sym, d.find, len(d.slabs), P, d.cp))
        else:
            if fv.shape[1] == 0:
                fv = jnp.zeros((fv.shape[0], 1), fv.dtype)
            if iv.shape[1] == 0:
                iv = jnp.zeros((iv.shape[0], 1), iv.dtype)
            for s in d.slabs:
                dict_specs.append(
                    pl.BlockSpec(s.shape, lambda i, pr, _nd=s.ndim: (0,) * _nd)
                )
            dict_specs += [
                pl.BlockSpec(fv.shape, lambda i, pr: (0, 0)),
                pl.BlockSpec(iv.shape, lambda i, pr: (0, 0)),
            ]
            dict_meta.append((sym, d.find, len(d.slabs), 0, 0))
        dict_args += [*d.slabs, fv, iv]

    scalar_names = tuple(sorted(scalars))
    scalar_args = [scalars[s] for s in scalar_names]
    scalar_specs = [
        pl.BlockSpec((1,), lambda i, pr: (0,)) for _ in scalar_names
    ]

    if out_spec[0] == "dict":
        _, capacity, V = out_spec
        assert capacity & (capacity - 1) == 0
        if part_terminal:
            P = radix.n_parts
            out_specs = [
                pl.BlockSpec((1, capacity), lambda i, pr: (pr[i], 0)),
                pl.BlockSpec((1, capacity, V), lambda i, pr: (pr[i], 0, 0)),
            ]
            out_shape = [
                jax.ShapeDtypeStruct((P, capacity), jnp.int32),
                jax.ShapeDtypeStruct((P, capacity, V), jnp.float32),
            ]
        else:
            out_specs = [
                pl.BlockSpec((capacity,), lambda i, pr: (0,)),
                pl.BlockSpec((capacity, V), lambda i, pr: (0, 0)),
            ]
            out_shape = [
                jax.ShapeDtypeStruct((capacity,), jnp.int32),
                jax.ShapeDtypeStruct((capacity, V), jnp.float32),
            ]
        acc_scratch = [
            pltpu.VMEM((capacity,), jnp.int32),
            pltpu.VMEM((capacity, V), jnp.float32),
        ]
    else:
        _, V = out_spec
        out_specs = [pl.BlockSpec((1, V), lambda i, pr: (0, 0))]
        out_shape = [jax.ShapeDtypeStruct((1, V), jnp.float32)]
        acc_scratch = [pltpu.VMEM((1, V), jnp.float32)]

    init_args = []
    init_specs = []
    if init is not None:
        assert out_spec[0] == "dict" and not part_terminal, (
            "carried state applies to non-partitioned dictionary terminals"
        )
        tk0, tv0 = init
        init_args = [tk0, tv0]
        init_specs = [
            pl.BlockSpec(tk0.shape, lambda i, pr: (0,)),
            pl.BlockSpec(tv0.shape, lambda i, pr: (0, 0)),
        ]

    nc = len(streams)
    scratch = (
        [
            pltpu.VMEM((2, col_meta[k][2]), s.dtype)
            for k, s in enumerate(streams)
        ]
        + [pltpu.SemaphoreType.DMA((nc, 2))]
        + acc_scratch
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=stream_specs + dict_specs + aux_specs + init_specs
        + scalar_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            col_meta=col_meta,
            aux_meta=tuple(aux_meta),
            dict_meta=tuple(dict_meta),
            scalar_names=scalar_names,
            row_fn=row_fn,
            out_spec=out_spec,
            accumulate=accumulate,
            n_tiles=n_tiles,
            block=block,
            part_terminal=part_terminal,
            lane_ops=lane_ops,
            has_init=init is not None,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(tile_part, *streams, *dict_args, *aux_args, *init_args, *scalar_args)
    if out_spec[0] == "dict":
        tk, tv = out
        if part_terminal:
            # unvisited partitions hold uninitialized memory: mask them out
            vis = radix.visited
            tk = jnp.where(vis[:, None], tk, dbase.EMPTY)
            tv = jnp.where(vis[:, None, None], tv, 0.0)
        return tk, tv
    return out[0][0]
