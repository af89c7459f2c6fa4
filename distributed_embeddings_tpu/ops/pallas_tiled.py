"""Pallas TPU kernels: tiled one-hot-matmul sparse row ops (gather + update).

XLA:TPU's row machinery is descriptor-bound, not bandwidth-bound: the one
on-chip record of this code (one v5e chip, 2026-07-31, since deleted with
its toolchain) put scatter-add at ~55-106 ns/row, gather at ~22 ns/row and
segment_sum at ~45 ns/row against a ~0.1 ns/row bandwidth bound — and the
backward scatter + row-wise optimizer IS the train step. The kernels of
ops/pallas_scatter.py answer with per-row DMAs, which the chip's compiler
accepts only for f32 rows of width 128.

This module takes a different shape, chosen so that EVERY memory access is a
regular BlockSpec block stream, legal at every table width (the form of the
one-hot MXU kernel in ops/pallas_lookup.py). No `make_async_copy`, no
per-row DMA, no semaphores:

    sort ids once (XLA sort_key_val: measured 1.9 ns/key), then walk the
    table in row TILES and the sorted id stream in CHUNKS. Grid = the
    (tile, chunk) overlap pairs. Each step builds a [tile, chunk] one-hot
    on the VPU from an iota compare and contracts it with the chunk's
    gradient rows on the MXU:

        dense_tile_grad += onehot(ids_chunk - tile_base) @ grad_chunk

    Duplicate ids aggregate *inside the matmul* — no dedup pass, no
    segment_sum, no scatter anywhere. The optimizer (sgd/adagrad) applies
    as a dense elementwise VPU op on the tile when its last chunk lands,
    then the tile streams back to HBM. Gather is the transpose:

        rows_chunk += onehot(ids_chunk - tile_base)^T-form @ table_tile

    HBM traffic is block-sequential (the access pattern of a blocked
    matmul), so the cost model is bytes/bandwidth, not descriptors/row:
    ~visited tiles * tile bytes * 2(read+write) * arrays. XLA stores a
    table narrower than 128 lanes column-major, so the update walk of
    such a table runs over its transpose, rows on the lanes (see "update
    kernels"). Read on one v5e chip (PERF.md section 6, PR 33):
    `tiled_adagrad_rows` over Tiny V3's width-16 bucket (70.2M rows, its
    accumulator, 2.88M deduped slots) takes 42-48 ms a call with its
    walk (the kernel itself 35-40 ms in the cell: 18 GB at 450-510
    GB/s), where the two XLA scatter-adds and the re-read it stands in
    for take 609-673. `tiled_adagrad` over the same 2.88M slots with
    their duplicates is the same walk: PERF.md section 6, PR 37.

This is the TPU-native analogue of the reference backward kernel's
sort -> unique -> segment-reduce pipeline (reference:
cc/kernels/embedding_lookup_kernels.cu:603-775, cub radix sort at :645-661),
re-shaped for a machine whose fast paths are systolic matmul and sequential
DMA rather than warp-level shared-memory staging.

Semantics contract (shared by all entry points):
  * ids may contain duplicates in any order; invalid ids (id < 0 or
    id >= V) contribute nothing (XLA mode="drop" parity).
  * update kernels aggregate duplicate rows first (sum), matching the
    reference's unique-grad contract; adagrad uses the aggregated total
    (acc += total^2), identical to sparse_update.sparse_adagrad.
  * aggregation order differs from XLA's scatter order, so results match
    to f32 tolerance, not bit-exactly (tests pin ~1e-5 relative).

Status: interpret-mode tested on CPU (tests/test_pallas_tiled.py,
tests/test_pallas_fused.py); every entry point compiles for the chip at
widths 16 and 128 (tests/test_chip_compile.py) and ran compiled against its
XLA formulation at widths 8, 16 and 128 on a v5e chip (chip_smoke.py,
PR 22; the compiled checks again in PR 33, after the update walk was
re-oriented). One entry point is on a default path and timed in a cell:
`tiled_adagrad`, rows on the lanes, which `sparse_update.sparse_adagrad`
takes on a TPU for a narrow table's sort branch and hands the sorted
stream with its duplicates (ISSUE 37; both Tiny V3 cells. From PR 33 to
PR 36 the cells ran `tiled_adagrad_rows` behind `dedup_sum`: the same
kernel over the same number of slots). The rest of the dispatch lives in
sparse_update behind DET_SCATTER_IMPL=tiled (the other raw-stream kernels,
f32-tolerance parity) and DET_SCATTER_IMPL=pallas (the ISSUE 12 fused
strategy: deduped-row appliers + the weighted gather->combine forward,
bit-exact vs the XLA sort path — see the fused section below); none of
those has a step time in a cell.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_embeddings_tpu.obs.stages import staged
# shared rounding pin (see its docstring): the in-kernel optimizer
# arithmetic must round at exactly the seams the XLA sort path rounds at
# (scatter-operand materialization / the pinned adam products), or
# context-dependent backend FMA contraction breaks the fused strategy's
# bit-exactness. Every kernel's hp block carries a trailing RUNTIME 0.0
# (an SMEM load the compiler cannot prove constant) as the pin operand.
from distributed_embeddings_tpu.ops.sparse_update import (fp_round,
                                                          round_pin)


# Process-cached backend probe (ISSUE 12 satellite bugfix): the default
# interpret decision used to re-consult jax.default_backend() on every
# kernel call, so a backend flip mid-process (config update between the
# forward trace and the update trace) could run one step's phases in
# DIFFERENT modes. One probe per process; every entry point — the
# optimizer kernels, the row appliers AND tiled_gather_sorted — shares
# the cached verdict, so forward and update phases of one step can never
# diverge. An explicit interpret= argument always wins.
_BACKEND_INTERPRET: Optional[bool] = None


def _interpret_default(interpret: Optional[bool]) -> bool:
    global _BACKEND_INTERPRET
    if interpret is None:
        if _BACKEND_INTERPRET is None:
            _BACKEND_INTERPRET = jax.default_backend() != "tpu"
        return _BACKEND_INTERPRET
    return bool(interpret)


# defaults; wrappers shrink them for tiny shapes. tile bounds VMEM
# (tile * max(width,128) * 4B per buffered array), chunk bounds the one-hot
# slab and the MXU contraction depth.
_TILE = 1024     # table rows per tile (multiple of 8)
_CHUNK = 512     # sorted ids per chunk (multiple of 128)
# the rows-on-lanes update walk (see "update kernels"). Its two scalar-
# prefetch arrays hold one int32 a pair each and share the chip's 1 MiB of
# scalar memory (described-chip compiles, ISSUE 33: 79,819 pairs fit,
# 159,638 were refused). Its one-hots hold at most rows * chunk + n * tile
# elements a call (n_tiles + n_chunks pairs of tile * chunk), and a larger
# tile means fewer grid steps and block transfers: read on the chip at
# Tiny V3's bucket (70.2M rows, 2.88M ids, chunk 256; PERF.md section 6,
# PR 33) tile 1024 took 79 ms, 4096 47, 8192 46 and 16384 57.
_PAIRS_MAX = 130_000
_LANE_CHUNK = 256
_LANE_TILES = (1024, 2048, 4096, 8192)


def lane_blocks(rows: int, n: int):
    """(chunk, tile) of the rows-on-lanes update walk over a [rows, w]
    table and a stream of n ids: the largest tile of the ladder at which
    the stream's share of the one-hots (n * tile) stays under the table's
    (rows * chunk), and at least the smallest whose n_tiles + n_chunks
    pairs fit the scalar memory. None where no tile fits it."""
    n_chunks = -(-n // _LANE_CHUNK)
    fits = [t for t in _LANE_TILES if -(-rows // t) + n_chunks <= _PAIRS_MAX]
    if not fits:
        return None
    even = [t for t in fits if n * t <= rows * _LANE_CHUNK]
    return _LANE_CHUNK, (even[-1] if even else fits[0])


def _sort_ids(ids: jax.Array, contribs: Optional[jax.Array], vocab: int):
    """Sort ids ascending with invalid ids (neg / >= vocab) keyed to `vocab`
    so they land at the end; permute contribs alongside. Returns
    (sorted_keys [N] in [0, vocab], sorted_rows or None, perm)."""
    n = ids.shape[0]
    iota = lax.iota(jnp.int32, n)
    ids = ids.astype(jnp.int32)
    key = jnp.where((ids >= 0) & (ids < vocab), ids, jnp.int32(vocab))
    sid, perm = lax.sort_key_val(key, iota)
    rows = None if contribs is None else jnp.take(contribs, perm, axis=0)
    return sid, rows, perm


def _chunk_layout(sid: jax.Array, vocab: int, chunk: int, tile: int):
    """Pad the sorted id stream to whole chunks plus one all-filler chunk,
    and compute each real chunk's first/last table tile.

    Returns (kids [n_chunks+1, 1, chunk] int32 with -1 fillers — 3-D so a
             one-chunk block's trailing dims EQUAL the array's, the only
             tiling-legal form of a single-sublane block on the chip,
             pad_rows  total padded id count including the filler chunk,
             chunk_first [n_chunks], chunk_last [n_chunks], n_chunks).

    Filler handling: invalid ids carry sort key == vocab; for TILE MAPPING
    they are collapsed onto the last valid id so a half-filler boundary
    chunk does not claim to span to the end of the table (which would drag
    the pair walk across every trailing tile)."""
    n = sid.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    sid = jnp.concatenate([sid, jnp.full((pad,), vocab, jnp.int32)])
    num_valid = jnp.searchsorted(sid, vocab).astype(jnp.int32)
    last_valid = sid[jnp.maximum(num_valid - 1, 0)]
    last_valid = jnp.where(num_valid > 0, last_valid, 0)
    mapped = jnp.clip(jnp.where(sid < vocab, sid, last_valid), 0, vocab - 1)
    tiles = (mapped // tile).reshape(n_chunks, chunk)
    chunk_first = tiles[:, 0]
    chunk_last = tiles[:, -1]
    kids = jnp.where(sid < vocab, sid, -1)
    # one pure-filler chunk at index n_chunks: padded grid steps point here
    # and contribute exactly zero
    kids = jnp.concatenate(
        [kids, jnp.full((chunk,), -1, jnp.int32)]).reshape(n_chunks + 1, 1,
                                                           chunk)
    return kids, (n_chunks + 1) * chunk, chunk_first, chunk_last, n_chunks


def _chunk_spec(chunk: int) -> pl.BlockSpec:
    """One chunk of a [n_chunks+1, 1, chunk] per-id stream (ids, weights),
    selected by the walk's chunk index; the kernel sees it as [1, chunk]."""
    return pl.BlockSpec((None, 1, chunk), lambda g, tof, cof: (cof[g], 0, 0),
                        memory_space=pltpu.VMEM)


def _tile_major_pairs(sid, vocab: int, n_tiles: int, n_chunks: int,
                      chunk: int, tile: int):
    """Static-size (tile, chunk) pair walk, TILE-major, from where each
    tile's ids sit in the sorted stream `sid` (whole chunks, fillers
    >= vocab at the end): tile t holds slots [pos[t], pos[t+1]), so it
    pairs with the chunks pos[t] // chunk .. (pos[t+1] - 1) // chunk and
    with no other — a chunk whose ids jump over a tile is not paired with
    it. A tile with no id gets one pair with the FILLER chunk (index
    n_chunks), so every output tile block is still visited and written,
    and the kernels read `cof == n_chunks` as "nothing to place". Pairs
    are monotone in tile, so each tile's pairs are consecutive and the
    out block revisit/flush pattern is exact.

    Returns (tof [G], cof [G]) int32 with G = n_tiles + n_chunks static
    (a tile's span is one more than the chunk edges inside its ids);
    padded trailing pairs map to (last tile, filler chunk)."""
    g_count = n_tiles + n_chunks
    edges = jnp.minimum(lax.iota(jnp.int32, n_tiles + 1) * tile, vocab)
    pos = jnp.searchsorted(sid, edges, side="left").astype(jnp.int32)
    lo = pos[:-1] // chunk
    some = pos[1:] > pos[:-1]
    span = jnp.where(some, (pos[1:] - 1) // chunk - lo + 1, 1)
    pstart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(span)[:-1].astype(jnp.int32)])
    total = pstart[-1] + span[-1]
    g_iota = lax.iota(jnp.int32, g_count)
    tof = jnp.clip(
        jnp.searchsorted(pstart, g_iota, side="right").astype(jnp.int32) - 1,
        0, n_tiles - 1)
    cof = jnp.clip(jnp.take(lo, tof) + (g_iota - jnp.take(pstart, tof)),
                   0, n_chunks - 1)
    cof = jnp.where((g_iota < total) & jnp.take(some, tof), cof,
                    jnp.int32(n_chunks))
    tof = jnp.where(g_iota < total, tof, jnp.int32(n_tiles - 1))
    return tof, cof


def _chunk_major_pairs(chunk_first, chunk_last, n_tiles: int, n_chunks: int):
    """CHUNK-major pair walk for gather: for each chunk, the tiles it spans
    (>=1). Monotone in chunk => each output rows-chunk block's visits are
    consecutive. Padded trailing pairs point at the all-filler chunk
    (index n_chunks, ids all -1), so they contribute exactly zero and the
    kernel stays branch-free.

    Returns (tof [G], cof [G]) with G = n_chunks + n_tiles static. The
    filler chunk's padded pairs also flush its all-zero output block,
    which the wrapper slices off."""
    g_count = n_chunks + n_tiles
    c_iota = lax.iota(jnp.int32, n_chunks)
    span = jnp.maximum(1, chunk_last - chunk_first + 1)
    pstart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(span)[:-1].astype(jnp.int32)])
    total = pstart[-1] + span[-1]
    g_iota = lax.iota(jnp.int32, g_count)
    cof = jnp.clip(
        jnp.searchsorted(pstart, g_iota, side="right").astype(jnp.int32) - 1,
        0, n_chunks - 1)
    tof = jnp.clip(
        jnp.take(chunk_first, cof) + (g_iota - jnp.take(pstart, cof)),
        0, n_tiles - 1)
    # padded pairs -> filler chunk, reusing the last tile (already resident)
    cof = jnp.where(g_iota < total, cof, jnp.int32(n_chunks))
    tof = jnp.where(g_iota < total, tof, jnp.take(chunk_last,
                                                  jnp.int32(n_chunks - 1)))
    del c_iota
    return tof, cof


def _onehot(ids_row: jax.Array, tile_base, tile: int) -> jax.Array:
    """[tile, chunk] f32 one-hot: oh[r, j] = (ids_row[j] == tile_base + r).
    Invalid ids (-1 fillers, other-tile ids) match nothing."""
    local = (ids_row - tile_base)[None, :]
    r = lax.broadcasted_iota(jnp.int32, (tile, ids_row.shape[0]), 0)
    return (r == local).astype(jnp.float32)


# --------------------------------------------------------------------------
# update kernels (tile-major walk)
#
# Two orientations of one walk. A table of 128 lanes and more is stored
# row-major and its blocks are [tile, width]. A narrower one the chip
# stores COLUMN-major (`f32[V,16]{0,1:T(8,128)}`, which is byte for byte
# `f32[16,V]{1,0:T(8,128)}`): a [tile, 16] block of it makes the compiler
# copy the whole table to a row-major one with its 16 lanes padded to 128
# (36 GB for Tiny V3's 70.2M-row bucket: ISSUE 33), so the walk runs over
# `table.T` with blocks [width, tile], rows on the lanes, and the
# transposes on either side of the call are bitcasts.
# --------------------------------------------------------------------------
ROW_MAJOR_WIDTH = 128   # narrower tables are stored, and walked, rows on lanes


def _flags(tof_ref, g, g_count):
    t = tof_ref[g]
    prev_t = tof_ref[jnp.maximum(g - 1, 0)]
    nxt_t = tof_ref[jnp.minimum(g + 1, g_count - 1)]
    first = (g == 0) | (prev_t != t)
    last = (g == g_count - 1) | (nxt_t != t)
    return t, first, last


def _top16(x: jax.Array) -> jax.Array:
    """x with the low 16 bits of its f32 pattern cleared: a bfloat16 value
    held in f32, and ``x - _top16(x)`` is exact."""
    bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _pieces(x: jax.Array) -> jax.Array:
    """[width, chunk] f32 rows cut into three bfloat16 pieces, stacked
    [3 * width, chunk] f32: 8 + 8 + 8 bits of mantissa, all of an f32, so
    that their sum is x exactly."""
    hi = _top16(x)
    mid = _top16(x - hi)
    return jnp.concatenate([hi, mid, (x - hi) - mid], axis=0)


def _slab_sum(slabs: jax.Array, width: int) -> jax.Array:
    """The three pieces' slabs added back into [width, tile] f32 rows."""
    return (slabs[:width] + slabs[width:2 * width]) + slabs[2 * width:
                                                            3 * width]


def _placed(ids_ref, grads_ref, base, tile: int, lanes: bool) -> jax.Array:
    """One pair's contribution to its tile, in the tile's orientation:
    [tile, width], or [width, tile] with `lanes`.

    Rows on lanes, the one-hot [tile, chunk] is the large operand (chunk x
    tile a pair, ~10^10 elements a Tiny V3 step) and HIGHEST on f32 would
    pass it through the matrix unit six times. It is exact in bfloat16, so
    the gradient rows are cut into three bfloat16 pieces instead (8 + 8 +
    8 bits of mantissa: all of an f32), stacked as 3 * width rows of ONE
    bfloat16 matmul with f32 accumulation, and the three slabs added. Over
    a unique id stream (`tiled_*_rows`) each output lane receives one
    non-zero product a slab, so the total is the f32 value bit for bit:
    placement, not arithmetic. Over duplicates (`tiled_adagrad` on the
    sorted stream, what a cell runs) a lane's slab is the f32-accumulated
    sum of its run's exact bfloat16 pieces within the chunk, and the
    chunks of a run are added in f32 in `acc_ref`: an f32 sum of the
    run's rows in another order than `dedup_sum`'s tree, no longer a
    placement. (A non-finite gradient element spreads over its tile here
    as in the row-major form: 0 * inf.)"""
    ids = ids_ref[0, :]
    if not lanes:
        return lax.dot_general(_onehot(ids, base, tile),
                               grads_ref[:].astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    x = grads_ref[:].astype(jnp.float32)                 # [width, chunk]
    slabs = lax.dot_general(_pieces(x).astype(jnp.bfloat16),
                            _onehot(ids, base, tile).astype(jnp.bfloat16),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return _slab_sum(slabs, x.shape[0])


def _tile_total(tof_ref, cof_ref, ids_ref, grads_ref, acc_ref, *, tile: int,
                g_count: int, n_chunks: int, lanes: bool):
    """Add this pair's placed rows to the tile's running total in
    `acc_ref`; a pair with the filler chunk places nothing and skips the
    matmul. Returns whether this is the tile's last pair."""
    g = pl.program_id(0)
    t, first, last = _flags(tof_ref, g, g_count)

    @pl.when(first)
    def _():
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(cof_ref[g] != n_chunks)
    def _():
        acc_ref[:] = acc_ref[:] + _placed(ids_ref, grads_ref, t * tile, tile,
                                          lanes)

    return last


def _sgd_kernel(tof_ref, cof_ref, ids_ref, grads_ref, hp_ref, table_ref,
                out_ref, acc_ref, **walk):
    last = _tile_total(tof_ref, cof_ref, ids_ref, grads_ref, acc_ref, **walk)

    @pl.when(last)
    def _():
        lr = hp_ref[0, 0]
        zero = hp_ref[0, 1]         # rounding pin (see fp_round)
        out_ref[:] = (table_ref[:].astype(jnp.float32)
                      - fp_round(lr * acc_ref[:], zero)).astype(
                          out_ref.dtype)


def _adagrad_kernel(tof_ref, cof_ref, ids_ref, grads_ref, hp_ref, table_ref,
                    accum_ref, out_t_ref, out_a_ref, acc_ref, *, eps: float,
                    **walk):
    last = _tile_total(tof_ref, cof_ref, ids_ref, grads_ref, acc_ref, **walk)

    @pl.when(last)
    def _():
        lr = hp_ref[0, 0]
        zero = hp_ref[0, 1]         # rounding pin (see fp_round)
        gs = acc_ref[:]
        a_new = accum_ref[:].astype(jnp.float32) + fp_round(gs * gs, zero)
        out_a_ref[:] = a_new.astype(out_a_ref.dtype)
        # untouched rows: gs == 0 -> the accumulator as read, and a zero
        # delta whatever rsqrt makes of an accumulator of zero
        delta = jnp.where(gs != 0.0, fp_round(lr * gs * lax.rsqrt(a_new + eps),
                                              zero), 0.0)
        out_t_ref[:] = (table_ref[:].astype(jnp.float32)
                        - delta).astype(out_t_ref.dtype)


def _update_call(kernel, n_out, table, extra_tables, sid, rows, hp,
                 chunk: Optional[int], tile: Optional[int], interpret,
                 extra_scratch=(), lanes: Optional[bool] = None):
    """Shared pallas_call builder for the tile-major update kernels.
    extra_tables: additional [V, w] state arrays (adagrad accumulator,
    adam moments); extra_scratch: VMEM scratch beyond the grad
    accumulator (adam's touched-count column). `lanes`: walk the state
    arrays and the stream transposed, rows on the lanes (see above; None:
    wherever the chip stores the table so). chunk / tile of None: the
    orientation's own (`_walk_blocks`)."""
    vocab, width = table.shape
    n = sid.shape[0]
    if lanes is None:
        lanes = width < ROW_MAJOR_WIDTH
    chunk, tile = _walk_blocks(vocab, n, chunk, tile, lanes)
    n_chunks = -(-n // chunk)
    # whole chunks plus one all-filler chunk: pairs that place nothing
    # point there
    pad = (n_chunks + 1) * chunk - n
    sid = jnp.concatenate([sid, jnp.full((pad,), vocab, jnp.int32)])
    # 3-D so a one-chunk block's trailing dims EQUAL the array's, the only
    # tiling-legal form of a single-sublane block on the chip
    kids = jnp.where(sid < vocab, sid, -1).reshape(n_chunks + 1, 1, chunk)
    rows = jnp.concatenate(
        [rows.astype(jnp.float32), jnp.zeros((pad, width), jnp.float32)])
    n_tiles = -(-vocab // tile)
    tof, cof = _tile_major_pairs(sid, vocab, n_tiles, n_chunks, chunk, tile)
    g_count = n_tiles + n_chunks
    tables = [table, *extra_tables]
    if lanes:
        rows = rows.T
        tables = [t.T for t in tables]

    def block(size: int, walk: int) -> pl.BlockSpec:
        """`size` rows of a [rows, width] array (its transpose with
        `lanes`), chosen by scalar-prefetch array `walk` (0 tof, 1 cof)."""
        if lanes:
            return pl.BlockSpec((width, size),
                                lambda g, *of: (0, of[walk][g]),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((size, width), lambda g, *of: (of[walk][g], 0),
                            memory_space=pltpu.VMEM)

    out_specs = [block(tile, 0) for _ in range(n_out)]
    out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype)
                 for t in tables[:n_out]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g_count,),
        in_specs=[
            _chunk_spec(chunk),
            block(chunk, 1),
            pl.BlockSpec(hp.shape, lambda g, tof, cof: (0, 0),
                         memory_space=pltpu.SMEM),
        ] + [block(tile, 0) for _ in tables],
        out_specs=out_specs if n_out > 1 else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((width, tile) if lanes else (tile, width),
                       jnp.float32), *extra_scratch],
    )
    # operand indices include the 2 prefetch args: ids=2, rows=3, hp=4,
    # tables start at 5
    aliases = {5 + i: i for i in range(n_out)}
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile, g_count=g_count,
                          n_chunks=n_chunks, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=out_shape if n_out > 1 else out_shape[0],
        input_output_aliases=aliases,
        interpret=_interpret_default(interpret),
    )(tof, cof, kids, rows, hp, *tables)
    if not lanes:
        return out
    return [o.T for o in out] if n_out > 1 else out.T


def _shrink(vocab: int, n: int, chunk: int, tile: int):
    """Clamp block sizes for small problems (keep multiples of 8/128)."""
    tile = min(tile, max(8, -(-vocab // 8) * 8))
    chunk = min(chunk, max(128, -(-n // 128) * 128))
    return chunk, tile


def _walk_blocks(vocab: int, n: int, chunk: Optional[int],
                 tile: Optional[int], lanes: bool):
    """(chunk, tile) of an update walk: the caller's where given, else the
    orientation's defaults, clamped for small problems. Rows on lanes the
    tile is the block's lane extent: a multiple of 128."""
    if not lanes:
        return _shrink(vocab, n, chunk or _CHUNK, tile or _TILE)
    auto = lane_blocks(vocab, n) or (_LANE_CHUNK, _LANE_TILES[-1])
    return (min(chunk or auto[0], -(-n // 128) * 128),
            min(tile or auto[1], -(-vocab // 128) * 128))


def _hp_with_pin(ids, lr, *extra):
    """SMEM hyperparameter block [1, n]: lr, any extra scalars, then the
    RUNTIME 0.0 every kernel reads as its rounding pin (see fp_round).
    The pin derives from the id stream — lr is usually a trace-time
    constant, and a constant hp block would let the backend fold the pin
    away; ids are traced in every real flow, which keeps the SMEM slot
    opaque."""
    vals = [jnp.asarray(lr, jnp.float32).reshape(())]
    vals += [jnp.asarray(e, jnp.float32).reshape(()) for e in extra]
    vals.append(round_pin(ids).reshape(()))
    return jnp.stack(vals).reshape(1, len(vals))


@staged("dedup")
def _sorted_stream(ids, contribs, vocab: int, presorted):
    """(sid, permuted contrib rows) for an update kernel: fresh sort, or a
    caller-provided (sid, perm) — e.g. the forward lookup's sort reused by
    the backward over the SAME id stream (saves ~2 ns/key sort + the key
    build; XLA CSE does not merge the fwd/bwd sorts on its own, measured
    round 5 — see docs/perf_model.md 'Sort folding'). Stage `dedup`, as
    `dedup_sum`'s sort and permutation gather are: the walk and the kernel
    that follow stay the caller's `apply`."""
    if presorted is None:
        return _sort_ids(ids, contribs, vocab)[:2]
    sid, perm = presorted
    rows = None if contribs is None else jnp.take(contribs, perm, axis=0)
    return sid, rows


def tiled_sgd(table: jax.Array, ids: jax.Array, contribs: jax.Array, lr,
              chunk: Optional[int] = None, tile: Optional[int] = None,
              interpret: Optional[bool] = None,
              presorted=None) -> jax.Array:
    """table[ids] -= lr * contribs with duplicate aggregation in-kernel.
    Invalid ids dropped. lr may be traced (SMEM scalar). `presorted` may
    carry this id stream's (sid, perm) from a prior `_sort_ids`."""
    if ids.shape[0] == 0:
        return table
    sid, rows = _sorted_stream(ids, contribs, table.shape[0], presorted)
    hp = _hp_with_pin(sid, lr)
    return _update_call(_sgd_kernel, 1, table, [], sid, rows, hp,
                        chunk, tile, interpret)


def tiled_adagrad(table: jax.Array, accum: jax.Array, ids: jax.Array,
                  contribs: jax.Array, lr, eps: float = 1e-10,
                  chunk: Optional[int] = None, tile: Optional[int] = None,
                  interpret: Optional[bool] = None, presorted=None):
    """Fused row-wise adagrad with in-kernel duplicate aggregation:
        total[r]  = sum of contribs rows for r
        acc[r]   += total^2 ; table[r] -= lr * total * rsqrt(acc[r] + eps)
    Returns (table', accum'). Matches sparse_update.sparse_adagrad to f32
    tolerance. lr may be traced; eps is static."""
    if ids.shape[0] == 0:
        return table, accum
    sid, rows = _sorted_stream(ids, contribs, table.shape[0], presorted)
    hp = _hp_with_pin(sid, lr)
    out = _update_call(functools.partial(_adagrad_kernel, eps=eps), 2,
                       table, [accum], sid, rows, hp, chunk, tile, interpret)
    return out[0], out[1]


def _adam_kernel(tof_ref, cof_ref, ids_ref, grads_ref, hp_ref, table_ref,
                 mu_ref, nu_ref, out_t_ref, out_mu_ref, out_nu_ref, acc_ref,
                 cnt_ref, *, tile: int, g_count: int, n_chunks: int,
                 lanes: bool, b1: float, b2: float, eps: float):
    """Lazy row-wise adam (sparse_update.sparse_adam semantics): moments
    decay ONLY on touched rows, so the kernel also accumulates a per-row
    id count (one extra all-ones matmul column) to build the touched mask
    — a zero gradient SUM on a touched row must still decay its moments,
    so `sum != 0` is not a usable mask. Row-major at every width (the
    count is a column of the tile): no cell runs adam."""
    del n_chunks, lanes     # a filler pair's one-hot is zero: no skip here
    g = pl.program_id(0)
    t, first, last = _flags(tof_ref, g, g_count)
    oh = _onehot(ids_ref[0, :], t * tile, tile)
    gf = grads_ref[:].astype(jnp.float32)
    part = lax.dot_general(oh, gf, (((1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    cnt_part = jnp.sum(oh, axis=1, keepdims=True)        # [tile, 1]

    @pl.when(first)
    def _():
        acc_ref[:] = part
        cnt_ref[:] = cnt_part

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[:] = acc_ref[:] + part
        cnt_ref[:] = cnt_ref[:] + cnt_part

    @pl.when(last)
    def _():
        lr = hp_ref[0, 0]
        c1 = hp_ref[0, 1]        # 1 - b1^count (precomputed outside)
        c2 = hp_ref[0, 2]        # 1 - b2^count
        gs = acc_ref[:]
        touched = cnt_ref[:] > 0.0                        # [tile, 1]
        zero = hp_ref[0, 3]         # rounding pin (see fp_round)
        mu_old = mu_ref[:].astype(jnp.float32)
        nu_old = nu_ref[:].astype(jnp.float32)
        mu_new = jnp.where(touched, fp_round(b1 * mu_old, zero)
                           + fp_round((1.0 - b1) * gs, zero), mu_old)
        nu_new = jnp.where(
            touched, fp_round(b2 * nu_old, zero)
            + fp_round((1.0 - b2) * fp_round(gs * gs, zero), zero),
            nu_old)
        delta = jnp.where(
            touched,
            -lr * (mu_new / c1) / (jnp.sqrt(nu_new / c2) + eps), 0.0)
        out_mu_ref[:] = mu_new.astype(out_mu_ref.dtype)
        out_nu_ref[:] = nu_new.astype(out_nu_ref.dtype)
        out_t_ref[:] = (table_ref[:].astype(jnp.float32)
                        + delta).astype(out_t_ref.dtype)


def _adam_call(table, mu, nu, sid, rows, hp, chunk, tile, interpret, **hyper):
    """The adam walk: row-major at every width, so the block sizes are
    fixed here, where the count column's scratch takes the tile's."""
    chunk, tile = _walk_blocks(table.shape[0], sid.shape[0], chunk, tile,
                               lanes=False)
    return _update_call(
        functools.partial(_adam_kernel, **hyper), 3, table, [mu, nu], sid,
        rows, hp, chunk, tile, interpret, lanes=False,
        extra_scratch=[pltpu.VMEM((tile, 1), jnp.float32)])


def tiled_adam(table: jax.Array, mu: jax.Array, nu: jax.Array, count,
               ids: jax.Array, contribs: jax.Array, lr, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8, chunk: Optional[int] = None,
               tile: Optional[int] = None, interpret: Optional[bool] = None,
               presorted=None):
    """Fused lazy row-wise adam with in-kernel duplicate aggregation;
    matches sparse_update.sparse_adam (touched rows decay, bias correction
    by global step count) to f32 tolerance. Returns (table, mu, nu, count);
    `count` increments exactly as the XLA rule does (including for a
    statically-empty grad shard)."""
    count = count + 1
    if ids.shape[0] == 0:
        return table, mu, nu, count
    cf = count.astype(jnp.float32)
    c1 = 1.0 - lax.pow(jnp.float32(b1), cf)
    c2 = 1.0 - lax.pow(jnp.float32(b2), cf)
    sid, rows = _sorted_stream(ids, contribs, table.shape[0], presorted)
    hp = _hp_with_pin(sid, lr, c1, c2)
    out = _adam_call(table, mu, nu, sid, rows, hp, chunk, tile, interpret,
                     b1=b1, b2=b2, eps=eps)
    return out[0], out[1], out[2], count


# --------------------------------------------------------------------------
# dense aggregate (chunk-major walk, the whole target resident)
#
# `sparse_update._dense_sum` for a small column-major bucket on a TPU: the
# stream is walked in the order it arrives, no sort and no permutation, and
# the target never leaves fast memory. XLA's scatter-add into the same
# target is paid by the row (18 ns at widths 8 and 16: 45 ms a step for
# Tiny V3's width-8 bucket, 2.69M contributions into 60,160 rows, with its
# padded copy of the stream and its sort of the ids); here a chunk is
# summed into each tile of rows its ids can name by a one-hot product, so
# the price goes by how far a chunk's ids spread.
#
# The product is the update walk's (`_placed`, rows on lanes: three exact
# bfloat16 pieces, f32 accumulation) with its one-hot FACTORED. A row id is
# block * 128 + lane. The lane's one-hot `[128, chunk]` is built once a
# chunk, whatever tiles the chunk is paired with; a pair copies the pieces
# once for each of the tile's blocks, zeroed where a slot names another
# block (a select, not a compare against `tile` rows), and one matmul
# `[blocks * 32, chunk] x [chunk, 128]` places all of them: the result's
# sublane groups are the target's blocks as the chip stores them. Read on
# one v5e chip (PERF.md section 6, PR 41): 0.30 ps an element of the
# one-hot this stands for, where building and multiplying it whole costs
# 0.70.
# --------------------------------------------------------------------------
# Fast memory, of the 16 MiB a kernel may use by default. The target's block
# is [rows / 128, width + 8, 128] f32, rows on the lanes, the count's
# sublane group beneath the sums, and the pipeline keeps two buffers of it:
# 4 MiB each. A pair's operands take 4 MiB more: a chunk's pieces
# [3 w + 8, chunk] f32, the lane's one-hot [128, chunk] (f32, then
# bfloat16) and the tile's copies of the pieces [blocks (3 w + 8), chunk]
# (f32, then bfloat16), which grow with the width where the target's
# bound does not. The rest is the stream's blocks and the compiler's own.
# The pair's count is of what the operands could take, not a limit the
# compiler was seen at: on the chip the kernel compiled and ran at widths
# 32, 64 and 96 with tiles of 1,024 rows too, no faster than with these
# (PERF.md section 6, PR 41).
_DENSE_SUM_BYTES_MAX = 4 * 2 ** 20
_DENSE_PAIR_BYTES_MAX = 4 * 2 ** 20
# Slots a chunk, rows a tile at most, chunks a grid step. Read on the chip
# over Tiny V3's width-8 bucket (2,686,976 slots feature-major into 60,160
# rows; PERF.md section 6, PR 41): chunk 256 / tile 2048 10.7 ms,
# 512 / 2048 7.9, 1024 / 1024 7.0, 1024 / 2048 7.0, 1024 / 4096 8.4. A
# larger chunk means fewer pairs, each with a fixed cost, until a chunk
# holds more than one feature's slots.
_DENSE_CHUNK = 1024
_DENSE_TILE = 1024
_DENSE_SPAN = 2


def _dense_pair_bytes(width: int, tile: int) -> int:
    """Fast memory that one (chunk, tile) pair's operands take."""
    held = 3 * width + 8
    return (tile // 128 * held * 6 + held * 4 + 128 * 6) * _DENSE_CHUNK


def dense_sum_blocks(rows: int, width: int):
    """(chunk, tile) of the dense aggregate's walk over a [rows, width]
    target: the largest tile, a power of two blocks of 128 rows, whose
    pair fits `_DENSE_PAIR_BYTES_MAX` (1,024 rows at widths 8 and 16, 128
    at 96). None where no tile's does (widths over 104) or where the
    target with its count rows does not fit `_DENSE_SUM_BYTES_MAX`."""
    def fits(tile):
        return _dense_pair_bytes(width, tile) <= _DENSE_PAIR_BYTES_MAX

    tile = _DENSE_TILE
    while tile > 128 and not fits(tile):
        tile //= 2
    tile = min(tile, -(-rows // 128) * 128)
    n_tiles = -(-rows // tile)
    if (rows < 1 or not fits(tile)
            or n_tiles * (width + 8) * tile * 4 > _DENSE_SUM_BYTES_MAX):
        return None
    return _DENSE_CHUNK, tile


def dense_sum_pair_ns(chunk: int, tile: int, width: int) -> float:
    """What one (chunk, tile) pair of `dense_sum` costs on a v5e chip, in
    ns: a fixed part, and for each of the tile's blocks of 128 rows a
    part that goes by the chunk and by the rows a block holds of the
    product, 3 w + 8 (the pieces' copy and its share of the matmul).
    Fitted to 24 timed calls of `tools/tpu_dense_sum_sweep.py` (PERF.md
    section 6, PR 41): widths 8-104, tiles of 128-1,024 rows, 2,112 to
    153,809 pairs a call, feature-major and batch-major. It reads 496 ns
    at width 8 and 757 at 16 (tile 1,024) where 480-490 and 744-767 were
    timed, and is within 3% of every call but the feature-major ones at
    widths 96 and 104 (5-13% under: so few pairs that a chunk's own cost
    shows). That it goes by `held * chunk` is PR 41's earlier sweep's,
    chunks of 256-1,024 slots at width 8."""
    held = 3 * width + 8
    return 140.0 + tile / 128 * (1.0 + 1.36 * held * chunk / 1024)


def dense_sum_walk(ids: jax.Array, rows: int, chunk: int, tile: int):
    """The chunk-major walk of an id stream in the order it arrives:
    (kids [n_chunks, chunk] int32, -1 where an id names no row of the
    target (negative, >= rows, the last chunk's padding); lo, hi
    [n_chunks]: the first and last tile of `tile` rows that a chunk's
    valid ids name, hi < lo for a chunk with none; pairs: a chunk is
    paired with tiles lo..hi, `sum(hi - lo + 1)` pairs a call). The
    stream is padded to whole grid steps of `_DENSE_SPAN` chunks."""
    n = ids.shape[0]
    n_chunks = -(-n // chunk)
    span = min(_DENSE_SPAN, n_chunks)
    n_chunks = -(-n_chunks // span) * span
    ids = ids.astype(jnp.int32)
    kids = jnp.where((ids >= 0) & (ids < rows), ids, -1)
    kids = jnp.concatenate(
        [kids, jnp.full((n_chunks * chunk - n,), -1, jnp.int32)]
    ).reshape(n_chunks, chunk)
    hi = jnp.max(kids, axis=1)
    lo = jnp.min(jnp.where(kids >= 0, kids, jnp.int32(rows)), axis=1)
    some = hi >= 0
    lo = jnp.where(some, lo // tile, 0)
    hi = jnp.where(some, hi // tile, -1)
    return kids, lo, hi, jnp.sum(hi - lo + 1)


def _dense_sum_kernel(lo_ref, hi_ref, ids_ref, grads_ref, out_ref, *,
                      blocks: int, chunk: int, span: int):
    """One grid step: `span` chunks of the stream, each summed into the
    tiles lo..hi (of `blocks` blocks of 128 rows) of the resident target.
    Eight rows of ones ride beneath the pieces: their slab is the count
    of the chunk's ids that a lane names."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[:] = jnp.zeros(out_ref.shape, jnp.float32)

    width = grads_ref.shape[0]
    held = 3 * width + 8            # a block's rows of the product
    for s in range(span):
        c = step * span + s
        ids = ids_ref[s, 0, :]
        x = grads_ref[:, s * chunk:(s + 1) * chunk].astype(jnp.float32)
        pieces = jnp.concatenate(
            [_pieces(x), jnp.ones((8, chunk), jnp.float32)], axis=0)
        lane = _onehot(ids & 127, 0, 128).astype(jnp.bfloat16)
        block = (ids >> 7)[None, :]         # -1 where the id is dropped

        def pair(t, carry, pieces=pieces, lane=lane, block=block):
            first = t * blocks
            placed = jnp.concatenate(
                [jnp.where(block == first + b, pieces, 0.0)
                 for b in range(blocks)], axis=0).astype(jnp.bfloat16)
            slabs = lax.dot_general(placed, lane, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            for b in range(blocks):
                slab = slabs[b * held:(b + 1) * held]
                out_ref[first + b] = out_ref[first + b] + jnp.concatenate(
                    [_slab_sum(slab, width), slab[3 * width:]], axis=0)
            return carry

        lax.fori_loop(lo_ref[c], hi_ref[c] + 1, pair, 0)


def dense_sum(kids: jax.Array, lo: jax.Array, hi: jax.Array,
              contribs: jax.Array, rows: int, tile: int,
              interpret: Optional[bool] = None):
    """(g [rows, w] f32, counts [rows] f32): every row's summed
    contributions and how many it received, from `dense_sum_walk`'s
    (kids, lo, hi) and the stream's [n, w] contribution rows. Every
    duplicate is summed (three exact bfloat16 pieces, f32 accumulation:
    an f32 sum in another order than a scatter's), counts are exact, an
    id of -1 adds nothing. The stream is read once, in place
    (`contribs.T` is a bitcast of a column-major [n, w]), the target
    written once, and where w is 8 it comes out as the chip stores a
    column-major [rows, 8]."""
    n_chunks, chunk = kids.shape
    n, width = contribs.shape
    span = min(_DENSE_SPAN, n_chunks)       # the walk's whole grid steps
    steps = n_chunks // span
    grads = contribs.astype(jnp.float32).T
    if n_chunks * chunk != n:
        # a partial block's tail is undefined, and 0 * NaN is not 0
        grads = jnp.pad(grads, ((0, 0), (0, n_chunks * chunk - n)))
    n_blocks = -(-rows // tile) * (tile // 128)
    out = pl.pallas_call(
        functools.partial(_dense_sum_kernel, blocks=tile // 128,
                          chunk=chunk, span=span),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[
                # 3-D: a one-sublane block is tiling-legal only where its
                # trailing dims equal the array's (see `_update_call`)
                pl.BlockSpec((span, 1, chunk), lambda g, lo, hi: (g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((width, span * chunk),
                             lambda g, lo, hi: (0, g),
                             memory_space=pltpu.VMEM),
            ],
            # the same block at every step: it stays where it is until
            # the grid ends, and is written out once
            out_specs=pl.BlockSpec((n_blocks, width + 8, 128),
                                   lambda g, lo, hi: (0, 0, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks, width + 8, 128),
                                       jnp.float32),
        name="dense_sum",
        interpret=_interpret_default(interpret),
    )(lo, hi, kids.reshape(n_chunks, 1, chunk), grads)
    g = out[:, :width, :].transpose(0, 2, 1).reshape(n_blocks * 128, width)
    return g[:rows], out[:, width, :].reshape(n_blocks * 128)[:rows]


# --------------------------------------------------------------------------
# gather kernel (chunk-major walk)
# --------------------------------------------------------------------------
def _gather_kernel(tof_ref, cof_ref, ids_ref, *refs, tile: int,
                   g_count: int, vocab: int, weighted: bool = False):
    """Chunk-major gather: out[j] = table[ids[j]] — or, with `weighted`
    (the ISSUE 12 fused forward), w[j] * table[ids[j]]: the per-lane
    weight scales the one-hot COLUMN, so the weight multiply is free on
    the MXU and no separate [N, w] elementwise pass exists."""
    if weighted:
        w_ref, table_ref, out_ref = refs
    else:
        table_ref, out_ref = refs
    g = pl.program_id(0)
    c = cof_ref[g]
    prev_c = cof_ref[jnp.maximum(g - 1, 0)]
    first = (g == 0) | (prev_c != c)
    t = tof_ref[g]
    # contract the one-hot on the TILE axis. The last tile's
    # out-of-bounds rows must be zeroed before the contraction: their
    # buffer content is undefined (NaN in interpret mode) and
    # 0 * NaN = NaN would poison every output row of the chunk. (The
    # update kernels don't contract over tile rows, so undefined tail
    # rows stay confined there and are masked on write-back.)
    base = t * tile
    r_iota = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    valid_row = (base + r_iota) < vocab
    tbl = jnp.where(valid_row, table_ref[:].astype(jnp.float32), 0.0)
    oh = _onehot(ids_ref[0, :], base, tile)              # [tile, chunk]
    if weighted:
        oh = oh * w_ref[0, :][None, :]
    part = lax.dot_general(oh, tbl,
                           (((0,), (0,)), ((), ())),     # sum over tile rows
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    @pl.when(first)
    def _():
        out_ref[:] = part

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[:] = out_ref[:] + part


def _gather_call(table, sid, w_sorted, chunk: int, tile: int, interpret):
    """Shared pallas_call builder for the chunk-major gather walk; with
    `w_sorted` the weight stream rides a second chunk-indexed operand
    into the weighted kernel variant."""
    vocab, width = table.shape
    n = sid.shape[0]
    chunk, tile = _shrink(vocab, n, chunk, tile)
    kids, pad_rows, c_first, c_last, n_chunks = _chunk_layout(
        sid, vocab, chunk, tile)
    n_tiles = -(-vocab // tile)
    tof, cof = _chunk_major_pairs(c_first, c_last, n_tiles, n_chunks)
    g_count = n_chunks + n_tiles
    operands = [kids]
    in_specs = [_chunk_spec(chunk)]
    if w_sorted is not None:
        operands.append(jnp.concatenate(
            [w_sorted.astype(jnp.float32),
             jnp.zeros((pad_rows - n,), jnp.float32)]).reshape(
                 n_chunks + 1, 1, chunk))
        in_specs.append(_chunk_spec(chunk))
    operands.append(table)
    in_specs.append(pl.BlockSpec((tile, width),
                                 lambda g, tof, cof: (tof[g], 0),
                                 memory_space=pltpu.VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g_count,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((chunk, width),
                               lambda g, tof, cof: (cof[g], 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, g_count=g_count,
                          vocab=vocab, weighted=w_sorted is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            ((n_chunks + 1) * chunk, width), jnp.float32),
        interpret=_interpret_default(interpret),
    )(tof, cof, *operands)
    return out[:n]


def tiled_gather_sorted(table: jax.Array, sid: jax.Array,
                        chunk: int = _CHUNK, tile: int = _TILE,
                        interpret: Optional[bool] = None) -> jax.Array:
    """rows[k] = table[sid[k]] for ASCENDING-sorted sid (as produced by
    `_sort_ids`); invalid ids (neg / >= V) yield zero rows (callers mask or
    ignore them — note this differs from XLA's clamp-gather). Output dtype
    f32. The block walk reads each table tile once per spanning chunk
    (sequential HBM), replacing the ~22 ns/row descriptor-bound XLA gather
    for large sorted batches."""
    if sid.shape[0] == 0:
        return jnp.zeros((0, table.shape[1]), jnp.float32)
    return _gather_call(table, sid, None, chunk, tile, interpret)


def _sort_with_inv(flat_ids, vocab: int, presorted):
    """(sid, perm, inv) of a flat id stream under the canonical key: the
    caller-provided triple verbatim, or one fresh sort plus the
    scatter-free second-sort inversion — the ONE derivation the tiled
    and fused lookups (forward and custom-vjp fwd) all share."""
    if presorted is not None:
        return presorted
    sid, _, perm = _sort_ids(flat_ids, None, vocab)
    iota = lax.iota(jnp.int32, perm.shape[0])
    return sid, perm, lax.sort_key_val(perm, iota)[1]


def tiled_gather(table: jax.Array, ids: jax.Array,
                 chunk: int = _CHUNK, tile: int = _TILE,
                 interpret: Optional[bool] = None,
                 presorted=None) -> jax.Array:
    """rows[k] = table[ids[k]] for arbitrary-order ids (invalid ids yield
    zero rows): sort + tiled sorted gather + inverse permute. `presorted`
    reuses a prior (sid, perm) of this id stream."""
    if ids.shape[0] == 0:
        return jnp.zeros((0, table.shape[1]), jnp.float32)
    if presorted is not None and len(presorted) == 2:
        # a 2-tuple carries no inverse: derive it scatter-free (an
        # .at[perm].set would reintroduce the ~100 ns/row scatter
        # lowering this whole path exists to avoid)
        sid, perm = presorted
        iota = lax.iota(jnp.int32, perm.shape[0])
        inv = lax.sort_key_val(perm, iota)[1]
    else:
        sid, perm, inv = _sort_with_inv(ids, table.shape[0], presorted)
    rows = tiled_gather_sorted(table, sid, chunk, tile, interpret)
    return jnp.take(rows, inv, axis=0)


# --------------------------------------------------------------------------
# forward lookup-combine on the tiled gather (drop-in for the XLA
# gather+reduce in DistributedEmbedding._group_lookup)
# --------------------------------------------------------------------------
def _combine_prologue(params, ids, weights, combiner, presorted):
    """Shared lookup-wrapper prologue (tiled + fused): validate the
    combiner, default/normalize weights (mean pre-divides), clamp ids to
    XLA gather semantics, and clamp a caller presorted triple's keys the
    same way (positive OOB ids keep their clamp; NEGATIVE ids — already
    unspecified in the fused-bucket forward — read row V-1 on these
    paths instead of row 0)."""
    if combiner not in ("sum", "mean"):
        raise ValueError(f"Unsupported combiner {combiner}")
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    if combiner == "mean":
        denom = jnp.maximum(jnp.sum(weights, axis=1, keepdims=True), 1.0)
        weights = weights / denom
    ids = jnp.clip(ids, 0, params.shape[0] - 1)
    if presorted is not None:
        sid, perm, inv = presorted
        presorted = (jnp.minimum(sid, params.shape[0] - 1), perm, inv)
    return ids, weights, presorted


def _tiled_lookup_impl(params, ids, weights, interpret, presorted=None):
    b, k = ids.shape
    rows = tiled_gather(params, ids.reshape(-1), interpret=interpret,
                        presorted=presorted).reshape(b, k, -1)
    return jnp.einsum("bk,bkw->bw", weights.astype(jnp.float32), rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tiled_lookup(params, ids, weights, presorted, interpret):
    return _tiled_lookup_impl(params, ids, weights, interpret,
                              presorted=presorted)


def _tiled_lookup_fwd(params, ids, weights, presorted, interpret):
    # sort once: the backward reuses (sid, perm, inv) for BOTH its
    # aggregation and its dweights gather (the id stream is identical, and
    # XLA CSE does not merge fwd/bwd sorts — measured round 5). A caller-
    # provided `presorted` (the tapped path's TapResiduals artifact) folds
    # even the forward's own sort away.
    sid, perm, inv = _sort_with_inv(ids.reshape(-1), params.shape[0],
                                    presorted)
    return (_tiled_lookup_impl(params, ids, weights, interpret,
                               presorted=(sid, perm, inv)),
            (params, ids, weights, sid, perm, inv))


def _tiled_lookup_bwd(interpret, res, g):
    # Dense-table cotangent WITHOUT a scatter (ADVICE r4: the previous
    # zeros.at[ids].add here was the exact ~100 ns/row lowering this module
    # exists to avoid): aggregate duplicate rows on the MXU via the sgd
    # kernel at lr = -1 over a zero table, reusing the forward's sort.
    # Only the DENSE train path differentiates through the lookup; the
    # sparse tapped path extracts gradients at the taps and applies them
    # via the tiled update kernels directly.
    params, ids, weights, sid, perm, inv = res
    flat_ids = ids.reshape(-1)
    contrib = (weights[..., None].astype(jnp.float32)
               * g[:, None, :].astype(jnp.float32)).reshape(-1, g.shape[-1])
    dtable = tiled_sgd(jnp.zeros(params.shape, jnp.float32), flat_ids,
                       contrib, -1.0, interpret=interpret,
                       presorted=(sid, perm)).astype(params.dtype)
    rows = tiled_gather(params, flat_ids, interpret=interpret,
                        presorted=(sid, perm, inv)).reshape(
        ids.shape[0], ids.shape[1], -1).astype(g.dtype)
    dweights = jnp.einsum("bkw,bw->bk", rows, g).astype(weights.dtype)
    return dtable, None, dweights, None


_tiled_lookup.defvjp(_tiled_lookup_fwd, _tiled_lookup_bwd)


# --------------------------------------------------------------------------
# fused sparse path (ISSUE 12, DET_SCATTER_IMPL=pallas): deduped-row
# appliers + weighted gather->combine forward
#
# The tiled_* kernels above take the RAW contribution stream and
# aggregate duplicates inside the matmul — results match XLA to f32
# tolerance (aggregation order differs). The fused strategy instead
# consumes the EXACT `sparse_update.dedup_sum` aggregation (bit-for-bit
# the XLA sort path's (rep, sums): unique ascending row ids, per-row
# totals, OOB fillers >= sentinel) and applies the optimizer as ONE
# tile-walk RMW stream per bucket. With a unique id stream the one-hot
# matmul is an exact PLACEMENT — each tile row receives its single total
# plus exact zeros — and the in-tile optimizer arithmetic mirrors the
# XLA sort path expression for expression, so the fused update is
# BIT-exact against it (asserted in tests/test_pallas_fused.py). The
# rep stream is canonical-sorted by dedup_sum's contract, so no sort
# happens here: the forward's folded GroupSort is the only sort in the
# step. Dispatch + gates live in sparse_update behind
# DET_SCATTER_IMPL=pallas. (What sparse_adagrad's sort branch takes,
# unasked, for a narrow table on a TPU is the raw-stream `tiled_adagrad`
# above: `sparse_update._tile_stream`.)
# --------------------------------------------------------------------------
def tiled_sgd_rows(table: jax.Array, rep: jax.Array, sums: jax.Array, lr,
                   chunk: Optional[int] = None, tile: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """table[rep] -= lr * sums for a canonical-sorted UNIQUE `rep` stream
    (dedup_sum 'sort' output; fillers >= table rows are dropped).
    Bit-identical to ``table.at[rep].add(-lr * sums, mode="drop")`` —
    exact one-hot placement, one table-tile RMW stream. lr may be traced
    (SMEM scalar)."""
    if rep.shape[0] == 0:
        return table
    rep = rep.astype(jnp.int32)
    hp = _hp_with_pin(rep, lr)
    return _update_call(_sgd_kernel, 1, table, [], rep, sums, hp,
                        chunk, tile, interpret)


def tiled_adagrad_rows(table: jax.Array, accum: jax.Array, rep: jax.Array,
                       sums: jax.Array, lr, eps: float = 1e-10,
                       chunk: Optional[int] = None, tile: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Fused adagrad over deduped rows — one RMW stream reads and writes
    each touched table+accumulator tile once:
        acc[r]   += sums[s]^2
        table[r] -= lr * sums[s] * rsqrt(acc[r] + eps)
    Bit-identical to sparse_update.sparse_adagrad's 'sort' path (same
    placement, same expression grouping). Returns (table', accum')."""
    if rep.shape[0] == 0:
        return table, accum
    rep = rep.astype(jnp.int32)
    hp = _hp_with_pin(rep, lr)
    out = _update_call(functools.partial(_adagrad_kernel, eps=eps), 2,
                       table, [accum], rep, sums, hp, chunk, tile,
                       interpret)
    return out[0], out[1]


def tiled_adam_rows(table: jax.Array, mu: jax.Array, nu: jax.Array, count,
                    rep: jax.Array, sums: jax.Array, lr, b1: float = 0.9,
                    b2: float = 0.999, eps: float = 1e-8,
                    chunk: Optional[int] = None, tile: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused lazy adam over deduped rows (sparse_update.sparse_adam's
    touched-row semantics, bit-identical to its 'sort' path): the
    one-hot count column marks touched rows — a zero TOTAL on a touched
    row still decays its moments. Returns (table, mu, nu, count)."""
    count = count + 1
    if rep.shape[0] == 0:
        return table, mu, nu, count
    cf = count.astype(jnp.float32)
    # exact expression twin of sparse_adam's bias correction
    c1 = 1.0 - b1 ** cf
    c2 = 1.0 - b2 ** cf
    rep = rep.astype(jnp.int32)
    hp = _hp_with_pin(rep, lr, c1, c2)
    out = _adam_call(table, mu, nu, rep, sums, hp, chunk, tile, interpret,
                     b1=b1, b2=b2, eps=eps)
    return out[0], out[1], out[2], count


# --------------------------------------------------------------------------
# fused forward: weighted gather (chunk-major walk, weights folded into
# the one-hot so one MXU contraction yields COMBINE-ready rows)
# --------------------------------------------------------------------------
def tiled_gather_sorted_weighted(table: jax.Array, sid: jax.Array,
                                 w_sorted: jax.Array,
                                 chunk: int = _CHUNK, tile: int = _TILE,
                                 interpret: Optional[bool] = None
                                 ) -> jax.Array:
    """rows[k] = w_sorted[k] * table[sid[k]] for ASCENDING-sorted sid;
    invalid ids (>= V keys) yield zero rows regardless of weight. Same
    block walk as `tiled_gather_sorted` (one shared builder); the weight
    multiply rides the one-hot, not a second pass over [N, w]."""
    if sid.shape[0] == 0:
        return jnp.zeros((0, table.shape[1]), jnp.float32)
    return _gather_call(table, sid, w_sorted, chunk, tile, interpret)


def _fused_lookup_impl(params, ids, weights, interpret, presorted=None):
    b, k = ids.shape
    sid, perm, inv = _sort_with_inv(ids.reshape(-1), params.shape[0],
                                    presorted)
    w_sorted = jnp.take(weights.reshape(-1).astype(jnp.float32), perm,
                        axis=0)
    rows = tiled_gather_sorted_weighted(params, sid, w_sorted,
                                        interpret=interpret)
    # scatter-free unpermute (second-sort take, see tiled_gather), then
    # the combine degenerates to a plain hotness-axis sum — the weights
    # already rode the gather
    rows = jnp.take(rows, inv, axis=0).reshape(b, k, -1)
    return jnp.sum(rows, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_lookup(params, ids, weights, presorted, interpret):
    return _fused_lookup_impl(params, ids, weights, interpret,
                              presorted=presorted)


def _fused_lookup_fwd(params, ids, weights, presorted, interpret):
    # one sort serves forward gather, backward aggregation and the
    # dweights gather — identical structure to _tiled_lookup_fwd
    sid, perm, inv = _sort_with_inv(ids.reshape(-1), params.shape[0],
                                    presorted)
    return (_fused_lookup_impl(params, ids, weights, interpret,
                               presorted=(sid, perm, inv)),
            (params, ids, weights, sid, perm, inv))


# the backward is IDENTICAL to the tiled lookup's (same residual tuple):
# dense-table cotangent via the sgd kernel at lr = -1, scatter-free
_fused_lookup.defvjp(_fused_lookup_fwd, _tiled_lookup_bwd)


def fused_lookup_combine(params: jax.Array, ids: jax.Array,
                         weights: Optional[jax.Array] = None,
                         combiner: str = "sum",
                         interpret: Optional[bool] = None,
                         presorted=None) -> jax.Array:
    """Fused gather->combine forward (ISSUE 12): [V,W] table, [B,K] ids
    -> [B,W] in ONE weighted-gather kernel pass + a scatter-free
    unpermute + a plain hotness sum. Same contract as
    `tiled_embedding_lookup` (weights carry 0.0 in padded slots; mean
    pre-normalizes; positive OOB ids clamp like the XLA gather;
    differentiable in params and weights, scatter-free on the dense
    path). `presorted`: the canonical (sid, perm, inv) of the flattened
    id stream — the tapped forward's residual sort folds the fused
    forward's own sort away. Dispatch: DET_LOOKUP_PATH=fused in
    `dist_model_parallel._group_lookup`."""
    ids, weights, presorted = _combine_prologue(params, ids, weights,
                                                combiner, presorted)
    return _fused_lookup(params, ids, weights, presorted,
                         interpret).astype(params.dtype)


def tiled_embedding_lookup(params: jax.Array, ids: jax.Array,
                           weights: Optional[jax.Array] = None,
                           combiner: str = "sum",
                           interpret: Optional[bool] = None,
                           presorted=None) -> jax.Array:
    """Padded multi-hot lookup over the tiled gather: [V,W] table, [B,K]
    ids -> [B,W]. Same contract as pallas_lookup.fused_embedding_lookup
    (weights carry 0.0 in padded slots; mean pre-normalizes; OOB ids
    clamped to match XLA gather semantics). Differentiable in params and
    weights.

    `presorted`: optional (sid, perm, inv) of the FLATTENED id stream under
    the canonical key (embedding_ops.canonical_id_sort) — typically the
    tapped forward's residual sort. sid is clamped to V-1 here, so positive
    OOB ids keep their XLA clamp semantics; NEGATIVE ids (already
    unspecified in the fused-bucket forward) read row V-1 instead of row 0
    on this path."""
    ids, weights, presorted = _combine_prologue(params, ids, weights,
                                                combiner, presorted)
    return _tiled_lookup(params, ids, weights, presorted,
                         interpret).astype(params.dtype)
