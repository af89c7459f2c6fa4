"""Pallas TPU kernel: row scatter-add with sorted-unique ids (RMW stream).

THE bottleneck of embedding training on this hardware is XLA:TPU's scatter
lowering: the one on-chip record of this code (2026-07-31, see
ops/pallas_tiled.py) put it at ~100-280 ns per scattered row against a
~0.1 ns/row bandwidth bound, and every backward + row-wise optimizer
update funnels through it. The reference hits the same
op class with cub sort + a segment-reduce reusing its forward kernel
(reference: cc/kernels/embedding_lookup_kernels.cu:603-775); the TPU answer
is explicit DMA: after `dedup_sum` the update rows are UNIQUE, so a kernel
can stream read-modify-write row DMAs with no conflict hazard and no
atomics. Per grid step (one id tile, an SMEM block):

    start + wait row reads of the tile        (tile_b copies in flight)
    add the delta block                       (VPU)
    start + wait row writes of the tile

Tiles themselves overlap through the grid pipeline (the delta blocks of
step i+1 stream in while step i runs); read/write overlap WITHIN a tile is
not attempted: this is the minimal correct RMW stream.

OOB ids (the dedup filler tail, id >= V) issue no DMA at all — reads and
writes are predicated per row, so no dump row, no table copy, and the
table rides input_output_aliasing untouched except for the rows actually
updated.

Status: interpret-mode correct (tests/test_pallas_scatter.py). On the chip
a single-row DMA can address only float32 rows of width 128
(`pallas_lookup.check_row_dma`): there the kernels compile
(tests/test_chip_compile.py) and ran compiled against XLA on a v5e chip
(chip_smoke.py, PR 22); every other width raises, by name, before a step
runs. No step time has been measured. Dispatch lives in
sparse_update._row_scatter_add behind DET_SCATTER_IMPL=pallas-dma (the
'pallas' value names the fused deduped-row tile-walk strategy, ISSUE 12).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_embeddings_tpu.ops.pallas_lookup import (check_row_dma,
                                                          smem_ids_spec)


def _interpret_default(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# rows per tile; bounds VMEM (tile * width * 4B for the row buffer) and the
# number of concurrent row DMAs. Each in-flight copy owns a DMA semaphore
# and the chip has 2 KiB of semaphore memory (512 words, a few taken by the
# grid pipeline): the adagrad kernel's two semaphore arrays of 128 fit. A
# tile's writes reuse its reads' semaphores — every read is waited on
# before the first write starts.
_TILE = 128


def _scatter_kernel(ids_ref, delta_ref, table_ref, out_ref, rows_ref, sem,
                    *, tile: int, vocab: int):
    """Grid step i processes ids[i*tile : (i+1)*tile] (its SMEM block).
    table_ref/out_ref are the SAME HBM buffer (input_output_aliasing), so
    reads see prior tiles' writes only across grid steps — safe because
    ids are globally unique."""
    def rd(j):
        row = ids_ref[0, j]
        return pltpu.make_async_copy(
            table_ref.at[row], rows_ref.at[j], sem.at[j])

    def wr(j):
        row = ids_ref[0, j]
        return pltpu.make_async_copy(
            rows_ref.at[j], out_ref.at[row], sem.at[j])

    def issue(j, fn):
        # fillers (id >= vocab) and negative ids issue no DMA: the XLA path
        # this replaces drops both via mode="drop" (ADVICE r3: a negative id
        # must not reach table_ref.at[row])
        row = ids_ref[0, j]
        @pl.when((row >= 0) & (row < vocab))
        def _():
            fn(j)

    jax.lax.fori_loop(0, tile,
                      lambda j, _: (issue(j, lambda k: rd(k).start()), 0)[1],
                      0)
    jax.lax.fori_loop(0, tile,
                      lambda j, _: (issue(j, lambda k: rd(k).wait()), 0)[1],
                      0)
    rows_ref[:] = rows_ref[:] + delta_ref[:].astype(rows_ref.dtype)
    jax.lax.fori_loop(0, tile,
                      lambda j, _: (issue(j, lambda k: wr(k).start()), 0)[1],
                      0)
    jax.lax.fori_loop(0, tile,
                      lambda j, _: (issue(j, lambda k: wr(k).wait()), 0)[1],
                      0)


def scatter_add_sorted_unique(table: jax.Array, ids: jax.Array,
                              delta: jax.Array,
                              interpret: Optional[bool] = None) -> jax.Array:
    """table[ids[k]] += delta[k] for UNIQUE ids (sorted preferred for HBM
    locality); ids >= V are dropped (dedup filler contract). Returns the
    updated table; donate `table` for a true in-place update — the table
    travels through input_output_aliasing, so HBM traffic is the touched
    rows only (read + write), not a table copy.
    """
    vocab, width = table.shape
    n = ids.shape[0]
    if n == 0:        # empty grad shard: XLA scatter handles this; match it
        return table
    tile = min(_TILE, n)
    pad = -n % tile
    if pad:
        # filler ids (>= vocab) — predicated out inside the kernel
        ids = jnp.concatenate(
            [ids, jnp.full((pad,), vocab, ids.dtype)])
        delta = jnp.concatenate(
            [delta, jnp.zeros((pad, width), delta.dtype)], axis=0)
        n += pad

    grid_spec = pl.GridSpec(
        grid=(n // tile,),
        in_specs=[
            smem_ids_spec(tile),
            pl.BlockSpec((tile, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),      # table in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((tile, width), table.dtype),
            pltpu.SemaphoreType.DMA((tile,)),
        ],
    )
    interpret = _interpret_default(interpret)
    if not interpret:
        check_row_dma("pallas_scatter.scatter_add_sorted_unique", width,
                      table.dtype)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile, vocab=vocab),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},   # table (input 2 incl. the ids) -> out
        interpret=interpret,
    )(ids.astype(jnp.int32).reshape(n // tile, 1, tile), delta, table)


# ---------------------------------------------------------------------------
# fused row-wise adagrad: one RMW stream updates table AND accumulator
# ---------------------------------------------------------------------------
def _adagrad_kernel(ids_ref, sums_ref, table_ref, acc_ref, out_t, out_a,
                    trows, arows, sems,
                    *, tile: int, vocab: int, lr: float, eps: float):
    t_sem, a_sem = sems.at[0], sems.at[1]    # reads, then the writes

    def rd_t(j):
        return pltpu.make_async_copy(table_ref.at[ids_ref[0, j]],
                                     trows.at[j], t_sem.at[j])

    def rd_a(j):
        return pltpu.make_async_copy(acc_ref.at[ids_ref[0, j]],
                                     arows.at[j], a_sem.at[j])

    def wr_t(j):
        return pltpu.make_async_copy(trows.at[j],
                                     out_t.at[ids_ref[0, j]],
                                     t_sem.at[j])

    def wr_a(j):
        return pltpu.make_async_copy(arows.at[j],
                                     out_a.at[ids_ref[0, j]],
                                     a_sem.at[j])

    def guarded(j, fn):
        row = ids_ref[0, j]
        @pl.when((row >= 0) & (row < vocab))   # drop fillers AND negatives
        def _():
            fn(j)

    def loop(fn):
        jax.lax.fori_loop(0, tile,
                          lambda j, _: (guarded(j, fn), 0)[1], 0)

    loop(lambda j: rd_t(j).start())
    loop(lambda j: rd_a(j).start())
    loop(lambda j: rd_t(j).wait())
    loop(lambda j: rd_a(j).wait())

    s = sums_ref[:].astype(jnp.float32)
    acc_new = arows[:].astype(jnp.float32) + s * s
    delta = (-lr) * s * jax.lax.rsqrt(acc_new + eps)
    arows[:] = acc_new.astype(arows.dtype)
    trows[:] = (trows[:].astype(jnp.float32) + delta).astype(trows.dtype)

    loop(lambda j: wr_t(j).start())
    loop(lambda j: wr_a(j).start())
    loop(lambda j: wr_t(j).wait())
    loop(lambda j: wr_a(j).wait())


def adagrad_rows_sorted_unique(table: jax.Array, accum: jax.Array,
                               ids: jax.Array, sums: jax.Array, lr: float,
                               eps: float = 1e-10,
                               interpret: Optional[bool] = None):
    """Fused sparse adagrad on UNIQUE rows (dedup_sum output):

        acc[r]   += sums_r^2
        table[r] -= lr * sums_r * rsqrt(acc[r] + eps)

    in ONE read-modify-write stream per row pair — the XLA formulation
    costs two scatters plus a gather of the same rows (the dominant cost
    at 100-280 ns/row, round-3 prims). ids >= V are skipped; their sums
    must be zero. Returns (table', accum'), both alias their inputs.
    """
    vocab, width = table.shape
    n = ids.shape[0]
    if n == 0:        # empty grad shard: nothing to update
        return table, accum
    tile = min(_TILE, n)
    pad = -n % tile
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), vocab, ids.dtype)])
        sums = jnp.concatenate(
            [sums, jnp.zeros((pad, width), sums.dtype)], axis=0)
        n += pad

    grid_spec = pl.GridSpec(
        grid=(n // tile,),
        in_specs=[
            smem_ids_spec(tile),
            pl.BlockSpec((tile, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),      # table
            pl.BlockSpec(memory_space=pl.ANY),      # accumulator
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((tile, width), table.dtype),
            pltpu.VMEM((tile, width), accum.dtype),
            pltpu.SemaphoreType.DMA((2, tile)),
        ],
    )
    interpret = _interpret_default(interpret)
    if not interpret:
        check_row_dma("pallas_scatter.adagrad_rows_sorted_unique", width,
                      table.dtype)
    return pl.pallas_call(
        functools.partial(_adagrad_kernel, tile=tile, vocab=vocab,
                          lr=float(lr), eps=float(eps)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(accum.shape, accum.dtype)],
        input_output_aliases={2: 0, 3: 1},   # table->out_t, acc->out_a
        interpret=interpret,
    )(ids.astype(jnp.int32).reshape(n // tile, 1, tile), sums, table, accum)
