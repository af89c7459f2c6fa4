"""Pallas TPU kernels: fused multi-hot embedding lookup-combine.

TPU-native replacement for the reference's custom CUDA combiner kernels
(reference: cc/kernels/embedding_lookup_kernels.cu:33-336 — warp-level CSR
segment reduce with shared-memory index staging). The TPU design is shaped by
different hardware: there is no warp shuffle, but there is a 128x128 MXU and
explicit async DMA. Two kernels cover the vocab spectrum:

  * ``_onehot_lookup`` (small vocab): the weighted combine
    ``out[b] = sum_k w[b,k] * table[ids[b,k]]`` is algebraically
    ``A @ table`` with ``A[b,v] = sum_k w[b,k] * [ids[b,k] == v]``.
    The kernel builds each ``[tile_b, tile_v]`` slab of A on the fly in VMEM
    (never materializing the [B, V] one-hot in HBM) and accumulates partial
    matmuls on the MXU over vocab tiles. Lookup *is* a matmul on TPU.

  * ``_dma_gather_lookup`` (large vocab): each batch tile's ids ride an
    SMEM block, the table stays in HBM, and the kernel streams
    the addressed rows VMEM-ward with double-buffered async DMA — one buffer
    accumulates ``w[b,k] * row`` while the next hotness step's rows are in
    flight. This is the moral equivalent of the CUDA kernel's smem staging +
    register accumulation (.cu:33-107), with DMA latency instead of memory
    coalescing as the thing being hidden.

The backward is XLA-native scatter-add (static shapes, no D2H sync — the
reference grad kernel's `num_unique_ids` D2H copy at .cu:665 is the failure
mode this avoids), registered through ``jax.custom_vjp``.

Inputs are the framework's canonical padded multi-hot form: ids [B, K] with
arbitrary ids in padded slots, weights [B, K] carrying 0.0 there (and the
mean normalization pre-applied — see ``fused_embedding_lookup``).
"""

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Vocab size at or below which the MXU one-hot-matmul kernel is used. The
# default has no on-chip measurement behind it. DET_ONEHOT_MAX_VOCAB
# overrides per trace (read per call, so in-process A/B works); 0 disables the MXU kernel entirely.
ONEHOT_MAX_VOCAB = 8192


def _onehot_max_vocab() -> int:
    return int(os.environ.get("DET_ONEHOT_MAX_VOCAB", ONEHOT_MAX_VOCAB))


def is_tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default(interpret: Optional[bool]) -> bool:
    # compiled on TPU; interpreter elsewhere (CPU tests)
    if interpret is None:
        return not is_tpu_backend()
    return interpret


# --------------------------------------------------------------------------
# small-vocab kernel: one-hot matmul on the MXU
# --------------------------------------------------------------------------
def _onehot_kernel(ids_ref, w_ref, table_ref, out_ref, *, tile_v: int):
    j = pl.program_id(1)
    ids = ids_ref[:]                               # [tb, K] int32
    w = w_ref[:]                                   # [tb, K] f32
    tb = ids.shape[0]
    v_iota = (jax.lax.broadcasted_iota(jnp.int32, (tb, tile_v), 1)
              + j * tile_v)
    a = jnp.zeros((tb, tile_v), jnp.float32)
    for k in range(ids.shape[1]):                  # K is small and static
        a = a + jnp.where(v_iota == ids[:, k:k + 1], w[:, k:k + 1], 0.0)
    # HIGHEST: the MXU's default bf16 passes lose ~2^-8 relative accuracy
    # (observed 2e-3 vs the f32 XLA path on hardware); the 3-pass f32
    # emulation keeps the kernel bit-comparable to gather+reduce
    part = jax.lax.dot_general(
        a, table_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        out_ref[:] = part

    @pl.when(j != 0)
    def _():
        out_ref[:] = out_ref[:] + part


def _onehot_lookup(table: jax.Array, ids: jax.Array, weights: jax.Array,
                   tile_b: int = 256, tile_v: int = 512,
                   interpret: Optional[bool] = None) -> jax.Array:
    batch, k = ids.shape
    vocab, width = table.shape
    # sublane-align the batch tile (Mosaic wants multiples of 8; odd sizes
    # compiled but returned wrong results on hardware)
    tile_b = min(tile_b, max(8, -(-batch // 8) * 8))
    pad_b = -batch % tile_b
    if pad_b:
        ids = jnp.pad(ids, ((0, pad_b), (0, 0)))
        weights = jnp.pad(weights, ((0, pad_b), (0, 0)))
    pad_v = -vocab % tile_v
    if pad_v:
        # zero-pad so OOB vocab tiles contribute exact zeros (never NaN*0)
        table = jnp.pad(table, ((0, pad_v), (0, 0)))
    grid = ((batch + pad_b) // tile_b, (vocab + pad_v) // tile_v)
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, tile_v=tile_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_v, width), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_b, width), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((batch + pad_b, width), jnp.float32),
        interpret=_interpret_default(interpret),
    )(ids.astype(jnp.int32), weights.astype(jnp.float32), table)
    return out[:batch]


# --------------------------------------------------------------------------
# large-vocab kernel: per-tile SMEM ids + deep-pipelined row DMA
# --------------------------------------------------------------------------
# Row gathers from HBM are latency/descriptor-rate bound on TPU, so the
# kernel's job is to keep MANY row DMAs in flight: hotness is processed in
# chunks of `hc` slots x `tile_b` rows (tile_b*hc concurrent copies),
# double-buffered so chunk c+1's copies are in flight while chunk c combines.
# DMA issue loops are lax.fori_loop, not Python-unrolled — the round-1 kernel
# unrolled 2*tile_b*hot copy ops and crashed the compiler at hotness 200.
def _dma_gather_kernel(ids_ref, w_ref, table_ref, out_ref, rows_ref, sems,
                       *, tile_b: int, hot: int, hc: int):
    nchunks = hot // hc

    def dma(c, slot, j):
        # j enumerates (kk, t) in the chunk: kk = j // tile_b, t = j % tile_b
        kk, t = j // tile_b, j % tile_b
        row = ids_ref[0, t * hot + c * hc + kk]    # this tile's ids, row-major
        return pltpu.make_async_copy(
            table_ref.at[row], rows_ref.at[slot, kk, t], sems.at[slot, j])

    def start_chunk(c, slot):
        jax.lax.fori_loop(
            0, tile_b * hc,
            lambda j, _: (dma(c, slot, j).start(), 0)[1], 0)

    def wait_chunk(c, slot):
        jax.lax.fori_loop(
            0, tile_b * hc,
            lambda j, _: (dma(c, slot, j).wait(), 0)[1], 0)

    start_chunk(0, 0)
    out_ref[:] = jnp.zeros_like(out_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_b, hot), 1)

    def body(c, _):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nchunks)
        def _():
            start_chunk(c + 1, jax.lax.rem(c + 1, 2))

        wait_chunk(c, slot)
        w = w_ref[:]                               # [tile_b, hot]
        acc = out_ref[:]
        for kk in range(hc):                       # hc is small and static
            # the chunk's weight column by lane mask + reduce: a dynamic
            # LANE slice (w_ref[:, pl.ds(c * hc, hc)]) is not provably
            # 128-aligned and the chip's compiler refuses it
            col = jnp.sum(jnp.where(lane == c * hc + kk, w, 0.0), axis=1,
                          keepdims=True)
            acc = acc + rows_ref[slot, kk].astype(jnp.float32) * col
        out_ref[:] = acc
        return 0

    jax.lax.fori_loop(0, nchunks, body, 0)


def smem_ids_spec(n: int) -> pl.BlockSpec:
    """Grid step i's `n` ids of a [n_steps, 1, n] int32 array as an SMEM
    block, read in the kernel as ids_ref[0, j]. Not a whole-array scalar
    prefetch: a 65,536 x 10 id array alone is 2.5x the chip's 1 MiB scalar
    memory (a 1.7M-row DLRM update 6.5x). The unit middle dim makes the
    block's trailing dims equal the array's, the tiling-legal form of a
    one-row block."""
    return pl.BlockSpec((None, 1, n), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


# row copies in flight per buffer. Each copy owns a DMA semaphore and the
# chip has 2 KiB of semaphore memory (512 words, a few taken by the grid
# pipeline), so two buffers of 128 fit with room to spare; in-flight bytes
# = 2 * DMA_DEPTH * width * 4.
_DMA_DEPTH = 128
# ids of one batch tile (int32 words) — bounds the tile at high hotness
_SMEM_TILE_IDS = 32 * 1024


def _dma_gather_lookup(table: jax.Array, ids: jax.Array, weights: jax.Array,
                       interpret: Optional[bool] = None) -> jax.Array:
    batch, hot = ids.shape
    _, width = table.shape
    # batch tile: sublane-aligned, sized so tile_b * hc ~ _DMA_DEPTH and so
    # one tile's ids (tile_b * hot words, double-buffered) stay a small
    # part of the 1 MiB scalar memory
    tile_b = max(8, min(_DMA_DEPTH, -(-batch // 8) * 8,
                        (_SMEM_TILE_IDS // hot) // 8 * 8))
    hc = max(1, min(hot, _DMA_DEPTH // tile_b))
    pad_k = -hot % hc
    if pad_k:
        # zero-weight padded hotness slots (id 0 is a safe in-bounds row)
        ids = jnp.pad(ids, ((0, 0), (0, pad_k)))
        weights = jnp.pad(weights, ((0, 0), (0, pad_k)))
        hot += pad_k
    pad_b = -batch % tile_b
    if pad_b:
        ids = jnp.pad(ids, ((0, pad_b), (0, 0)))
        weights = jnp.pad(weights, ((0, pad_b), (0, 0)))
    n_tiles = (batch + pad_b) // tile_b
    grid_spec = pl.GridSpec(
        grid=(n_tiles,),
        in_specs=[
            smem_ids_spec(tile_b * hot),
            pl.BlockSpec((tile_b, hot), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),      # table stays in HBM
        ],
        out_specs=pl.BlockSpec((tile_b, width), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            # [slot, hotness slot, batch row, width]: each hotness slot is
            # one whole (tile_b, width) tile, so the combine reads aligned
            # tiles and a row copy lands on one sublane of one
            pltpu.VMEM((2, hc, tile_b, width), table.dtype),
            pltpu.SemaphoreType.DMA((2, tile_b * hc)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_dma_gather_kernel, tile_b=tile_b, hot=hot, hc=hc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch + pad_b, width), jnp.float32),
        interpret=_interpret_default(interpret),
    )(ids.reshape(n_tiles, 1, tile_b * hot).astype(jnp.int32),
      weights.astype(jnp.float32), table)
    return out[:batch]


# --------------------------------------------------------------------------
# dispatch + autodiff
# --------------------------------------------------------------------------
# The only row a single-row DMA can address on the chip: an HBM array tiles
# as (8, 128), so a [V, 128] f32 table is row-contiguous, while a wider one
# interleaves 8 rows per tile, a narrower one is stored column-major and a
# 16-bit one packs two rows per sublane — the compiler refuses a one-row
# slice of each (compile probes in tests/test_chip_compile.py).
_ROW_DMA_WIDTH = 128


def row_dma_ok(width: int, dtype) -> bool:
    return width == _ROW_DMA_WIDTH and jnp.dtype(dtype) == jnp.float32


def check_row_dma(kernel: str, width: int, dtype) -> None:
    """Raise unless a per-row DMA kernel can address rows of this table on
    the chip (see _ROW_DMA_WIDTH). Interpret mode has no such limit."""
    if not row_dma_ok(width, dtype):
        raise ValueError(
            f"{kernel} moves one table row per DMA, which the TPU "
            f"compiler accepts only for float32 rows of width "
            f"{_ROW_DMA_WIDTH}; got width {width} dtype "
            f"{jnp.dtype(dtype).name}. Use the tiled/fused kernels or the "
            "XLA path for this table.")


def check_lookup_kernel(vocab: int, width: int, dtype) -> None:
    """Raise unless `fused_embedding_lookup` serves a [vocab, width] table
    with a Pallas kernel on the chip — what an explicit
    DET_LOOKUP_PATH=pallas asks for (the layer constructor calls this per
    bucket; under 'auto' such a table takes the XLA gather by design)."""
    if vocab > _onehot_max_vocab():
        check_row_dma("DET_LOOKUP_PATH=pallas "
                      "(pallas_lookup._dma_gather_lookup)", width, dtype)


def has_kernel(vocab: int, width: int, dtype) -> bool:
    """Does `fused_embedding_lookup` serve a [vocab, width] table with a
    Pallas kernel (the one-hot product or the row DMA), or fall through to
    XLA's gather? The layer asks before it hands a narrow bucket's group
    over in the kernels' batch-major order."""
    return vocab <= _onehot_max_vocab() or row_dma_ok(width, dtype)


def _fused_impl(params, ids, weights, interpret):
    vocab, width = params.shape
    if vocab <= _onehot_max_vocab():
        return _onehot_lookup(params, ids, weights, interpret=interpret)
    if row_dma_ok(width, params.dtype):
        return _dma_gather_lookup(params, ids, weights, interpret=interpret)
    # a table the row DMA cannot address (chosen from its shape, not from a
    # failure): XLA gather + weighted reduce, still fused by XLA. A narrow
    # bucket's group does not come this far on the layer's default path:
    # `DistributedEmbedding._group_lookup` gathers it feature-major
    embs = jnp.take(params, ids, axis=0)
    return jnp.einsum("bk,bkw->bw", weights.astype(embs.dtype),
                      embs).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_lookup(params, ids, weights, interpret):
    return _fused_impl(params, ids, weights, interpret)


def _fused_fwd(params, ids, weights, interpret):
    return _fused_impl(params, ids, weights, interpret), (params, ids, weights)


def _fused_bwd(interpret, res, g):
    params, ids, weights = res
    flat_ids = ids.reshape(-1)
    contrib = (weights[..., None].astype(g.dtype) * g[:, None, :]).reshape(
        -1, g.shape[-1])
    # dense-table scatter-add: static shapes, no sort/unique, no host sync
    dtable = jnp.zeros_like(params).at[flat_ids].add(
        contrib.astype(params.dtype))
    rows = jnp.take(params, ids, axis=0).astype(g.dtype)
    dweights = jnp.einsum("bkw,bw->bk", rows, g).astype(weights.dtype)
    return dtable, None, dweights


_fused_lookup.defvjp(_fused_fwd, _fused_bwd)


def fused_embedding_lookup(params: jax.Array, ids: jax.Array,
                           weights: Optional[jax.Array] = None,
                           combiner: str = "sum",
                           interpret: Optional[bool] = None) -> jax.Array:
    """Fused padded multi-hot lookup: [V,W] table, [B,K] ids -> [B,W].

    weights [B, K] carry 0.0 in padded slots (None = all-ones). Mean is
    handled by pre-normalizing weights so both kernels only ever compute a
    weighted sum (matching the reference Combiner semantics, .cu:96-99).
    Differentiable in params and weights.
    """
    if combiner not in ("sum", "mean"):
        raise ValueError(f"Unsupported combiner {combiner}")
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    if combiner == "mean":
        denom = jnp.maximum(jnp.sum(weights, axis=1, keepdims=True), 1.0)
        weights = weights / denom
    # match XLA gather semantics (clamp OOB) so results don't depend on which
    # kernel path ran; also keeps the DMA kernel from reading past the table
    ids = jnp.clip(ids, 0, params.shape[0] - 1)
    return _fused_lookup(params, ids, weights, interpret).astype(params.dtype)
