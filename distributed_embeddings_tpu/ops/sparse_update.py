"""Sparse embedding-table updates: the TPU answer to IndexedSlices.

The reference keeps embedding gradients sparse end to end: its backward kernel
emits (unique_ids, unique_grads) consumed as tf.IndexedSlices (reference:
cc/kernels/embedding_lookup_kernels.cu:603-775, python/ops/
embedding_lookup_ops.py:105-122), and TF optimizers apply them row-wise.
Under plain `jax.grad` + optax the table gradient is a *dense* [V, w] array:
for a 4.2 GiB table that is a 4.2 GiB scatter-add temp per step plus a
full-table optimizer pass (~21 GiB of HBM traffic for adagrad — already
slower than the reference's entire step). This module keeps both the
gradient and the optimizer update O(touched rows):

  * `SparseRowGrad(ids, contribs)` — per-contribution gradient rows, static
    shape [N] / [N, w] (N = batch x hotness), never host-synced (the
    reference's D2H `num_unique_ids` copy at .cu:665 is the failure mode
    static shapes avoid).
  * `dedup_sum` — sort-based duplicate aggregation (the reference uses
    cub radix sort + unique, .cu:645-661). Empty/padded slots get a
    `sentinel` row id == V; JAX scatters DROP out-of-bounds ids, so
    sentinel rows vanish in the update without a mask.
  * `sparse_sgd` / `sparse_adagrad` — row-wise updates via .at[ids] ops.
    With donated buffers XLA performs them in place, touching only looked-up
    rows.

Aggregation strategy is selectable (`strategy=`):
  * 'sort'  — lax.sort, then a segmented doubling scan over the sorted
    contributions and a shift network that moves each run's total to the
    front (no row scatter until the final row update). O(N log N)
    streamed work but no [V, w] temp.
  * 'dense' — scatter-add into a dense [V, w] zeros then a *masked* row
    update. Simple and fast when V*w is small; O(V, w) memory.
  Auto mode picks 'dense' below `DENSE_ELEMS_MAX` elements, 'sort' above.
"""

import os
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_embeddings_tpu.obs.stages import staged

# auto-strategy threshold: buckets up to this many elements aggregate through
# a dense temp (64 MiB at f32 width 16); larger buckets use the sort path.
# One value has ever run, with one traced reading either side of it (Tiny V3,
# ROADMAP S1's first question): 14 ns a contribution into the width-8
# bucket's dense target below it, 108 ns a contribution through the width-16
# bucket's sorted path above it.
DENSE_ELEMS_MAX = 16 * 1024 * 1024


def fp_round(x: jax.Array, zero: jax.Array) -> jax.Array:
    """Pin a PRODUCT to its f32-rounded value before it feeds another
    add/sub: add `zero`, a RUNTIME 0.0 the compiler cannot prove
    constant (an SMEM hyperparameter slot inside the Pallas kernels, a
    traced-scalar derivation here — see `round_pin`). Backend FMA
    contraction happens BELOW HLO (LLVM fuses fmul+fadd inside one
    fusion; `lax.optimization_barrier`, bitcast round-trips and
    mul-by-dynamic-one pins do not reliably survive — all measured), and
    it is context-dependent: the same expression contracts differently
    inside a Pallas kernel body than next to a scatter whose operand
    forces materialization. The add-zero pin is IDEMPOTENT under
    contraction — fused or not, ``fma(a, b, 0) == round(a*b)`` — so the
    value is the plain IEEE product either way, and the fused pallas
    kernels and the XLA sort path round at identical seams. The
    bit-exactness contract between the two strategies (ISSUE 12,
    tests/test_pallas_fused.py) rests on this. (Sole side effect:
    ``-0.0`` pins to ``+0.0`` — invisible to ``==``.)"""
    return x + zero


def round_pin(traced_int: jax.Array) -> jax.Array:
    """An opaque f32 0.0 derived from a traced INTEGER scalar/array (int
    -> float cast can never be NaN/inf, so the 0-mul identity is exact):
    ``x*0`` only folds under nnan/ninf fast-math, which XLA:CPU/TPU do
    not enable for f32. Pass a TRACED value (adam's step count, an id
    array lane) — a concrete closure constant would fold at trace
    time (eager flows need no pin: per-op dispatch rounds every
    product)."""
    return (traced_int.reshape(-1)[0].astype(jnp.float32)
            * jnp.float32(0.0))


# scatter/gather promise kwargs legal for dedup_sum's rep output, which is
# strictly increasing (sentinel tail included)
DEDUP_FLAGS = {"unique_indices": True, "indices_are_sorted": True}


# ------------- kernel dispatch + the compiled check
# A kernel family is selected by request (DET_SCATTER_IMPL /
# DET_LOOKUP_PATH / an explicit strategy=): what was asked for runs, and a
# kernel the chip's compiler refuses stops the program with the compiler's
# own error. Nothing here falls back to another path. With no request,
# two kernels run by what the code sees: on a TPU, adagrad's sort branch
# over a table the chip stores column-major hands the sorted stream,
# duplicates and all, to the tile stream (`_tile_stream`, ISSUEs 33 and
# 37), and the dense branch's aggregate over a small such table stays in
# fast memory (`_dense_kernel`, ISSUE 41). On a TPU backend the step/layer
# factories also run each family they will dispatch to ONCE per width
# class, eagerly and compiled, against its XLA formulation
# (`prevalidate_active_impl`) and raise on a mismatch; off-TPU the kernels
# run in interpret mode and the test suite is that check.
def _width_class(width: int) -> int:
    """Pow2 lane-width shape-class for the compiled checks: the compiled
    form of a BlockSpec kernel depends on the lane padding of its width,
    not the exact value, so one check covers every width of a class
    (clamped to [8, 512] — wider tables share the 512 class's tiling)."""
    c = 8
    while c < width and c < 512:
        c *= 2
    return c


class _KernelCheck:
    """Once-per-(process, width class) compiled-vs-XLA check of one kernel
    family. A compile or run error propagates; a numerics mismatch
    raises. `classed` maps a width to the one its check runs at: the
    pow2 class, or the width itself for a kernel whose operands go by the
    exact width."""

    def __init__(self, validator, what: str, classed=_width_class):
        self.validator = validator      # (width class) -> bool, may raise
        self.what = what
        self.classed = classed
        self.validated: set = set()     # width classes checked

    def prevalidate(self, width: int = 16) -> bool:
        cls = self.classed(width)
        if cls not in self.validated:
            if not self.validator(cls):
                raise RuntimeError(
                    f"{self.what}: compiled kernels disagree with the XLA "
                    f"formulation at width class {cls} on this backend")
            self.validated.add(cls)
        return True


def _close(got, want, tol: float) -> bool:
    return bool(jnp.max(jnp.abs(got - want)) < tol)


def _validate_tiled(width: int) -> bool:
    """Compiled correctness of the tiled one-hot-matmul kernels
    (ops/pallas_tiled.py): gather, sgd, fused adagrad and adam vs XLA."""
    import numpy as np
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    rng = np.random.RandomState(0)
    v, w, n = 4096, width, 2048
    ids = jnp.asarray(rng.randint(0, v, n).astype(np.int32))
    delta = jnp.asarray(rng.randn(n, w).astype(np.float32))
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    got = ptl.tiled_sgd(table, ids, delta, 0.05, interpret=False)
    want = table.at[ids].add(-0.05 * delta, mode="drop")
    ok = _close(got, want, 1e-3)
    acc = jnp.full((v, w), 0.1, jnp.float32)
    t2, a2 = ptl.tiled_adagrad(table, acc, ids, delta, 0.05,
                               interpret=False)
    rep, sums = dedup_sum(ids, delta, sentinel=v)
    t_want, a_want = _adagrad_rows_xla(table, acc, rep, sums, 0.05, 1e-10)
    ok = ok and _close(a2, a_want, 1e-3) and _close(t2, t_want, 1e-3)
    g3 = ptl.tiled_gather(table, ids, interpret=False)
    ok = ok and _close(g3, jnp.take(table, ids, axis=0), 1e-4)
    mu = jnp.zeros((v, w), jnp.float32)
    nu = jnp.zeros((v, w), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    t4, mu4, nu4, _ = ptl.tiled_adam(table, mu, nu, cnt, ids, delta, 0.01,
                                     interpret=False)
    tw, muw, nuw, _ = sparse_adam(table, mu, nu, cnt,
                                  SparseRowGrad(ids, delta), 0.01,
                                  strategy="sort")
    return (ok and _close(t4, tw, 1e-3) and _close(mu4, muw, 1e-3)
            and _close(nu4, nuw, 1e-3))


def _validate_pallas_scatter(width: int) -> bool:
    """Compiled correctness of the per-row DMA RMW kernels
    (ops/pallas_scatter.py): scatter-add + fused adagrad vs XLA. The
    kernels themselves raise for a width they cannot address
    (`pallas_scatter.check_row_dma`)."""
    import numpy as np
    from distributed_embeddings_tpu.ops import pallas_scatter as ps
    rng = np.random.RandomState(0)
    v, w, n = 4096, width, 512
    ids = jnp.asarray(np.sort(rng.choice(v, n, replace=False))
                      .astype(np.int32))
    delta = jnp.asarray(rng.randn(n, w).astype(np.float32))
    table = jnp.zeros((v, w), jnp.float32)
    got = ps.scatter_add_sorted_unique(table, ids, delta, interpret=False)
    ok = _close(got, table.at[ids].add(delta, mode="drop"), 1e-5)
    # the fused adagrad kernel rides the same check
    acc = jnp.full((v, w), 0.1, jnp.float32)
    t2, a2 = ps.adagrad_rows_sorted_unique(table, acc, ids, delta, 0.05,
                                           interpret=False)
    a_want = acc.at[ids].add(delta * delta, mode="drop")
    d_want = -0.05 * delta * lax.rsqrt(jnp.take(a_want, ids, axis=0) + 1e-10)
    t_want = table.at[ids].add(d_want, mode="drop")
    return ok and _close(a2, a_want, 1e-5) and _close(t2, t_want, 1e-5)


def _validate_pallas_fused(width: int) -> bool:
    """Compiled correctness of the fused deduped-row kernels (ISSUE 12,
    ops/pallas_tiled.py *_rows + the weighted gather) at one lane-width
    class, against the XLA sort-path formulations they must reproduce."""
    import numpy as np
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    rng = np.random.RandomState(0)
    v, n, w = 4096, 1024, width
    ids = jnp.asarray(rng.randint(0, v, n).astype(np.int32))
    delta = jnp.asarray(rng.randn(n, w).astype(np.float32))
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    rep, sums = dedup_sum(ids, delta, sentinel=v)
    got = ptl.tiled_sgd_rows(table, rep, sums, 0.05, interpret=False)
    want = table.at[rep].add(-0.05 * sums, mode="drop", **DEDUP_FLAGS)
    ok = _close(got, want, 1e-4)
    acc = jnp.full((v, w), 0.1, jnp.float32)
    t2, a2 = ptl.tiled_adagrad_rows(table, acc, rep, sums, 0.05,
                                    interpret=False)
    t_want, a_want = _adagrad_rows_xla(table, acc, rep, sums, 0.05, 1e-10)
    ok = ok and _close(a2, a_want, 1e-4) and _close(t2, t_want, 1e-4)
    mu = jnp.zeros((v, w), jnp.float32)
    nu = jnp.zeros((v, w), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    t4, mu4, nu4, _ = ptl.tiled_adam_rows(table, mu, nu, cnt, rep, sums,
                                          0.01, interpret=False)
    tw, muw, nuw, _ = sparse_adam(table, mu, nu, cnt,
                                  SparseRowGrad(ids, delta), 0.01,
                                  strategy="sort")
    ok = (ok and _close(t4, tw, 1e-4) and _close(mu4, muw, 1e-4)
          and _close(nu4, nuw, 1e-4))
    # fused forward: weighted gather->combine vs the XLA gather+einsum
    ids2 = ids[:(n // 4) * 4].reshape(-1, 4)
    wts = jnp.asarray(np.abs(rng.rand(*ids2.shape)).astype(np.float32))
    got_f = ptl.fused_lookup_combine(table, ids2, wts, "sum",
                                     interpret=False)
    want_f = jnp.einsum("bk,bkw->bw", wts, jnp.take(table, ids2, axis=0))
    return ok and _close(got_f, want_f, 1e-3)


def _validate_tile_stream(width: int) -> bool:
    """Compiled correctness of the one kernel a default path runs:
    `pallas_tiled.tiled_adagrad`, rows on the lanes, on the sorted stream
    with its duplicates, against the XLA lines it stands in for in
    `sparse_adagrad` (`dedup_sum` + `_adagrad_rows_xla`), as one program
    (one compile, kept by the persistent cache). Duplicates are the rule:
    three ids in four name one of 300 hot rows that straddle a tile edge,
    the hottest some thousand times over several chunks, the rest a
    uniform tail with a few slots out of range. The kernel sums a run in
    another f32 order than the scan does, so a row is held to 1e-5 of its
    largest element, not bit for bit."""
    import numpy as np
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    rng = np.random.RandomState(0)
    v, n = 20_000, 16_384
    hot = 900 + (rng.zipf(1.2, n) - 1) % 300     # tile edge at row 1,024
    ids = np.where(rng.rand(n) < 0.75, hot, rng.randint(-8, v + 8, n))

    @jax.jit
    def gaps(table, acc, ids, delta):
        got = ptl.tiled_adagrad(table, acc, ids, delta, 0.05,
                                interpret=False)
        rep, sums = dedup_sum(ids, delta, sentinel=v)
        want = _adagrad_rows_xla(table, acc, rep, sums, 0.05, 1e-10)
        return [jnp.max(jnp.abs(g - w)
                        / jnp.max(jnp.abs(w), axis=1, keepdims=True))
                for g, w in zip(got, want)]

    table_gap, acc_gap = gaps(
        jnp.asarray(rng.randn(v, width).astype(np.float32)),
        jnp.full((v, width), 0.1, jnp.float32),
        jnp.asarray(ids.astype(np.int32)),
        jnp.asarray(rng.randn(n, width).astype(np.float32)))
    return bool(table_gap < 1e-5) and bool(acc_gap < 1e-5)


def _validate_dense_sum(width: int) -> bool:
    """Compiled correctness of the second kernel a default path runs:
    `pallas_tiled.dense_sum`, the resident dense aggregate, against
    `_scatter_sum`, the XLA lines it stands in for in `_dense_sum`, as one
    program. The stream is what the kernel is for and what it must
    survive: runs of slots that name one small table's rows (a table
    straddles a tile edge), most of them duplicates, then a run spread
    over the whole target, a few ids out of range either side, a length
    that is no whole chunk. Counts are held exactly, which a lost or a
    doubled contribution cannot pass; a row's sums to 1e-4 of its largest
    element (an f32 sum in another order: 7e-6 was read on the chip
    against XLA's scatter-add over a row of 49,000 contributions). It
    runs at the width itself and not at its pow2 class: a pair's operands
    are `3 * width + 8` rows a block, and `dense_sum_blocks` cuts the
    tile to what they leave room for."""
    import numpy as np
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    rng = np.random.RandomState(0)
    rows, n = _DENSE_SUM_CHECK_ROWS, 20_000
    chunk, tile = ptl.dense_sum_blocks(rows, width)
    base = np.repeat(rng.randint(0, rows - 1_500, n // 500 + 1), 500)[:n]
    ids = np.where(np.arange(n) < 15_000, base + rng.zipf(1.3, n) % 1_500,
                   rng.randint(-8, rows + 8, n))

    @jax.jit
    def gaps(ids, contribs):
        kids, lo, hi, _ = ptl.dense_sum_walk(ids, rows, chunk, tile)
        g, counts = ptl.dense_sum(kids, lo, hi, contribs, rows, tile,
                                  interpret=False)
        g_want, counts_want = _scatter_sum(ids, contribs, rows)
        scale = jnp.maximum(jnp.max(jnp.abs(g_want), axis=1, keepdims=True),
                            1e-30)
        return (jnp.max(jnp.abs(g - g_want) / scale),
                jnp.max(jnp.abs(counts - counts_want)))

    g_gap, count_gap = gaps(
        jnp.asarray(ids.astype(np.int32)),
        jnp.asarray(rng.randn(n, width).astype(np.float32)))
    return bool(g_gap < 1e-4) and bool(count_gap == 0)


# 'pallas' names the fused deduped-row tile-walk strategy (ISSUE 12); the
# per-row DMA RMW kernels (ops/pallas_scatter.py) are 'pallas-dma'
_TILED_CHECK = _KernelCheck(_validate_tiled, "DET_SCATTER_IMPL=tiled")
_PALLAS_DMA_CHECK = _KernelCheck(_validate_pallas_scatter,
                                 "DET_SCATTER_IMPL=pallas-dma")
_PALLAS_FUSED_CHECK = _KernelCheck(_validate_pallas_fused,
                                   "DET_SCATTER_IMPL=pallas")
# the two kernels on a default path (see `_tile_stream`, `_dense_kernel`)
_TILE_STREAM_CHECK = _KernelCheck(_validate_tile_stream,
                                  "sparse_adagrad's tile stream")
_DENSE_SUM_CHECK = _KernelCheck(_validate_dense_sum,
                                "_dense_sum's resident kernel", classed=int)
# Rows of that check's target: a width whose pair fits fast memory at all
# (`pallas_tiled.dense_sum_blocks`) fits a target of so many
_DENSE_SUM_CHECK_ROWS = 9_000


def prevalidate_tiled(width: int = 16) -> bool:
    return _TILED_CHECK.prevalidate(width)


def prevalidate_pallas_scatter(width: int = 128) -> bool:
    return _PALLAS_DMA_CHECK.prevalidate(width)


def prevalidate_pallas_fused(width: int = 16) -> bool:
    """Eager compiled check of the fused pallas family at `width`'s
    shape-class (see _KernelCheck)."""
    return _PALLAS_FUSED_CHECK.prevalidate(width)


def _scatter_env(value: str) -> bool:
    """DET_SCATTER_IMPL == value, honoured on the TPU backend only — the
    env route never flips CPU test numerics."""
    return (os.environ.get("DET_SCATTER_IMPL", "xla") == value
            and jax.default_backend() == "tpu")


def _pallas_requested(strategy: str) -> bool:
    """Did this call opt into the fused pallas strategy: explicit
    strategy='pallas', or auto + DET_SCATTER_IMPL=pallas (TPU only)."""
    return strategy == "pallas" or (strategy == "auto"
                                    and _scatter_env("pallas"))


def _scatter_route(strategy: str) -> str:
    """Which update family serves a call — 'pallas' | 'tiled' | 'xla' —
    from the request alone. Shared by dispatch, the obs label
    (`active_scatter_impl`) and the fold planner (`update_consumes_sort`)
    so the three cannot drift."""
    if _pallas_requested(strategy):
        return "pallas"
    if strategy == "tiled" or (strategy == "auto" and _scatter_env("tiled")):
        return "tiled"
    return "xla"


def _lane_width(width: int) -> bool:
    """A width the chip stores column-major (`f32[V,w]{0,1:T(8,128)}`, so
    that `table.T` is a bitcast): under 128 lanes, whole sublanes."""
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    return width < ptl.ROW_MAJOR_WIDTH and width % 8 == 0


def feature_major_stream(width: int, batch: int) -> bool:
    """Is an exchange group's `[batch, f, k]` block of id slots flattened
    into its stream as (f, k, b), and not as (b, f, k)? By what the code
    sees, never by a request: where the chip stores the bucket
    column-major (`_lane_width`) a gather of n slots comes back as
    `[width, n]`, the slot on the lanes, and with the batch a whole number
    of 128-lane vectors that is `[width, f, k, batch]`: the batch stays on
    the lanes from the gather to the model and back, which wants it there,
    and no pass re-tiles the rows in between. A row-major (wide) bucket
    gains nothing and keeps (b, f, k). The lookup, the folded sort and the
    update's contributions all ask here (through
    `DistributedEmbedding._feature_major`), so that a stream and its
    contributions cannot be flattened differently."""
    return _lane_width(width) and batch > 0 and batch % 128 == 0


def _tile_stream(strategy: str, rows: int, width: int, n: int) -> bool:
    """Does `sparse_adagrad` hand its n id slots, sorted and with their
    duplicates, to the Pallas tile stream (`pallas_tiled.tiled_adagrad`,
    rows on the lanes: the entry point both Tiny V3 cells run), which
    sums a run of duplicates in its one-hot product, and not to
    `dedup_sum` and the XLA scatter lines? By what the code sees, never by
    a request: a TPU, a table that takes the sort branch and is stored
    column-major there (a row scatter into it costs ~100 ns a row, the
    one thing the CPU and a row-major wide table do well), a pair walk
    that fits the chip's scalar memory. An explicit strategy="sort"
    keeps `dedup_sum` and the XLA lines: they are the reference the
    kernels are held to."""
    if strategy != "auto" or _scatter_route(strategy) != "xla":
        return False
    if jax.default_backend() != "tpu" or not _lane_width(width):
        return False
    if _pick(strategy, rows, width) != "sort":
        return False
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    return ptl.lane_blocks(rows, n) is not None


def gate_verdicts() -> dict:
    """{impl: verdict} for the ``kernels/gate_verdict{impl=}`` obs gauge:
    1 = the family's compiled check ran and passed in this process,
    -1 = it never ran (off-TPU interpret mode, or nothing dispatches to
    it). A failed check raises, so no process lives to report one."""
    return {"tiled": 1 if _TILED_CHECK.validated else -1,
            "pallas-dma": 1 if _PALLAS_DMA_CHECK.validated else -1,
            "pallas": 1 if (_PALLAS_FUSED_CHECK.validated
                            or _TILE_STREAM_CHECK.validated) else -1,
            "dense-sum": 1 if _DENSE_SUM_CHECK.validated else -1}


def active_scatter_impl(strategy: str = "auto", kind: Optional[str] = None,
                        rows: int = 0, width: int = 0, n: int = 0) -> str:
    """Which update family a step traced now dispatches to for a
    [rows, width] table under optimizer `kind` and a stream of n id
    slots — the obs label for the per-strategy update-phase span. With
    no shape it answers for the request alone."""
    if kind == "adagrad" and _tile_stream(strategy, rows, width, n):
        return "pallas"
    return _scatter_route(strategy)


def prevalidate_active_impl(strategy: Optional[str] = None,
                            widths=None, kind: Optional[str] = None) -> None:
    """Eagerly run the compiled check of whichever kernel family the env
    knobs (or an explicit strategy= argument) select, and of the two
    kernels a default path takes at the widths the chip stores
    column-major (adagrad's tile stream, and the dense aggregate's
    resident kernel under adagrad and adam, at each width it has a walk
    for; `kind`: the sparse optimizer about to be built), once per width
    class, before a train step is traced. A no-op off-TPU and where
    nothing dispatches to a kernel. Wired into make_sparse_train_step
    and DistributedEmbedding construction, so user code need not call it.

    `widths`: the table lane widths the caller will dispatch at (the
    layer/step factories pass their plan's bucket+row widths); None
    checks the two cells' lane classes (16, 128). The per-row DMA family
    can address only some widths and raises here, before any step runs,
    for one it cannot (`pallas_scatter.check_row_dma`)."""
    if jax.default_backend() != "tpu":
        return
    impl = os.environ.get("DET_SCATTER_IMPL", "xla")
    lookup = os.environ.get("DET_LOOKUP_PATH", "auto")
    widths = tuple(widths or (16, 128))
    checks = []
    if impl == "tiled" or strategy == "tiled" or lookup == "tiled":
        checks.append(_TILED_CHECK)
    if impl == "pallas" or strategy == "pallas" or lookup == "fused":
        checks.append(_PALLAS_FUSED_CHECK)
    if impl == "pallas-dma":
        from distributed_embeddings_tpu.ops import pallas_scatter as ps
        for w in widths:
            ps.check_row_dma("DET_SCATTER_IMPL=pallas-dma "
                             "(pallas_scatter.scatter_add_sorted_unique)",
                             w, jnp.float32)
        checks.append(_PALLAS_DMA_CHECK)
    for check in checks:
        for w in sorted({_width_class(w) for w in widths}):
            check.prevalidate(w)
    # the default path's own kernel: `_tile_stream` asks the same of the
    # request; rows and id counts are the trace's to know. It runs where
    # a chip is attached to run it on: a step compiled for a described
    # chip (tests/test_chip_compile.py, benchmark.tools.describe_chip)
    # has the backend answered for it and no device
    if (kind in ("adagrad", "adam") and strategy in (None, "auto")
            and _scatter_route("auto") == "xla"
            and jax.devices()[0].platform == "tpu"):
        lane_widths = sorted({w for w in widths if _lane_width(w)})
        if kind == "adagrad":
            for w in sorted({_width_class(w) for w in lane_widths}):
                _TILE_STREAM_CHECK.prevalidate(w)
        # both optimizers' dense branch sums through `_dense_sum`, whose
        # kernel takes the widths that `dense_sum_blocks` has a walk for
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        for w in lane_widths:
            if ptl.dense_sum_blocks(_DENSE_SUM_CHECK_ROWS, w) is not None:
                _DENSE_SUM_CHECK.prevalidate(w)


def _static_float(x):
    """float(x) when x is compile-time static (Python scalar or concrete
    array); None when traced — Pallas kernel hyperparameters must be
    static, so traced values route callers to the XLA path."""
    try:
        return float(x)
    except Exception:  # noqa: BLE001 - ConcretizationTypeError et al.
        return None


def _row_scatter_add(table: jax.Array, rep: jax.Array,
                     delta: jax.Array) -> jax.Array:
    """table[rep] += delta for dedup output (unique rep; OOB fillers carry
    zero delta). Routes to the per-row DMA RMW kernel under
    DET_SCATTER_IMPL=pallas-dma; default is the flagged XLA scatter."""
    if _scatter_env("pallas-dma"):
        from distributed_embeddings_tpu.ops import pallas_scatter as ps
        return ps.scatter_add_sorted_unique(
            table, rep, delta.astype(table.dtype))
    return table.at[rep].add(delta.astype(table.dtype), mode="drop",
                             **DEDUP_FLAGS)


def take_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Row gather via raw lax.gather with PROMISE_IN_BOUNDS: emits no
    bounds-check constants, so it is legal inside `compute_on` host regions
    on host-memory operands (jnp.take's clamp constants live in device space
    and trip XLA's memory-space checker). Caller must pre-clamp ids."""
    dn = lax.GatherDimensionNumbers(offset_dims=(1,), collapsed_slice_dims=(0,),
                                    start_index_map=(0,))
    return lax.gather(table, ids[:, None], dn,
                      slice_sizes=(1, table.shape[1]),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def scatter_add_rows(table: jax.Array, ids: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """Row scatter-add, PROMISE_IN_BOUNDS (see take_rows). Caller must
    pre-clamp ids and zero any masked rows."""
    dn = lax.ScatterDimensionNumbers(update_window_dims=(1,),
                                     inserted_window_dims=(0,),
                                     scatter_dims_to_operand_dims=(0,))
    return lax.scatter_add(table, ids[:, None], rows.astype(table.dtype), dn,
                           mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


class SparseRowGrad(NamedTuple):
    """Per-contribution gradient for one table (shard): row `ids[n]` received
    gradient row `contribs[n]`. Duplicate ids allowed; padded slots must
    carry zero contribs (any id) or id >= V (dropped on scatter)."""
    ids: jax.Array       # [N] int32
    contribs: jax.Array  # [N, w]


def concat_grads(grads) -> "SparseRowGrad":
    grads = list(grads)
    if len(grads) == 1:
        return grads[0]
    return SparseRowGrad(
        jnp.concatenate([g.ids for g in grads]),
        jnp.concatenate([g.contribs for g in grads], axis=0))


def _shift(a: jax.Array, d: int) -> jax.Array:
    """out[i] = a[i - d] along axis 0 (d of either sign), zeros moved in.
    One pad with a negative edge: the chip's compiler fuses it into its
    consumer, where a slice followed by a pad is copied out first."""
    edges = [(d, -d, 0)] + [(0, 0, 0)] * (a.ndim - 1)
    return lax.pad(a, jnp.zeros((), a.dtype), edges)


@staged("dedup")
def dedup_sum(ids: jax.Array, contribs: jax.Array, sentinel: int,
              presorted=None):
    """Aggregate duplicate row ids: returns (rep_ids [N], sums [N, w]) where
    segment s's id sits at rep_ids[s] with its total in sums[s]; unused slots
    carry rep_ids >= sentinel (dropped by the subsequent scatter).

    Sort by id, derive exact integer segment indices from the sorted key
    boundaries, and sum each run of the permuted rows in place with a
    segmented doubling scan: at level d = 1, 2, 4, ... slot i adds slot
    i - d's partial sum if that slot lies in i's run, so after log2(N)
    levels a run's last slot holds its total — a pairwise tree of rounded
    f32 adds over the run's own rows, every duplicate summed (the
    reference's sort+unique+sum contract, .cu:645-661). As many levels of
    conditional shifts then move the totals, and their ids, to the front.
    Nothing scatters or gathers an [N, w] array after the sort's
    permutation: on the TPU a scattered narrow row costs ~100 ns and a
    gathered one 23-47 (PERF.md sections 5 and 6), a level of either
    network one streamed pass of under a millisecond. The
    jax.ops.segment_sum this replaced (ISSUE 31) was a sorted-dupes scatter
    underneath, 311 ms of Tiny V3's 1,236 ms step (ledger, PR 28). (A
    whole-stream cumsum differenced at the run ends would also avoid the
    scatter, but loses ~N*eps relative precision at N in the millions.)

    `presorted` optionally carries this id stream's sort artifacts (an
    `embedding_ops.GroupSort` — sid/perm/seg_start under the SAME canonical
    key with `rows == sentinel`) from an earlier sort, e.g. the tapped
    forward's (TapResiduals): the dedup then runs zero sort ops and is
    bit-identical to the fresh-sort path, the analogue of the reference
    backward reusing forward-sorted ids (.cu:706-773).

    rep is STRICTLY INCREASING by construction: real segments carry the
    sorted unique ids (any OOB inputs are pre-collapsed onto `sentinel`,
    keeping one dropped segment), and each unused slot s carries
    `sentinel + s` — still out of bounds, but never equal to another slot.
    Downstream scatters/gathers may therefore promise
    ``unique_indices=True, indices_are_sorted=True``, which matters: the
    round-3 TPU prims data measured XLA's duplicate-safe scatter lowering
    at ~100-280 ns/row — the single dominant cost of the whole train step.
    (Requires sentinel + N < 2^31; per-shard vocab always satisfies this.)
    """
    n = ids.shape[0]
    iota = lax.iota(jnp.int32, n)
    if presorted is not None:
        sid, perm, is_start = (presorted.sid, presorted.perm,
                               presorted.seg_start)
    else:
        # collapse BOTH invalid sides onto the sentinel: a plain min() would
        # let negative ids through, and JAX scatters treat negative indices
        # as NumPy-style from-the-end (mode="drop" only drops ids outside
        # [-V, V)), silently updating the TAIL of the table (ADVICE r3)
        ids32 = ids.astype(jnp.int32)
        keys = jnp.where(ids32 < 0, jnp.int32(sentinel),
                         jnp.minimum(ids32, jnp.int32(sentinel)))
        sid, perm = lax.sort_key_val(keys, iota)
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    rows = jnp.take(contribs, perm, axis=0)
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1      # exact int prefix
    # slot i sits `off[i]` slots after the start of its run
    off = iota - lax.cummax(jnp.where(is_start, iota, -1))
    x = rows.astype(jnp.float32)
    d = 1
    while d < n:
        # a run is contiguous: slot i - d is in i's run iff off[i] >= d
        x = x + jnp.where((off >= d)[:, None], _shift(x, d), 0.0)
        d *= 2
    # x[i] = its run's sum up to i, so a run's last slot holds the total.
    # Segment s's total sits at the slot that ends its run and belongs at
    # slot s, `k` slots further down. k never decreases along the stream,
    # so moving the totals down by k's binary digits, lowest first, never
    # lands one on another that still has to stay (the compress network of
    # Hacker's Delight 7-4): log2(n) streamed passes instead of a gather,
    # which costs this column-major stream 38-47 ns a slot when its
    # indices are in order (PERF.md section 6, PR 31)
    is_end = jnp.concatenate([is_start[1:], jnp.ones((1,), bool)])
    k = jnp.where(is_end, iota - seg, 0)
    d = 1
    while d < n:
        go = (k & d) != 0
        come = _shift(go, -d)
        x = jnp.where(come[:, None], _shift(x, -d), x)
        sid = jnp.where(come, _shift(sid, -d), sid)
        k = jnp.where(come, _shift(k, -d), jnp.where(go, 0, k))
        d *= 2
    real = iota <= seg[-1]
    sums = jnp.where(real[:, None], x, 0.0)
    rep = jnp.where(real, sid, jnp.int32(sentinel) + iota)
    return rep, sums.astype(contribs.dtype)


# What XLA's scatter-add into a target of some ten thousand rows costs on
# a v5e chip: it goes by the ROW, whatever the ids, and by which of two
# forms the compiler gives it. With the count's column 9 or 17 floats wide
# (widths 8 and 16) 18.3 ns a row: 49.2 and 46.6 ms for 2.69M and 2.56M
# rows, the padded copy of the stream and the compiler's sort of the ids
# included (in Tiny V3's step 37.0 + 4.2 + 4.0 ms, ledger, PRs 28-40). At
# widths 32-104 7.6-8.4 ns a row. Read by `tools/tpu_dense_sum_sweep.py`
# (PERF.md section 6, PR 41); width 24 read 18.3 over 1.0M rows and 9.7
# over 2.5M and takes the lower price, under which the kernel runs only
# where it wins against either. The resident kernel's price goes by the
# pair (`pallas_tiled.dense_sum_pair_ns`); the two decide, call by
# call, which of them sums a stream (`_dense_walk`).
def _scatter_ns_per_row(width: int) -> float:
    return 18.3 if width <= 16 else 8.0


def _dense_walk(rows: int, width: int, n: int):
    """(chunk, tile, most pairs) of the resident kernel's walk
    (`pallas_tiled.dense_sum`) of n id slots into a [rows, width] target,
    from the shapes alone; None where no stream reaches the kernel
    whatever its ids: a target the chip does not store column-major
    (`_lane_width`: the stream's transpose is a bitcast there and the
    target's rows lie on the lanes), one that does not fit the kernel's
    share of fast memory beside a pair's operands, or a stream under one
    chunk of slots (the scatter of so few costs what a kernel's launch
    does: 19 us at 1,024 rows by `_scatter_ns_per_row`, computed and not
    read on the chip; and the kernel would multiply padding). The same
    floor is what lets a step of a few hundred id slots lower for CPU
    devices with the backend answered "tpu", as
    `tests/benchmark/test_benchmark_datadriven.py` stands a chip in:
    `_dense_kernel` asks the backend alone. The kernel's price goes by
    the (chunk, tile) pair where the scatter's goes by the row, so `most
    pairs` is the count at which the two cost the same for these
    shapes."""
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    blocks = ptl.dense_sum_blocks(rows, width) if _lane_width(width) else None
    if blocks is None or n < blocks[0]:
        return None
    chunk, tile = blocks
    return chunk, tile, int(_scatter_ns_per_row(width) * n
                            / ptl.dense_sum_pair_ns(chunk, tile, width))


def _dense_kernel(strategy: str, rows: int, width: int, n: int):
    """`_dense_walk`'s answer where `_dense_sum` may hand its stream to the
    resident kernel, None where XLA's scatter-add is all there is. By
    what the code sees, never by a request: a TPU and shapes the kernel
    takes. An explicit strategy keeps the XLA lines, the reference the
    kernel is held to. That much is static; how many pairs a call walks
    is the stream's to say (a feature-major stream, `feature_major_stream`,
    names one table's rows in a chunk, a batch-major one every table's),
    and `_dense_sum` compares a call's own count with the walk's most."""
    if strategy != "auto" or _scatter_route(strategy) != "xla":
        return None
    if jax.default_backend() != "tpu":
        return None
    return _dense_walk(rows, width, n)


def dense_sum_pairs(ids: jax.Array, rows: int, width: int):
    """(pairs, kernel) of one call of `_dense_sum` over this id stream
    into a [rows, width] target on a TPU: the (chunk, tile) pairs the
    resident kernel would walk, and whether that is few enough for it to
    run (1) and not XLA's scatter-add (0). Forward only and jittable, as
    `dup_share` is; (0, 0) where no stream reaches the kernel whatever
    its ids (`_dense_walk`)."""
    walk = _dense_walk(rows, width, ids.shape[0])
    if walk is None:
        return jnp.int32(0), jnp.int32(0)
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    chunk, tile, most = walk
    pairs = ptl.dense_sum_walk(ids, rows, chunk, tile)[3]
    return pairs, (pairs <= most).astype(jnp.int32)


def _scatter_sum(ids, contribs, rows):
    """`_dense_sum` as one WIDENED XLA scatter-add: each contribution row
    is extended with a 1.0 count column, so that the count comes out of
    the same scatter as the data."""
    w = contribs.shape[-1]
    ext = jnp.concatenate(
        [contribs.astype(jnp.float32),
         jnp.ones((contribs.shape[0], 1), jnp.float32)], axis=1)
    # negative ids would wrap NumPy-style onto the table tail (see
    # dedup_sum); route them to the dropped OOB row instead
    safe_ids = jnp.where(ids < 0, rows, ids)
    dense_ext = jnp.zeros((rows, w + 1), jnp.float32).at[safe_ids].add(
        ext, mode="drop")
    return dense_ext[:, :w], dense_ext[:, w]


@staged("dedup")
def _dense_sum(ids, contribs, rows, strategy: str = "auto"):
    """[V, w] dense aggregation: every contribution summed into its row
    (ids that are negative or >= rows dropped), plus a row contribution
    COUNT so the updater can skip untouched rows (and so per-device
    partial aggregates can be psummed before thresholding — the hot-row
    shard's replicated update does exactly that). Returns (g [rows, w],
    counts [rows] f32).

    Two implementations of the one aggregate. XLA's scatter-add
    (`_scatter_sum`) is the CPU's, a wide target's and every explicit
    strategy's, and what the other is held to; on the chip it is paid by
    the ROW whatever the ids, 18 ns at widths 8 and 16 and 8 ns at 32 and
    wider (`_scatter_ns_per_row`; the round-3 figure of 55-106 ns is a
    row into a large table). Where the target is small and column-major
    on a TPU (`_dense_kernel`) a Pallas kernel keeps it in fast memory,
    rows on the lanes, and sums each chunk of the stream into the tiles
    its ids can name by a one-hot
    product, in the order the stream arrives: no sort, no permutation,
    no second copy of the stream. Its price goes by the (chunk, tile)
    pair, so a min/max pass over the ids counts the pairs first and a
    stream that spreads every chunk over the whole target (a batch-major
    one) keeps the scatter: both are compiled, `lax.cond` picks, the
    results agree to an f32 sum's order (counts exactly)."""
    kernel = _dense_kernel(strategy, rows, contribs.shape[-1], ids.shape[0])
    if kernel is None:
        return _scatter_sum(ids, contribs, rows)
    from distributed_embeddings_tpu.ops import pallas_tiled as ptl
    chunk, tile, most = kernel
    kids, lo, hi, pairs = ptl.dense_sum_walk(ids, rows, chunk, tile)
    return lax.cond(
        pairs <= most,
        lambda: ptl.dense_sum(kids, lo, hi, contribs, rows, tile),
        lambda: _scatter_sum(ids, contribs, rows))


@staged("apply")
def apply_dense_rows(kind: str, table, state, g, touched, lr, **hp):
    """Apply a DENSE aggregated gradient `g` [rows, w] with a boolean
    `touched` row mask to a (small) table + optimizer state — the exact
    masked-dense rules of sparse_sgd/adagrad/adam's 'dense' strategy,
    factored so the hot-row shard's replicated update (which must psum
    per-device dense partials BEFORE applying) shares one set of numerics
    with the dense aggregation strategy. Returns (table, state)."""
    t = touched[:, None]
    if kind == "sgd":
        # untouched rows carry g == 0: the add is the identity there
        return table + (-lr * g).astype(table.dtype), tuple(state)
    if kind == "adagrad":
        (acc,) = state
        eps = hp.get("eps", 1e-10)
        acc_new = acc + jnp.where(t, g * g, 0.0)
        upd = jnp.where(t, -lr * g * lax.rsqrt(acc_new + eps), 0.0)
        return table + upd.astype(table.dtype), (acc_new,)
    if kind == "adam":
        mu, nu, count = state
        b1 = hp.get("b1", 0.9)
        b2 = hp.get("b2", 0.999)
        eps = hp.get("eps", 1e-8)
        count = count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        mu_new = jnp.where(t, b1 * mu + (1 - b1) * g, mu)
        nu_new = jnp.where(t, b2 * nu + (1 - b2) * g * g, nu)
        upd = jnp.where(t, -lr * (mu_new / c1)
                        / (jnp.sqrt(nu_new / c2) + eps), 0.0)
        return table + upd.astype(table.dtype), (mu_new, nu_new, count)
    raise ValueError(f"Unknown sparse optimizer {kind!r}")


def _pick(strategy: str, rows: int, width: int) -> str:
    if strategy != "auto":
        return strategy
    return "dense" if rows * width <= DENSE_ELEMS_MAX else "sort"


def _usable_presorted(presorted, grad: SparseRowGrad, rows: int):
    """The given GroupSort, or None when it cannot serve this grad: the
    artifact must cover exactly this id stream (same static length). A
    mismatched artifact (e.g. a per-group sort offered against a
    multi-group concat) degrades to the fresh-sort path rather than
    corrupting the update."""
    if presorted is None or presorted.sid.shape[0] != grad.ids.shape[0]:
        return None
    return presorted


def dup_share(sort, rows: int) -> jax.Array:
    """1 - distinct rows / valid slots of a sorted id stream (a
    `GroupSort` under `rows`' canonical key, any leading axes): the share
    of the contributions that a duplicate sum folds into a row another
    slot already names. What `dedup_sum`'s scan, or the tile stream's
    one-hot product, aggregates; 0.0 for a stream of distinct ids, and
    for one with no valid slot."""
    valid = sort.sid < rows
    distinct = jnp.sum(sort.seg_start & valid)
    return 1.0 - distinct / jnp.maximum(jnp.sum(valid), 1)


# ------------------------------------------------------------------ SGD
@staged("apply")
def sparse_sgd(table: jax.Array, grad: SparseRowGrad, lr,
               strategy: str = "auto", presorted=None) -> jax.Array:
    """table[ids] -= lr * contribs. Under 'auto'/'dense', duplicates need
    no aggregation (add is associative) and the plain duplicate-safe
    scatter runs; OOB/padded ids are dropped. The EXPLICIT 'sort'
    strategy — and the fused 'pallas' strategy built on its aggregation
    — dedups first (one segment-sum total per row, the reference's
    unique-grad contract): the sort aggregation IS the strategy, it
    consumes the folded forward sort, and it is the seam that makes the
    fused pallas step bit-exact against the XLA sort path (ISSUE 12 —
    duplicate-heavy streams see last-ulp differences vs the sequential
    scatter, within every documented tolerance). `presorted` (GroupSort)
    feeds the tiled/pallas sorted stream and the sort-strategy dedup;
    'auto''s scatter ignores it."""
    rows = table.shape[0]
    ps = _usable_presorted(presorted, grad, rows)
    route = _scatter_route(strategy)
    if route == "tiled":
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        return ptl.tiled_sgd(table, grad.ids, grad.contribs, lr,
                             presorted=(None if ps is None
                                        else (ps.sid, ps.perm)))
    if route == "pallas" or strategy == "sort":
        rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                              presorted=ps)
        if route == "pallas":
            from distributed_embeddings_tpu.ops import pallas_tiled as ptl
            return ptl.tiled_sgd_rows(table, rep, sums, lr)
        return table.at[rep].add((-lr * sums).astype(table.dtype),
                                 mode="drop", **DEDUP_FLAGS)
    # negative ids -> dropped OOB row, not NumPy wraparound (see dedup_sum)
    safe_ids = jnp.where(grad.ids < 0, table.shape[0], grad.ids)
    return table.at[safe_ids].add(
        (-lr * grad.contribs.astype(jnp.float32)).astype(table.dtype),
        mode="drop")


# -------------------------------------------------------------- Adagrad
@staged("apply")
def sparse_adagrad(table: jax.Array, accum: jax.Array, grad: SparseRowGrad,
                   lr, eps: float = 1e-10, strategy: str = "auto",
                   presorted=None):
    """Row-wise adagrad matching optax.adagrad on the touched rows:
        acc[r]   += (sum of contribs for r)^2
        table[r] -= lr * sum / sqrt(acc[r] + eps)
    Duplicates are aggregated first (the reference's unique-grad contract).
    `presorted` (GroupSort over this id stream, rows == table.shape[0])
    removes the sort from both the tiled kernel and the dedup pass —
    bit-identical results either way. Returns (new_table, new_accum).
    """
    rows = table.shape[0]
    ps = _usable_presorted(presorted, grad, rows)
    route = _scatter_route(strategy)
    if route == "tiled" or _tile_stream(strategy, rows, table.shape[-1],
                                        grad.ids.shape[0]):
        # tiled one-hot-matmul kernel: sort + in-kernel aggregation, no
        # dedup pass, no scatter (see ops/pallas_tiled.py). Explicit
        # strategy="tiled" runs in interpret mode off-TPU (tests). With
        # no request it is what a narrow table's sort branch takes on a
        # TPU (`_tile_stream`): one in-place stream over the table and
        # its accumulator as the chip stores them, rows on the lanes,
        # each tile read and written once (ISSUE 33; 47 ms at Tiny V3's
        # bucket where the XLA lines below take 673: PERF.md section 6,
        # PR 33), a run of duplicates summed in the tile's one-hot
        # product, so that `dedup_sum`'s scan and compress levels are not
        # in the step (ISSUE 37)
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        return ptl.tiled_adagrad(table, accum, grad.ids, grad.contribs,
                                 lr, eps=eps,
                                 presorted=(None if ps is None
                                            else (ps.sid, ps.perm)))
    if route == "pallas":
        # fused sparse path (ISSUE 12): the EXACT dedup aggregation
        # (shared bit-for-bit with the sort path below, consuming the
        # folded forward sort) feeds one tile-walk RMW stream that reads
        # and writes each touched table+accumulator tile once — vs the
        # sort path's 2 scatters + 1 gather over the same rows.
        # Bit-exact vs the sort path (tests/test_pallas_fused.py).
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                              presorted=ps)
        return ptl.tiled_adagrad_rows(table, accum, rep, sums, lr,
                                      eps=eps)
    how = _pick(strategy, rows, table.shape[-1])
    if how == "dense":
        g, counts = _dense_sum(grad.ids, grad.contribs, rows, strategy)
        t_new, (acc_new,) = apply_dense_rows(
            "adagrad", table, (accum,), g, counts > 0, lr, eps=eps)
        return t_new, acc_new
    rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                          presorted=ps)
    lr_static = _static_float(lr)
    if _scatter_env("pallas-dma") and lr_static is not None:
        # fused RMW stream: one pass reads+updates table and accumulator
        # rows together (vs two scatters + a gather of the same rows).
        # lr must be compile-time static (kernel hyperparameter); a traced
        # lr (schedule passed through jit args) takes the XLA path
        from distributed_embeddings_tpu.ops import pallas_scatter as ps
        return ps.adagrad_rows_sorted_unique(table, accum, rep, sums,
                                             lr_static, eps)
    return _adagrad_rows_xla(table, accum, rep, sums, lr, eps)


def _adagrad_rows_xla(table, accum, rep, sums, lr, eps):
    """Adagrad over `dedup_sum`'s output as two row scatter-adds and the
    accumulator's re-read between them: the CPU's path, a wide table's,
    and what every kernel that takes (rep, sums) is held to."""
    rows = table.shape[0]
    # rep is strictly increasing (dedup_sum contract) => both scatter
    # promises hold; without them XLA's duplicate-safe lowering costs
    # ~100-280 ns/row on TPU (round-3 prims measurement)
    acc_new = _row_scatter_add(accum, rep, sums * sums)
    # gather with clamped index is safe: sentinel rows multiply a zero
    # update. Clamping collapses the dropped tail onto rows-1, so only the
    # sorted promise survives
    acc_rows = jnp.take(acc_new, jnp.minimum(rep, rows - 1), axis=0,
                        indices_are_sorted=True)
    delta = -lr * sums * lax.rsqrt(acc_rows + eps)
    return _row_scatter_add(table, rep, delta), acc_new


# ----------------------------------------------------------------- Adam
@staged("apply")
def sparse_adam(table: jax.Array, mu: jax.Array, nu: jax.Array, count,
                grad: SparseRowGrad, lr, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-8, strategy: str = "auto", presorted=None):
    """Lazy row-wise Adam: moments decay only on touched rows (the standard
    sparse-Adam compromise — identical to dense Adam when every row is
    touched every step; avoids O(V) work otherwise). `presorted`: see
    sparse_adagrad. Returns (table, mu, nu, count).
    """
    rows = table.shape[0]
    ps = _usable_presorted(presorted, grad, rows)
    route = _scatter_route(strategy)
    if route == "tiled":
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        return ptl.tiled_adam(table, mu, nu, count, grad.ids, grad.contribs,
                              lr, b1=b1, b2=b2, eps=eps,
                              presorted=(None if ps is None
                                         else (ps.sid, ps.perm)))
    if route == "pallas":
        # fused sparse path: exact dedup + one RMW stream over
        # table/mu/nu tiles (see sparse_adagrad); the kernel's count
        # column rebuilds the touched mask, so lazy moment decay is
        # bit-identical to the sort path's .at[rep].set
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                              presorted=ps)
        return ptl.tiled_adam_rows(table, mu, nu, count, rep, sums, lr,
                                   b1=b1, b2=b2, eps=eps)
    how = _pick(strategy, rows, table.shape[-1])
    if how == "dense":
        g, counts = _dense_sum(grad.ids, grad.contribs, rows, strategy)
        t_new, (mu_new, nu_new, count) = apply_dense_rows(
            "adam", table, (mu, nu, count), g, counts > 0, lr,
            b1=b1, b2=b2, eps=eps)
        return t_new, mu_new, nu_new, count
    count = count + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)
    rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                          presorted=ps)
    # scatter promises as in sparse_adagrad; clamped gathers keep the
    # sorted promise only
    safe = jnp.minimum(rep, rows - 1)
    # fp_round pins each moment product's rounding (no context-dependent
    # FMA fusion) — the identical pins live in the fused pallas kernels,
    # so the two strategies stay bit-exact (see fp_round). `count` is
    # traced in every jitted flow, making the pin opaque to the backend.
    # The square is parenthesized FIRST so neither side leaves the
    # association to a simplifier.
    zero = round_pin(count)
    mu_rows = (fp_round(b1 * jnp.take(mu, safe, axis=0,
                                      indices_are_sorted=True), zero)
               + fp_round((1 - b1) * sums, zero))
    nu_rows = (fp_round(b2 * jnp.take(nu, safe, axis=0,
                                      indices_are_sorted=True), zero)
               + fp_round((1 - b2) * fp_round(sums * sums, zero), zero))
    mu_new = mu.at[rep].set(mu_rows, mode="drop", **DEDUP_FLAGS)
    nu_new = nu.at[rep].set(nu_rows, mode="drop", **DEDUP_FLAGS)
    delta = -lr * (mu_rows / c1) / (jnp.sqrt(nu_rows / c2) + eps)
    return (table.at[rep].add(delta.astype(table.dtype), mode="drop",
                              **DEDUP_FLAGS),
            mu_new, nu_new, count)


# -------------------------- quantized (master-weight-free) row updates
# Optimizers whose quantized-table update is expressible row-wise without
# an f32 master copy of the TABLE: the update direction depends only on
# the aggregated gradient (+ f32 row-wise state), never on sub-grid-step
# table precision. Adam is deliberately absent — see quantized_row_update.
QUANTIZED_ROW_KINDS = ("sgd", "adagrad")


@staged("apply")
def quantized_row_update(kind: str, payload: jax.Array, scale: jax.Array,
                         state, grad: SparseRowGrad, store_dtype: str, lr,
                         eps: float = 1e-10, presorted=None):
    """Master-weight-free sparse update of a QUANTIZED table shard
    (ISSUE 17): decode ONLY the touched rows -> f32 optimizer math ->
    hash-SR re-encode, scattered back into the int8/fp8 payload and its
    per-row scale stack. No f32 shadow table ever exists, so a quantized
    HBM-resident bucket costs ~1/4 the f32 HBM with zero resident mirror.

    The optimizer state (adagrad's accumulator) stays full f32 — the
    master-weight-FREE claim is about the TABLE. SR (the wire seam's
    keyless hash, `wire.encode_rows(sr=True)`) centers the write-back
    rounding on zero across a step's many updated values; a zero-delta
    touched row round-trips exactly (the row amax element re-derives the
    identical scale).

    kind must be in QUANTIZED_ROW_KINDS. Adam REFUSES loudly: its
    per-element moment normalization produces effective steps orders of
    magnitude below the row's quantization grid (scale = amax/127), which
    systematically vanish under round-to-grid — SR preserves them only in
    expectation over many steps, exactly the early-training phase adam's
    bias correction depends on — and its two f32 moments already double
    the state, making the table saving marginal. Use f32 storage under
    adam, or a row-wise optimizer.

    Returns (payload, scale, state).
    """
    if kind not in QUANTIZED_ROW_KINDS:
        raise NotImplementedError(
            f"sparse optimizer {kind!r} has no master-weight-free "
            f"quantized-table update (available: {QUANTIZED_ROW_KINDS}); "
            "adam's moment-normalized steps fall below the row "
            "quantization grid — store this bucket at f32 or switch to "
            "sgd/row-wise adagrad")
    from distributed_embeddings_tpu.ops import wire as wire_ops
    rows = payload.shape[0]
    ps = _usable_presorted(presorted, grad, rows)
    rep, sums = dedup_sum(grad.ids, grad.contribs, sentinel=rows,
                          presorted=ps)
    # clamped gathers are safe: sentinel slots carry zero sums and their
    # scatter-back is dropped outright (rep >= rows under mode='drop')
    safe = jnp.minimum(rep, rows - 1)
    old = wire_ops.decode_rows(
        jnp.take(payload, safe, axis=0, indices_are_sorted=True),
        jnp.take(scale, safe, axis=0, indices_are_sorted=True),
        store_dtype)
    if kind == "sgd":
        new_rows = old - lr * sums
        new_state = tuple(state)
    else:  # adagrad — same accumulator math as sparse_adagrad's sort path
        (acc,) = state
        acc = _row_scatter_add(acc, rep, sums * sums)
        acc_rows = jnp.take(acc, safe, axis=0, indices_are_sorted=True)
        new_rows = old - lr * sums * lax.rsqrt(acc_rows + eps)
        new_state = (acc,)
    p_rows, s_rows = wire_ops.encode_rows(new_rows, store_dtype, sr=True)
    return (payload.at[rep].set(p_rows, mode="drop", **DEDUP_FLAGS),
            scale.at[rep].set(s_rows, mode="drop", **DEDUP_FLAGS),
            new_state)


# ------------------------------------- host-memory (offloaded) row updates
def prepare_safe_grad(ids: jax.Array, contribs: jax.Array, rows: int):
    """Dedup + make scatter-safe for PROMISE_IN_BOUNDS host scatters: padded
    segments get id 0 with zero sums (additive identity for sgd/adagrad),
    so no drop-mode bounds machinery (whose constants are illegal in host
    regions) is needed. Returns (rep [N] in-bounds, sums [N, w],
    valid [N] f32 mask) — non-additive rules (adam's moment decay) must
    mask with `valid`; padded slots alias row 0."""
    rep, sums = dedup_sum(ids, contribs, sentinel=rows)
    valid = rep < rows
    return (jnp.where(valid, rep, 0),
            jnp.where(valid[:, None], sums, 0.0),
            valid.astype(jnp.float32))


def host_sparse_sgd(table, state, rep, sums, valid, lr):
    """Additive row update in host memory (inside compute_on). Args from
    prepare_safe_grad; `valid` unused — padded slots carry zero sums, the
    additive identity."""
    del state, valid
    return scatter_add_rows(table, rep, -lr * sums), ()


def host_sparse_adagrad(table, state, rep, sums, valid, lr,
                        eps: float = 1e-10):   # = sparse_adagrad's default
    del valid                       # zero sums -> zero delta on row 0
    (acc,) = state
    acc = scatter_add_rows(acc, rep, sums * sums)
    acc_rows = take_rows(acc, rep)
    delta = -lr * sums * lax.rsqrt(acc_rows + eps)
    return scatter_add_rows(table, rep, delta), (acc,)


def host_sparse_adam(table, state, rep, sums, valid, lr, b1: float = 0.9,
                     b2: float = 0.999, eps: float = 1e-8):
    """Lazy row-wise adam in host memory, matching `sparse_adam` on touched
    rows. The moment decay is multiplicative, so it is expressed as a
    masked additive delta (gather old rows, scatter-add new-minus-old);
    deduped valid reps are unique, making the scatter-add exact. Masking is
    arithmetic (multiply by the f32 `valid`) — no select/clamp constants,
    which XLA's memory-space checker rejects inside host regions."""
    mu, nu, count = state
    count = count + 1
    cf = count.astype(jnp.float32)
    c1 = 1.0 - lax.pow(jnp.float32(b1), cf)
    c2 = 1.0 - lax.pow(jnp.float32(b2), cf)
    v = valid[:, None]
    mu_rows = take_rows(mu, rep)
    nu_rows = take_rows(nu, rep)
    mu_new_rows = b1 * mu_rows + (1.0 - b1) * sums
    nu_new_rows = b2 * nu_rows + (1.0 - b2) * sums * sums
    mu = scatter_add_rows(mu, rep, (mu_new_rows - mu_rows) * v)
    nu = scatter_add_rows(nu, rep, (nu_new_rows - nu_rows) * v)
    delta = -lr * (mu_new_rows / c1) / (jnp.sqrt(nu_new_rows / c2) + eps) * v
    return scatter_add_rows(table, rep, delta), (mu, nu, count)


HOST_SPARSE_APPLY = {"sgd": host_sparse_sgd, "adagrad": host_sparse_adagrad,
                     "adam": host_sparse_adam}


def host_apply_rows_inplace(kind: str, table, state, rep, sums, valid, lr,
                            reference: bool = False, **hp) -> None:
    """Apply one shard's deduped update rows to host-resident numpy buffers
    IN PLACE — the XLA-free twin of HOST_SPARSE_APPLY (same args, same
    numerics) used by the per-shard offload apply, where the table never
    enters an XLA program (see host_apply.cpp for why). `table` and the
    array leaves of `state` are mutated; adam's scalar count must be
    incremented by the CALLER (mirroring `count + 1` in host_sparse_adam).
    Runs the C++ kernels of native/host_apply.cpp, built on demand (a
    failed build raises); `reference=True` runs their numpy statement
    under the same input contract (tests)."""
    import numpy as np

    bad = [a.dtype for a in (table, *(s for s in state
                                      if getattr(s, "ndim", 0) >= 1))
           if a.dtype != np.float32]
    if bad:
        raise TypeError(
            f"host_apply_rows_inplace is float32-only, got {bad}; use the "
            "roundtrip offload apply (DET_HOST_APPLY=roundtrip) for "
            "non-f32 buckets")
    # the C++ kernels below consume raw .ctypes.data pointers with a dense
    # row-major stride assumption: a non-contiguous view here is silent
    # memory corruption, not an error (ADVICE r5) — refuse it up front, for
    # the numpy reference too, so both reject the same inputs
    noncontig = [name for name, a in
                 (("table", table),
                  *((f"state[{i}]", s) for i, s in enumerate(state)
                    if getattr(s, "ndim", 0) >= 1))
                 if not a.flags["C_CONTIGUOUS"]]
    if noncontig:
        raise ValueError(
            f"host_apply_rows_inplace requires C-contiguous buffers; "
            f"{noncontig} are not (pass np.ascontiguousarray copies and "
            "write them back, or fix the caller's layout)")
    n, w = sums.shape
    lr = float(lr)
    rep = np.ascontiguousarray(rep, dtype=np.int32)
    sums = np.ascontiguousarray(sums, dtype=np.float32)
    valid = np.ascontiguousarray(valid, dtype=np.float32)
    if kind == "set":
        # weight-streaming row SET (store/table_store.py delta apply):
        # `sums` carries replacement row VALUES, not gradients — valid
        # reps are unique, so a plain masked assignment is exact. Rides
        # this seam so offloaded-bucket delta consumption shares the
        # contiguity/dtype contract (and the shard-walk callers) of the
        # optimizer applies; trivially bandwidth-bound, so no C++ twin.
        ok_set = valid > 0.0
        table[rep[ok_set]] = sums[ok_set]
        return
    if reference:
        _host_apply_rows_numpy(kind, table, state, rep, sums, valid, lr, hp)
        return
    import ctypes
    from ..native import loader as _native_loader
    lib = _native_loader.load()      # builds on demand; a failed build raises

    def ptr(a):
        return ctypes.c_void_p(a.ctypes.data)

    if kind == "sgd":
        lib.ha_sgd(ptr(table), w, ptr(rep), ptr(sums), ptr(valid), n, lr)
    elif kind == "adagrad":
        (acc,) = state
        lib.ha_adagrad(ptr(table), ptr(acc), w, ptr(rep), ptr(sums),
                       ptr(valid), n, lr, float(hp.get("eps", 1e-10)))
    elif kind == "adam":
        mu, nu, count = state
        b1 = float(hp.get("b1", 0.9))
        b2 = float(hp.get("b2", 0.999))
        cf = float(count)             # already incremented by the caller
        lib.ha_adam(ptr(table), ptr(mu), ptr(nu), w, ptr(rep), ptr(sums),
                    ptr(valid), n, lr, b1, b2,
                    np.float32(1.0) - np.float32(b1) ** np.float32(cf),
                    np.float32(1.0) - np.float32(b2) ** np.float32(cf),
                    float(hp.get("eps", 1e-8)))
    else:
        raise NotImplementedError(
            f"no host-memory apply rule for optimizer {kind!r}")


def _host_apply_rows_numpy(kind, table, state, rep, sums, valid, lr, hp):
    """The plain numpy statement of the C++ row kernels — the reference
    tests/test_host_apply.py holds native/host_apply.cpp to
    (`host_apply_rows_inplace(..., reference=True)`)."""
    import numpy as np

    ok = valid > 0.0              # invalid slots alias row 0 with zero sums
    r = rep[ok]
    s = sums[ok]
    if kind == "sgd":
        np.add.at(table, r, (-lr * s).astype(np.float32))
    elif kind == "adagrad":
        (acc,) = state
        eps = np.float32(hp.get("eps", 1e-10))
        np.add.at(acc, r, s * s)
        np.add.at(table, r,
                  (-lr * s / np.sqrt(acc[r] + eps)).astype(np.float32))
    elif kind == "adam":
        mu, nu, count = state
        b1 = np.float32(hp.get("b1", 0.9))
        b2 = np.float32(hp.get("b2", 0.999))
        eps = np.float32(hp.get("eps", 1e-8))
        cf = np.float32(count)
        c1 = np.float32(1.0) - b1 ** cf
        c2 = np.float32(1.0) - b2 ** cf
        mu_new = b1 * mu[r] + (np.float32(1.0) - b1) * s
        nu_new = b2 * nu[r] + (np.float32(1.0) - b2) * s * s
        mu[r] = mu_new            # valid reps are unique: plain set is exact
        nu[r] = nu_new
        np.add.at(
            table, r,
            (-lr * (mu_new / c1) / (np.sqrt(nu_new / c2) + eps)).astype(
                np.float32))
    else:
        raise NotImplementedError(
            f"no host-memory apply rule for optimizer {kind!r}")


# ------------------------------------------------- optimizer description
class SparseOptimizer(NamedTuple):
    """A (init, update) pair over a single table shard; `update` consumes a
    SparseRowGrad (plus an optional `presorted` GroupSort of its id
    stream — the sort-folding seam). `kind` selects the rule; hyper-params
    are closed over (and kept in `lr`/`hp` for the host-offload apply
    path)."""
    kind: str
    init: callable       # table -> state pytree (tuple)
    update: callable     # (table, state, SparseRowGrad, presorted=None)
    lr: Any = 0.0        #   -> (table, state)
    hp: tuple = ()       # sorted (key, value) pairs
    strategy: str = "auto"


def update_consumes_sort(kind: str, strategy: str, rows: int,
                         width: int) -> bool:
    """Static answer to "would `SparseOptimizer.update` use a presorted
    GroupSort for a [rows, width] shard?" — mirrors the dispatch in
    sparse_sgd/adagrad/adam exactly, so forwards can decide at trace time
    whether producing the artifact is worthwhile (an unconsumed sort is
    not free: DCE does not reach through shard_map boundaries)."""
    # one routing function with the actual dispatch: both kernel families
    # consume the sorted stream
    route = _scatter_route(strategy)
    if route in ("pallas", "tiled"):
        return True                      # tile walks take (sid, perm)
    if _pick(strategy, rows, width) == "dense":
        return False                     # dense path aggregates scatterwise
    if kind == "sgd":
        # only the EXPLICIT sort strategy dedups for sgd (aggregate-first
        # seam, see sparse_sgd); auto's plain scatter needs no order
        return strategy == "sort"
    return kind in ("adagrad", "adam")


def make_sparse_optimizer(kind: str, lr, strategy: str = "auto",
                          **hp) -> SparseOptimizer:
    """kind in {'sgd', 'adagrad', 'adam'}; mirrors the optax rules used by
    the examples (reference synthetic main.py sgd/adagrad/adam flags)."""
    hp_t = tuple(sorted(hp.items()))
    if kind == "sgd":
        return SparseOptimizer(
            "sgd", lambda table: (),
            lambda table, state, g, presorted=None: (
                sparse_sgd(table, g, lr, strategy=strategy,
                           presorted=presorted), ()),
            lr, hp_t, strategy)
    if kind == "adagrad":
        init_acc = hp.get("initial_accumulator_value", 0.1)
        eps = hp.get("eps", 1e-10)

        def init(table):
            return (jnp.full(table.shape, init_acc, jnp.float32),)

        def update(table, state, g, presorted=None):
            t, acc = sparse_adagrad(table, state[0], g, lr, eps=eps,
                                    strategy=strategy, presorted=presorted)
            return t, (acc,)
        return SparseOptimizer("adagrad", init, update, lr, hp_t, strategy)
    if kind == "adam":
        b1, b2 = hp.get("b1", 0.9), hp.get("b2", 0.999)
        eps = hp.get("eps", 1e-8)

        def init(table):
            return (jnp.zeros(table.shape, jnp.float32),
                    jnp.zeros(table.shape, jnp.float32),
                    jnp.zeros((), jnp.int32))

        def update(table, state, g, presorted=None):
            t, mu, nu, c = sparse_adam(table, state[0], state[1], state[2],
                                       g, lr, b1=b1, b2=b2, eps=eps,
                                       strategy=strategy,
                                       presorted=presorted)
            return t, (mu, nu, c)
        return SparseOptimizer("adam", init, update, lr, hp_t, strategy)
    raise ValueError(f"Unknown sparse optimizer {kind!r}")


def drain_sparse_apply(emb, params_emb, state_emb, tap_grads, residuals,
                       opt, off_buckets=()):
    """Drain-stage entry (ISSUE 9): apply one batch's tap gradients to the
    embedding tables — the tail every train-step variant shares.

    Two producers feed it: the monolithic `make_sparse_train_step`, where
    autodiff delivered `tap_grads` (the backward already ran the dp->mp
    gradient transpose inside the custom-vjp exchange), and the lookahead
    pipeline (`schedule.LookaheadEngine`), where the engine's explicit
    `DistributedEmbedding.exchange_transpose` did. Both hand the exact
    `make_taps`-shaped pytree; the update itself is the layer's
    `sparse_update`.

    `off_buckets` slots of the RETURNED pytrees are zeroed out: host-
    resident leaves must never be jit outputs (XLA:CPU SPMD cannot place
    them; TPU would copy them device-ward) — the caller replaces those
    slots with the out-of-jit host-apply results, driven by the returned
    `pending` dict (see `make_sparse_train_step`).

    Returns (new_params_emb, new_state_emb, pending).
    """
    new_emb, new_state, pending = emb.sparse_update(
        params_emb, state_emb, tap_grads, residuals, opt)
    for b in off_buckets:
        new_emb["tp"][b] = jnp.zeros((0,), jnp.float32)
        if new_emb.get("tp_scale") is not None \
                and new_emb["tp_scale"][b] is not None:
            # the quantized bucket's scale stack is a host leaf too: a jit
            # output of it lands in DEVICE memory, and the next step's
            # host-region gather then mixes memory spaces
            new_emb["tp_scale"][b] = jnp.zeros((0,), jnp.float32)
        new_state["tp"][b] = jax.tree.map(
            lambda _: jnp.zeros((0,), jnp.float32), new_state["tp"][b])
    return new_emb, new_state, pending
