"""Sparse row-wise optimizer updates vs dense reference (optax formulas).

The contract (reference: IndexedSlices consumption of the grad kernel's
(unique_ids, unique_grads) output, embedding_lookup_ops.py:105-122): a sparse
update with per-contribution (ids, rows) must equal the dense update with the
scatter-added dense gradient, on every strategy.
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_embeddings_tpu.ops import sparse_update as su


def make_case(rng, n=257, v=50, w=8, oob=False):
    ids = rng.integers(0, v, size=(n,)).astype(np.int32)
    contribs = rng.standard_normal((n, w)).astype(np.float32)
    if oob:
        # padded slots: id == v with zero rows must be dropped
        ids[::7] = v
        contribs[::7] = 0.0
    dense = np.zeros((v, w), np.float32)
    np.add.at(dense, ids[ids < v], contribs[ids < v])
    return ids, contribs, dense


def test_dedup_sum_exact():
    rng = np.random.default_rng(0)
    ids, contribs, dense = make_case(rng)
    rep, sums = su.dedup_sum(jnp.asarray(ids), jnp.asarray(contribs),
                             sentinel=50)
    rep, sums = np.asarray(rep), np.asarray(sums)
    got = np.zeros_like(dense)
    for r, s in zip(rep, sums):
        if r < 50:
            got[r] += s
    np.testing.assert_allclose(got, dense, rtol=1e-6, atol=1e-6)
    # each id appears exactly once among rep
    real = rep[rep < 50]
    assert len(real) == len(set(real.tolist()))
    # promise contract: rep must be strictly increasing (unique AND sorted —
    # downstream scatters assert these to XLA; see DEDUP_FLAGS)
    assert (np.diff(rep.astype(np.int64)) > 0).all()


def _runs(lengths):
    """An id stream whose id r occurs lengths[r] times, shuffled."""
    ids = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    return np.random.default_rng(len(ids)).permutation(ids), len(lengths)


def _oob_ids():
    # negative and too-large ids share ONE dropped segment at the sentinel
    ids = np.random.default_rng(5).integers(0, 40, size=300).astype(np.int32)
    ids[::5] = -1 - ids[::5]
    ids[1::7] = 40 + ids[1::7]
    ids[2::11] = np.iinfo(np.int32).max
    return ids, 40


DEDUP_STREAMS = {
    "runs_of_1": lambda: _runs([1] * 333),
    "one_run": lambda: _runs([777]),
    "n_1": lambda: _runs([1]),
    "oob_onto_sentinel": _oob_ids,
    **{f"runs_near_2^{k}": (lambda k=k: _runs([2 ** k - 1, 2 ** k,
                                               2 ** k + 1]))
       for k in range(1, 12)},
}


@pytest.mark.parametrize("width", [8, 16, 128])
@pytest.mark.parametrize("stream", list(DEDUP_STREAMS))
def test_dedup_sum_scan(stream, width):
    """The segmented doubling scan's totals against a float64 np.add.at,
    each within run_length * eps * sum|x|, and dedup_sum's contract: rep
    strictly increasing, the real runs compacted at the front, every
    filler slot zero and out of bounds."""
    ids, sentinel = DEDUP_STREAMS[stream]()
    n = len(ids)
    contribs = np.random.default_rng(n + width).standard_normal(
        (n, width)).astype(np.float32)
    rep, sums = jax.jit(su.dedup_sum, static_argnames="sentinel")(
        jnp.asarray(ids), jnp.asarray(contribs), sentinel=sentinel)
    rep, sums = np.asarray(rep), np.asarray(sums)
    assert sums.dtype == np.float32 and sums.shape == (n, width)

    keys = np.where((ids < 0) | (ids > sentinel), sentinel, ids)
    uniq, inverse, counts = np.unique(keys, return_inverse=True,
                                      return_counts=True)
    want = np.zeros((len(uniq), width), np.float64)
    mass = np.zeros((len(uniq), width), np.float64)
    np.add.at(want, inverse, contribs.astype(np.float64))
    np.add.at(mass, inverse, np.abs(contribs).astype(np.float64))
    s = len(uniq)
    np.testing.assert_array_equal(rep[:s], uniq)
    bound = counts[:, None] * np.finfo(np.float32).eps * mass
    assert (np.abs(sums[:s] - want) <= bound).all()
    # a run of one is its contribution, to the bit
    single = counts == 1
    np.testing.assert_array_equal(
        sums[:s][single], contribs[np.argsort(keys, kind="stable")][
            np.cumsum(counts)[single] - 1])
    np.testing.assert_array_equal(rep[s:], sentinel + np.arange(s, n))
    assert not sums[s:].any()
    assert (np.diff(rep.astype(np.int64)) > 0).all()


@pytest.mark.parametrize("width", [8, 16, 128])
def test_dedup_sum_presorted_bit_identical(width):
    """A GroupSort made earlier (the forward's) serves the scan as a fresh
    sort does: same rep, same sums, to the bit."""
    from distributed_embeddings_tpu.ops.embedding_ops import (
        canonical_id_sort)
    ids, sentinel = _oob_ids()
    ids = np.concatenate([ids, _runs([129, 1, 64, 7])[0]])
    contribs = np.random.default_rng(width).standard_normal(
        (len(ids), width)).astype(np.float32)
    fresh = su.dedup_sum(jnp.asarray(ids), jnp.asarray(contribs),
                         sentinel=sentinel)
    folded = su.dedup_sum(
        jnp.asarray(ids), jnp.asarray(contribs), sentinel=sentinel,
        presorted=canonical_id_sort(jnp.asarray(ids), sentinel))
    for a, b in zip(fresh, folded):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dedup_sum_scatters_nothing():
    """The totals come from the scan and the shift network: the lowered
    program holds no scatter at all (the parent's held two: the segment-sum
    of the [n, w] contributions and the 1-D one that set rep) and one
    gather, the contributions by the sort's permutation. Keeps a later
    refactor from bringing the segment scatter-add back unseen."""
    text = jax.jit(su.dedup_sum, static_argnames="sentinel").lower(
        jax.ShapeDtypeStruct((4096,), jnp.int32),
        jax.ShapeDtypeStruct((4096, 16), jnp.float32),
        sentinel=1000).as_text()
    assert "4096x16xf32" in text and "stablehlo.sort" in text
    assert "scatter" not in text
    assert text.count('"stablehlo.gather"(') == 1


@pytest.mark.parametrize("strategy", ["sort", "dense"])
@pytest.mark.parametrize("oob", [False, True])
def test_sparse_adagrad_matches_optax(strategy, oob):
    rng = np.random.default_rng(1)
    ids, contribs, dense = make_case(rng, oob=oob)
    table = rng.standard_normal((50, 8)).astype(np.float32)
    lr, eps, acc0 = 0.05, 1e-7, 0.1

    opt = optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps)
    state = opt.init(jnp.asarray(table))
    upd, _ = opt.update(jnp.asarray(dense), state, jnp.asarray(table))
    want = np.asarray(jnp.asarray(table) + upd)

    t2, acc2 = su.sparse_adagrad(
        jnp.asarray(table), jnp.full((50, 8), acc0, jnp.float32),
        su.SparseRowGrad(jnp.asarray(ids), jnp.asarray(contribs)),
        lr, eps=eps, strategy=strategy)
    np.testing.assert_allclose(np.asarray(t2), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(acc2), acc0 + dense * dense,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("oob", [False, True])
def test_sparse_sgd_matches_dense(oob):
    rng = np.random.default_rng(2)
    ids, contribs, dense = make_case(rng, oob=oob)
    table = rng.standard_normal((50, 8)).astype(np.float32)
    got = su.sparse_sgd(jnp.asarray(table),
                        su.SparseRowGrad(jnp.asarray(ids),
                                         jnp.asarray(contribs)), 0.1)
    np.testing.assert_allclose(np.asarray(got), table - 0.1 * dense,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", ["sort", "dense"])
def test_sparse_adam_touched_rows_match_optax(strategy):
    """Lazy sparse Adam == dense Adam on rows where the dense grad is
    nonzero, over multiple steps with every row touched."""
    rng = np.random.default_rng(3)
    v, w = 30, 4
    table = rng.standard_normal((v, w)).astype(np.float32)
    lr = 0.01
    opt = optax.adam(lr)
    dstate = opt.init(jnp.asarray(table))
    dtable = jnp.asarray(table)

    sopt = su.make_sparse_optimizer("adam", lr, strategy=strategy)
    stable = jnp.asarray(table)
    sstate = sopt.init(stable)

    for step in range(3):
        # every row touched (ids = permutation + extras) so lazy == dense
        ids = np.concatenate([rng.permutation(v),
                              rng.integers(0, v, 17)]).astype(np.int32)
        contribs = rng.standard_normal((len(ids), w)).astype(np.float32)
        dense = np.zeros((v, w), np.float32)
        np.add.at(dense, ids, contribs)

        upd, dstate = opt.update(jnp.asarray(dense), dstate, dtable)
        dtable = dtable + upd
        stable, sstate = sopt.update(
            stable, sstate, su.SparseRowGrad(jnp.asarray(ids),
                                             jnp.asarray(contribs)))
        np.testing.assert_allclose(np.asarray(stable), np.asarray(dtable),
                                   rtol=3e-5, atol=3e-5,
                                   err_msg=f"step {step}")


def test_sparse_adagrad_untouched_rows_unchanged():
    rng = np.random.default_rng(4)
    table = rng.standard_normal((50, 8)).astype(np.float32)
    ids = jnp.asarray([3, 3, 7], jnp.int32)
    contribs = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    for strategy in ("sort", "dense"):
        t2, _ = su.sparse_adagrad(
            jnp.asarray(table), jnp.full((50, 8), 0.1, jnp.float32),
            su.SparseRowGrad(ids, contribs), 0.1, strategy=strategy)
        t2 = np.asarray(t2)
        mask = np.ones(50, bool)
        mask[[3, 7]] = False
        np.testing.assert_array_equal(t2[mask], table[mask])
        assert not np.allclose(t2[3], table[3])


def test_concat_grads_and_jit():
    rng = np.random.default_rng(5)
    g1 = su.SparseRowGrad(jnp.asarray(rng.integers(0, 20, 10), jnp.int32),
                          jnp.asarray(rng.standard_normal((10, 4)),
                                      jnp.float32))
    g2 = su.SparseRowGrad(jnp.asarray(rng.integers(0, 20, 6), jnp.int32),
                          jnp.asarray(rng.standard_normal((6, 4)),
                                      jnp.float32))
    g = su.concat_grads([g1, g2])
    assert g.ids.shape == (16,) and g.contribs.shape == (16, 4)

    table = jnp.asarray(rng.standard_normal((20, 4)), jnp.float32)
    acc = jnp.full((20, 4), 0.1, jnp.float32)
    f = jax.jit(lambda t, a, i, c: su.sparse_adagrad(
        t, a, su.SparseRowGrad(i, c), 0.1, strategy="sort"))
    t2, a2 = f(table, acc, g.ids, g.contribs)
    t3, a3 = su.sparse_adagrad(table, acc, g, 0.1, strategy="dense")
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t3), rtol=2e-5,
                               atol=2e-5)


def test_scatter_impl_pallas_ignored_off_tpu(monkeypatch):
    """DET_SCATTER_IMPL=pallas must be inert off-TPU (CPU tests and CPU
    meshes take the XLA scatter unconditionally)."""
    monkeypatch.setenv("DET_SCATTER_IMPL", "pallas")
    rng = np.random.default_rng(7)
    ids, contribs, _ = make_case(rng, n=129)
    table = rng.standard_normal((50, 8)).astype(np.float32)
    g = su.SparseRowGrad(jnp.asarray(ids), jnp.asarray(contribs))
    t1, a1 = su.sparse_adagrad(jnp.asarray(table),
                               jnp.full((50, 8), 0.1, jnp.float32), g, 0.05,
                               strategy="sort")
    monkeypatch.delenv("DET_SCATTER_IMPL")
    t2, a2 = su.sparse_adagrad(jnp.asarray(table),
                               jnp.full((50, 8), 0.1, jnp.float32), g, 0.05,
                               strategy="sort")
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_sparse_adagrad_traced_lr(monkeypatch):
    """lr as a traced value (schedule through jit args) must work on every
    path — the Pallas fused kernel needs static lr, so the dispatch falls
    back rather than crashing (review finding r03)."""
    monkeypatch.setenv("DET_SCATTER_IMPL", "pallas")
    rng = np.random.default_rng(13)
    ids, contribs, _ = make_case(rng, n=129)
    table = rng.standard_normal((50, 8)).astype(np.float32)
    g = su.SparseRowGrad(jnp.asarray(ids), jnp.asarray(contribs))

    @jax.jit
    def step(t, acc, lr):
        return su.sparse_adagrad(t, acc, g, lr, strategy="sort")

    t2, a2 = step(jnp.asarray(table), jnp.full((50, 8), 0.1, jnp.float32),
                  jnp.float32(0.05))
    want_t, want_a = su.sparse_adagrad(
        jnp.asarray(table), jnp.full((50, 8), 0.1, jnp.float32), g, 0.05,
        strategy="sort")
    np.testing.assert_allclose(np.asarray(t2), np.asarray(want_t),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(want_a),
                               rtol=1e-6, atol=1e-6)


# ---- the dense aggregate on a TPU: the resident kernel or the scatter
def _as_a_tpu(monkeypatch):
    """What a TPU backend is answered, on the CPU: the kernels in
    interpret mode."""
    from distributed_embeddings_tpu.ops import pallas_tiled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_tiled, "_BACKEND_INTERPRET", True)


def _bucket_streams(rng, rows, n, chunk):
    """Two streams of n slots into Tiny V3's width-8 bucket: runs of a
    chunk's slots inside one small table's rows (feature-major), and the
    same slots with the first and the last row in every chunk (what a
    batch-major stream does to a chunk's span)."""
    starts = rng.integers(0, rows - 1000, size=n // chunk)
    near = (np.repeat(starts, chunk)
            + rng.integers(0, 1000, size=n)).astype(np.int32)
    far = near.copy()
    far[::chunk], far[1::chunk] = 0, rows - 1
    return near, far


def test_dense_sum_picks_by_the_streams_span(monkeypatch):
    """The run-time rule of `_dense_sum` (ISSUE 41): the pairs a stream's
    min/max walk finds, against the count at which the kernel and XLA's
    scatter-add cost the same for the shapes. A stream whose chunks each
    name one table's rows takes the kernel, one whose chunks span the
    whole target the scatter; `dense_sum_pairs` says which, and either
    way the sums and counts are the scatter's."""
    rows, width, n = 60160, 8, 16384
    rng = np.random.default_rng(41)
    chunk, tile, most = su._dense_walk(rows, width, n)
    near, far = _bucket_streams(rng, rows, n, chunk)
    contribs = jnp.asarray(rng.standard_normal((n, width)), jnp.float32)
    n_chunks, n_tiles = n // chunk, -(-rows // tile)
    assert n_chunks * n_tiles > most > 2 * n_chunks
    pairs_near, kernel_near = su.dense_sum_pairs(jnp.asarray(near), rows,
                                                 width)
    pairs_far, kernel_far = su.dense_sum_pairs(jnp.asarray(far), rows, width)
    assert n_chunks <= int(pairs_near) <= 2 * n_chunks
    assert int(pairs_far) == n_chunks * n_tiles
    assert (int(kernel_near), int(kernel_far)) == (1, 0)
    _as_a_tpu(monkeypatch)
    traced = jax.make_jaxpr(lambda i, c: su._dense_sum(i, c, rows))(
        jnp.asarray(near), contribs)
    assert "cond" in str(traced) and "pallas_call" in str(traced)
    for ids in (near, far):
        ids = jnp.asarray(ids)
        g, counts = jax.jit(lambda i, c: su._dense_sum(i, c, rows))(
            ids, contribs)
        g_want, counts_want = su._scatter_sum(ids, contribs, rows)
        np.testing.assert_array_equal(counts, counts_want)
        np.testing.assert_allclose(g, g_want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,rows,width,strategy,kind", [
    ("tpu", 64, 128, "auto", "adagrad"),      # a row-major target
    # `lfm2.packed-4k`'s table: `sparse_adam`'s dense branch, 64 MB
    ("tpu", 8192, 2048, "auto", "adam"),
    ("cpu", 60160, 8, "auto", "adagrad"),
    ("tpu", 60160, 8, "sort", "adagrad"),     # a strategy is the request
    ("tpu", 60160, 8, "dense", "adam"),
    ("tpu", 1_000_000, 8, "auto", "adagrad"),  # 64 MB of target
])
def test_dense_sum_kernel_is_not_reached(backend, rows, width, strategy,
                                         kind, monkeypatch):
    """Who keeps XLA's scatter-add: a wide target, the CPU, an explicit
    strategy, a target over the kernel's share of fast memory, a stream
    of under one chunk. The rule
    answers None and the traced update holds no kernel; the Tiny bucket's
    shape under "auto" on a TPU is the control, for both optimizers."""
    _as_a_tpu(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n = 2048

    def traced(rows, width, strategy, kind):
        table = jnp.zeros((rows, width), jnp.float32)
        grad = su.SparseRowGrad(jnp.zeros((n,), jnp.int32),
                                jnp.ones((n, width), jnp.float32))
        if kind == "adam":
            return str(jax.make_jaxpr(lambda t, g: su.sparse_adam(
                t, t, t, jnp.int32(0), g, 0.01, strategy=strategy))(
                    table, grad))
        return str(jax.make_jaxpr(lambda t, g: su.sparse_adagrad(
            t, t, g, 0.01, strategy=strategy))(table, grad))

    assert su._dense_kernel(strategy, rows, width, n) is None
    assert "pallas_call" not in traced(rows, width, strategy, kind)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chunk, _, most = su._dense_kernel("auto", 60160, 8, n)
    assert most > n // chunk
    assert "pallas_call" in traced(60160, 8, "auto", kind)
    # a stream under one chunk of slots: the scatter costs a launch. The
    # same floor keeps `tests/benchmark/test_benchmark_datadriven.py::
    # test_describe_chip_lowers_the_cells_own_batch` lowering: it stands a
    # chip in by answering "tpu" over CPU devices, where no kernel can
    # lower, and its narrow adam cell has 256 id slots. Whoever shrinks
    # `_DENSE_CHUNK` under that meets it there
    assert su._dense_kernel("auto", 60160, 8, chunk - 1) is None
    assert su._dense_kernel("auto", 512, 16, 256) is None


# (width, rows, id slots, pairs walked, scatter ms, kernel ms) of
# `tools/tpu_dense_sum_sweep.py --widths 8,16,32,64,96,104` on one v5e chip
# (my run, PR 41): Tiny V3's width-8 bucket's tables, cut to what fits at
# the width, feature-major and then batch-major
SWEEP = [
    (8, 60160, 2686976, 12096, 49.2, 6.9),
    (8, 60160, 2686976, 153809, 49.2, 74.9),
    (16, 40160, 2555904, 10752, 46.6, 9.3),
    (16, 40160, 2555904, 97717, 46.6, 73.7),
    (32, 20160, 2424832, 17024, 18.6, 13.3),
    (32, 20160, 2424832, 94674, 18.4, 67.8),
    (64, 14160, 2031616, 30197, 16.2, 22.3),
    (64, 14160, 2031616, 110783, 16.1, 76.6),
    (96, 9160, 1638400, 6144, 13.5, 4.8),
    (96, 9160, 1638400, 114966, 13.5, 63.1),
    (104, 9160, 1638400, 6144, 13.7, 5.0),
    (104, 9160, 1638400, 114996, 13.5, 66.4),
]


@pytest.mark.parametrize("width,rows,n,pairs,scatter_ms,kernel_ms", SWEEP)
def test_dense_walk_prices_what_the_chip_read(width, rows, n, pairs,
                                              scatter_ms, kernel_ms):
    """`_dense_walk`'s two prices against the chip's readings: the
    scatter's by the row, the kernel's by the pair (less the walk's 1 ms,
    paid either way), each to 15%, and the count that decides between
    them picks the one that won: at every width but 8 a rule that priced
    the scatter at width 8's 1.7 ns an element picked the kernel for all
    of these, and it lost five of the ten."""
    from distributed_embeddings_tpu.ops import pallas_tiled
    chunk, tile, most = su._dense_walk(rows, width, n)
    assert su._scatter_ns_per_row(width) * n / 1e6 == pytest.approx(
        scatter_ms, rel=0.15)
    assert pairs * pallas_tiled.dense_sum_pair_ns(chunk, tile, width) / 1e6 \
        == pytest.approx(kernel_ms - 1.0, rel=0.15)
    assert (pairs <= most) == (kernel_ms < scatter_ms)
