"""Fused Pallas sparse path (ISSUE 12): DET_SCATTER_IMPL=pallas.

The contract under test: the fused strategy — exact `dedup_sum`
aggregation feeding one tile-walk RMW kernel per bucket
(ops/pallas_tiled.tiled_*_rows), plus the fused gather->combine forward
(fused_lookup_combine) — runs the full sparse train step BIT-exactly
against the XLA sort strategy (f32, interpret mode on CPU) across
sgd/adagrad/adam x padded/ragged exchange x hot-rows on/off, composes
with lookahead=1, and falls back LOUDLY (never silently) when its gate
fails. Bit-exactness rests on the shared dedup aggregation, exact
one-hot placement of unique rows, and the fp_round rounding pins (see
ops/sparse_update.fp_round).
"""


import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.ops import pallas_tiled as pt
from distributed_embeddings_tpu.ops import sparse_update as su
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import make_sparse_train_step

from test_sparse_train import TinyModel, BATCH

SPECS = [(96, 8, "sum"), (50, 8, "mean"), (70, 8, "sum")]


def _grad_case(seed, v=200, w=8, n=513):
    rng = np.random.RandomState(seed)
    ids = rng.randint(-5, v + 8, n).astype(np.int32)  # dupes + OOB both ways
    contribs = rng.randn(n, w).astype(np.float32)
    table = rng.randn(v, w).astype(np.float32)
    return (su.SparseRowGrad(jnp.asarray(ids), jnp.asarray(contribs)),
            jnp.asarray(table), v, w)


# ------------------------------------------------- kernel-level parity
@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
def test_pallas_strategy_update_bitexact_vs_sort(optimizer):
    """sparse_sgd/adagrad/adam(strategy='pallas') == strategy='sort'
    bit-for-bit under jit (traced ids keep the rounding pins opaque),
    over multiple accumulating steps."""
    g, table, v, w = _grad_case(3)

    def run(strategy):
        if optimizer == "sgd":
            f = jax.jit(lambda t, i, c: (su.sparse_sgd(
                t, su.SparseRowGrad(i, c), 0.05, strategy=strategy),))
            state = (table,)
        elif optimizer == "adagrad":
            f = jax.jit(lambda t, a, i, c: su.sparse_adagrad(
                t, a, su.SparseRowGrad(i, c), 0.05, strategy=strategy))
            state = (table, jnp.full((v, w), 0.1, jnp.float32))
        else:
            f = jax.jit(lambda t, m, u, c0, i, c: su.sparse_adam(
                t, m, u, c0, su.SparseRowGrad(i, c), 0.01,
                strategy=strategy))
            state = (table, jnp.zeros((v, w)), jnp.zeros((v, w)),
                     jnp.zeros((), jnp.int32))
        for _ in range(3):
            state = f(*state, g.ids, g.contribs)
        return state

    got = run("pallas")
    want = run("sort")
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{optimizer} leaf {i}")


def test_rows_appliers_exact_placement():
    """The deduped-row appliers place each unique row's total EXACTLY
    (one-hot matmul with a unique stream): sgd_rows at lr=-1 over a zero
    table reproduces the dedup sums bit-for-bit, fillers dropped."""
    g, table, v, w = _grad_case(5)
    rep, sums = su.dedup_sum(g.ids, g.contribs, sentinel=v)
    placed = pt.tiled_sgd_rows(jnp.zeros((v, w)), rep, sums, -1.0,
                               interpret=True)
    want = jnp.zeros((v, w)).at[rep].add(sums, mode="drop",
                                         **su.DEDUP_FLAGS)
    np.testing.assert_array_equal(np.asarray(placed), np.asarray(want))


# the rows-on-lanes tile stream (ISSUE 33): name -> (width, rows, raw ids).
# tile = chunk = 128 below, so that these small tables span several tiles
# and their streams several chunks
def _lane_cases():
    rng = np.random.RandomState(11)
    zipf = (rng.zipf(1.3, 700) - 1) % 1000
    return {
        "w16-rows-not-a-tile-multiple": (16, 1000, rng.choice(1000, 300,
                                                              False)),
        "w8": (8, 1000, rng.randint(0, 1000, 300)),
        "first-and-last-row": (16, 1000, np.array([0, 999, 0, 999])),
        "both-sides-of-a-tile-edge": (16, 640, np.array(
            [126, 127, 128, 129, 255, 256, 383, 384, 639])),
        "a-chunk-of-fillers-only": (16, 512, np.repeat([3, 130, 400], 128)),
        "no-row-in-bounds": (16, 512, np.full(256, -1)),
        "zipf-like": (16, 1000, zipf),
        "all-distinct": (8, 2000, rng.permutation(2000)[:1024]),
        "default-blocks": (16, 3000, rng.randint(0, 3000, 2000)),
    }


def _named_rows(ids, rows):
    """[rows] bool: the rows some in-range id of the stream names."""
    ids = np.asarray(ids)
    named = np.zeros(rows, bool)
    named[ids[(ids >= 0) & (ids < rows)]] = True
    return named


@pytest.mark.parametrize("case", list(_lane_cases()))
def test_lane_stream_matches_xla_lines(case):
    """`tiled_adagrad_rows` over a table the chip stores column-major
    (width under 128: blocks [width, tile], rows on the lanes, the
    gradient cut into three bfloat16 pieces for one matmul) against the
    XLA lines of `sparse_adagrad(strategy="sort")` on the same
    (rep, sums): table and accumulator equal bit for bit, two steps in a
    row, and rows no id names bit-identical to what went in. (No fused
    producer stands in front of the delta here, so not even rsqrt's
    rounding parts the two: see `_assert_adagrad_tables_match`.)"""
    width, rows, ids = _lane_cases()[case]
    rng = np.random.RandomState(5)
    ids = jnp.asarray(ids.astype(np.int32))
    contribs = jnp.asarray(rng.randn(ids.shape[0], width).astype(np.float32))
    table = jnp.asarray(rng.randn(rows, width).astype(np.float32))
    accum = jnp.asarray(0.1 + rng.rand(rows, width).astype(np.float32))
    blocks = {} if case == "default-blocks" else {"tile": 128, "chunk": 128}

    @jax.jit
    def both(table, accum, ids, contribs):
        rep, sums = su.dedup_sum(ids, contribs, sentinel=rows)
        return (pt.tiled_adagrad_rows(table, accum, rep, sums, 0.05,
                                      eps=1e-7, interpret=True, **blocks),
                su._adagrad_rows_xla(table, accum, rep, sums, 0.05, 1e-7))

    got, want = both(table, accum, ids, contribs)
    got2, want2 = both(*got, ids, contribs * 3.0)
    for a, b in zip(got + got2, want + want2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    named = _named_rows(ids, rows)
    if named.any():
        assert (np.asarray(got[0])[named] != np.asarray(table)[named]).any()
    for new, old in zip(got, (table, accum)):
        np.testing.assert_array_equal(np.asarray(new)[~named],
                                      np.asarray(old)[~named])


def test_lane_stream_empty_stream():
    """A stream of no slots at all returns what it was given."""
    table = jnp.ones((300, 16), jnp.float32)
    t, a = pt.tiled_adagrad_rows(table, table, jnp.zeros((0,), jnp.int32),
                                 jnp.zeros((0, 16), jnp.float32), 0.05)
    assert t is table and a is table


# `sparse_adagrad` under the tile-stream selection (ISSUE 37): the raw
# sorted stream, duplicates and all, goes to `tiled_adagrad`; name ->
# (rows, raw ids). Blocks are the walk's own: tile 1,024, chunk 256.
def _duplicate_cases():
    rng = np.random.RandomState(37)
    zipf = (rng.zipf(1.05, 2000) - 1) % 3000
    return {
        # row 1,500 named 5,000 times: its run spans 20 and more chunks
        "duplicate-heavy": (3000, rng.permutation(
            np.concatenate([np.full(5000, 1500), zipf]))),
        "all-distinct": (3000, rng.permutation(3000)[:1500]),
        # rows 1,019-1,029 sixty times each: a run over the tile edge at
        # 1,024 and runs over the chunk edges at slots 256 and 512
        "run-across-a-chunk-and-a-tile-edge": (
            2048, np.repeat(np.arange(1019, 1030), 60)),
        "fillers-and-out-of-range": (3000, np.concatenate(
            [rng.randint(-40, 3040, 700), np.full(300, 3000),
             np.full(60, 7), np.full(60, -1)])),
    }


HOT_ROW = 1500


def _ulp(x):
    return np.spacing(np.abs(np.asarray(x, np.float32)))


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("case", list(_duplicate_cases()))
def test_tile_stream_sums_duplicates_in_its_product(case, width,
                                                    monkeypatch):
    """`sparse_adagrad` as a TPU dispatches it for a narrow table's sort
    branch (interpret mode, rows on the lanes) against
    `sparse_adagrad(strategy="sort")` on the same raw stream, two steps in
    a row: touched rows within 1e-5 of the row's largest element (the
    kernel sums a run in another f32 order than `dedup_sum`'s tree), rows
    no id names bit-identical; the 5,000-fold row's total within 4 ulp
    (of the row's largest total) of a float64 sum of its contributions."""
    rows, ids = _duplicate_cases()[case]
    rng = np.random.RandomState(5)
    ids = jnp.asarray(ids.astype(np.int32))
    contribs = rng.randn(ids.shape[0], width).astype(np.float32)
    contribs[np.asarray(ids) == 7] = 0.0      # padded slots: a zero row
    contribs = jnp.asarray(contribs)
    table = jnp.asarray(rng.randn(rows, width).astype(np.float32))
    accum = jnp.asarray(0.1 + rng.rand(rows, width).astype(np.float32))
    # a table of this size takes the dense branch; the sort branch's rule
    # is held at its real threshold by
    # test_active_scatter_impl_answers_for_the_shape
    monkeypatch.setattr(su, "DENSE_ELEMS_MAX", 0)
    monkeypatch.setattr(pt, "_BACKEND_INTERPRET", True)

    def step(strategy):
        return jax.jit(lambda t, a, i, c: su.sparse_adagrad(
            t, a, su.SparseRowGrad(i, c), 0.05, eps=1e-7,
            strategy=strategy))

    want = step("sort")(table, accum, ids, contribs)
    want2 = step("sort")(*want, ids, contribs * 3.0)
    monkeypatch.setattr(su.jax, "default_backend", lambda: "tpu")
    assert su._tile_stream("auto", rows, width, ids.shape[0])
    got = step("auto")(table, accum, ids, contribs)
    got2 = step("auto")(*got, ids, contribs * 3.0)

    named = _named_rows(ids, rows)
    for g, w in zip(got + got2, want + want2):
        g, w = np.asarray(g), np.asarray(w)
        assert (np.abs(g - w) <= 1e-5 * np.abs(w).max(
            axis=1, keepdims=True)).all()
    assert (np.asarray(got[0])[named] != np.asarray(table)[named]).any()
    for new, old in zip(got2, (table, accum)):
        np.testing.assert_array_equal(np.asarray(new)[~named],
                                      np.asarray(old)[~named])
    if case == "duplicate-heavy":
        total = np.asarray(contribs, np.float64)[
            np.asarray(ids) == HOT_ROW].sum(axis=0)
        acc64 = np.asarray(accum, np.float64)[HOT_ROW] + total * total
        row64 = (np.asarray(table, np.float64)[HOT_ROW]
                 - 0.05 * total / np.sqrt(acc64 + 1e-7))
        # the sum is read through the accumulator, which squares it:
        # d(total^2) = 2 |total| d(total), and two roundings of its own
        off = 4 * _ulp(total).max()
        assert (np.abs(np.asarray(got[1])[HOT_ROW] - acc64)
                <= 2 * np.abs(total) * off + 2 * _ulp(acc64)).all()
        assert (np.abs(np.asarray(got[0])[HOT_ROW] - row64)
                <= 4 * _ulp(row64).max()).all()


@pytest.mark.parametrize("backend,width,strategy,scans", [
    ("tpu", 16, "auto", False),     # the tile stream: no scan in the step
    ("tpu", 8, "auto", False),
    ("cpu", 16, "auto", True),
    ("tpu", 128, "auto", True),     # a row-major table keeps the XLA lines
    ("tpu", 16, "sort", True),      # the reference, by request
])
def test_dedup_sum_is_traced_only_off_the_tile_stream(backend, width,
                                                      strategy, scans,
                                                      monkeypatch):
    """With the tile stream selected, the traced `sparse_adagrad` holds no
    `dedup_sum` (its 44 streamed levels at Tiny V3's bucket: ISSUE 37);
    everywhere else it still does."""
    class Scanned(Exception):
        pass

    def raising(*a, **k):
        raise Scanned

    monkeypatch.setattr(su, "dedup_sum", raising)
    monkeypatch.setattr(su.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pt, "_BACKEND_INTERPRET", True)
    S = jax.ShapeDtypeStruct
    state = S((3_000_000, width), jnp.float32)
    n = 65_536

    def trace():
        return jax.eval_shape(
            lambda t, a, i, c: su.sparse_adagrad(
                t, a, su.SparseRowGrad(i, c), 0.05, strategy=strategy),
            state, state, S((n,), jnp.int32), S((n, width), jnp.float32))

    if scans:
        with pytest.raises(Scanned):
            trace()
    else:
        assert [o.shape for o in trace()] == [state.shape] * 2


@pytest.mark.parametrize("mesh_size", [1, 8])
def test_dup_share_counts_what_the_duplicate_sum_folds(mesh_size):
    """`update/dup_share{bucket=}` (ISSUE 37): 1 - distinct rows / valid
    slots of the id stream a bucket's update receives, from the forward's
    folded sort alone, jitted; exact against numpy on one chip's stream,
    between the all-distinct and the one-row batch on a mesh; in the
    registry and in the catalog."""
    import os
    from distributed_embeddings_tpu.obs.instrument import export_update_gauges
    from distributed_embeddings_tpu.obs.registry import MetricRegistry
    mesh = create_mesh(jax.devices()[:mesh_size]) if mesh_size > 1 else None
    model = TinyModel([(96, 8, "sum")], mesh, input_max_hotness=[3])
    emb = model.embedding
    params = emb.init(jax.random.PRNGKey(0))
    shares = jax.jit(lambda p, c: emb.duplicate_shares(
        p, c, sort_spec=("adagrad", "sort")))
    rng = np.random.RandomState(3)
    distinct = jnp.asarray(rng.permutation(96)[:BATCH * 3].reshape(BATCH, 3)
                           .astype(np.int32))
    one_row = jnp.full((BATCH, 3), 5, jnp.int32)
    skewed = jnp.asarray(rng.zipf(1.3, (BATCH, 3)).astype(np.int32) % 96)
    registry = MetricRegistry()
    got = {name: export_update_gauges(registry, shares(params, [c]))
           for name, c in [("distinct", distinct), ("one_row", one_row),
                           ("skewed", skewed)]}
    assert all(set(g) == {0} for g in got.values())
    assert got["distinct"][0] == 0.0
    if mesh is None:
        assert got["one_row"][0] == pytest.approx(1 - 1 / (BATCH * 3))
        assert got["skewed"][0] == pytest.approx(
            1 - len(np.unique(skewed)) / skewed.size)
    assert got["distinct"][0] < got["skewed"][0] < got["one_row"][0] < 1
    # no artifact, no gauge: the dense branch aggregates without a sort
    assert emb.duplicate_shares(params, [skewed]) == {}
    assert registry.snapshot()["gauges"]["update/dup_share{bucket=0}"] \
        == pytest.approx(got["skewed"][0])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        assert "`update/dup_share{bucket=}`" in f.read()


def test_fused_lookup_matches_reference():
    """fused_lookup_combine == the XLA gather+einsum formulation (sum and
    mean, weighted and not) to f32 tolerance, with exact grads in params
    and weights, and the presorted path bit-identical to the fresh-sort
    path."""
    rng = np.random.RandomState(7)
    v, w = 120, 8
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, v, (24, 3)).astype(np.int32))
    wts = jnp.asarray(rng.rand(24, 3).astype(np.float32))
    for comb in ("sum", "mean"):
        for weights in (wts, None):
            got = pt.fused_lookup_combine(table, ids, weights, comb,
                                          interpret=True)
            wv = weights if weights is not None else jnp.ones(
                ids.shape, jnp.float32)
            ref = jnp.einsum("bk,bkw->bw", wv,
                             jnp.take(table, ids, axis=0))
            if comb == "mean":
                ref = ref / jnp.maximum(jnp.sum(wv, axis=1,
                                                keepdims=True), 1.0)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
    # grads (dense path, scatter-free by construction)
    cot = jnp.asarray(rng.randn(24, w).astype(np.float32))

    def f(t, wv):
        return jnp.vdot(pt.fused_lookup_combine(t, ids, wv, "sum",
                                                interpret=True), cot)

    def fr(t, wv):
        return jnp.vdot(jnp.einsum("bk,bkw->bw", wv,
                                   jnp.take(t, ids, axis=0)), cot)

    gt, gw = jax.grad(f, argnums=(0, 1))(table, wts)
    rt, rw = jax.grad(fr, argnums=(0, 1))(table, wts)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(rt), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-4,
                               atol=1e-4)
    # presorted == fresh sort, bit-identical
    from distributed_embeddings_tpu.ops.embedding_ops import (
        canonical_id_sort)
    gs = canonical_id_sort(ids, v, want_inv=True)
    a = pt.fused_lookup_combine(table, ids, wts, "sum", interpret=True)
    b = pt.fused_lookup_combine(table, ids, wts, "sum", interpret=True,
                                presorted=(gs.sid, gs.perm, gs.inv))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_lookup_invalid_ids_clamp():
    """Positive OOB ids clamp to the last row (XLA gather parity);
    zero-weight lanes contribute nothing even at OOB ids."""
    table = jnp.asarray(np.arange(40, dtype=np.float32).reshape(5, 8))
    ids = jnp.asarray([[0, 9], [2, 3]], jnp.int32)
    wts = jnp.asarray([[1.0, 1.0], [1.0, 0.0]], jnp.float32)
    got = np.asarray(pt.fused_lookup_combine(table, ids, wts, "sum",
                                             interpret=True))
    want = np.stack([np.asarray(table[0] + table[4]),
                     np.asarray(table[2])])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ------------------------------------------------- full train-step matrix
def _run_steps(model, optimizer, strategy, weights, head, batches):
    init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.05,
                                              strategy=strategy)
    params = {"embedding": model.embedding.set_weights(weights),
              "head": {"w": jnp.asarray(head)}}
    state = init_fn(params)
    losses = []
    for cats, labels in batches:
        params, state, loss = step_fn(params, state,
                                      jnp.zeros((BATCH, 1)), cats, labels)
        losses.append(float(loss))
    return losses, model.embedding.get_weights(params["embedding"])


def _assert_adagrad_tables_match(w_s, w_p, lr, steps):
    """Adagrad's full-step tables agree to the rounding of
    ``rsqrt(acc + eps)``, not to the bit: since ISSUE 31 the sort
    strategy's delta fusion holds the tail of dedup_sum's shift network
    (elementwise, so XLA:CPU fuses it in), LLVM no longer vectorises that
    loop, and jaxlib 0.9.0's scalar and vector rsqrt round one ulp apart
    on some inputs. Measured at these shapes: the dedup sums and the
    accumulators of the two strategies are bit-identical, 6 of 768 table
    elements differ by one ulp of the delta, and none with
    ``--xla_backend_optimization_level=0``. |delta| <= lr, so a step moves
    an element by at most a few eps * lr; the kernel-level parity test
    above (no fused producer) stays bit-exact for adagrad too."""
    eps = np.finfo(np.float32).eps
    for t, (a, b) in enumerate(zip(w_s, w_p)):
        np.testing.assert_allclose(b, a, rtol=2 * eps,
                                   atol=steps * 4 * eps * lr,
                                   err_msg=f"table {t}")


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
@pytest.mark.parametrize("ragged", [False, True])
def test_pallas_train_step_bitexact_matrix(optimizer, ragged, monkeypatch):
    """The acceptance gate: DET_SCATTER_IMPL strategy 'pallas' runs the
    full distributed sparse train step (8-device mesh, interpret-mode
    kernels) BIT-exactly vs the 'sort' strategy, across optimizers and
    the padded/ragged exchange axis (adagrad to its rsqrt's rounding:
    `_assert_adagrad_tables_match`)."""
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1" if ragged else "0")
    rng = np.random.RandomState(17)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in SPECS]
    head = rng.randn(sum(w for _, w, _ in SPECS), 1).astype(np.float32)
    r2 = np.random.RandomState(23)
    batches = []
    for _ in range(2):
        cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 3)))
                for v, _, _ in SPECS]
        batches.append((cats, jnp.asarray(r2.randn(BATCH)
                                          .astype(np.float32))))

    def build():
        return TinyModel(SPECS, mesh, input_max_hotness=[3] * len(SPECS))

    l_p, w_p = _run_steps(build(), optimizer, "pallas", weights, head,
                          batches)
    l_s, w_s = _run_steps(build(), optimizer, "sort", weights, head,
                          batches)
    if optimizer == "adagrad":
        np.testing.assert_allclose(l_p, l_s, rtol=1e-6)
        _assert_adagrad_tables_match(w_s, w_p, lr=0.05, steps=len(batches))
    else:
        assert l_p == l_s, f"losses diverged: {l_p} vs {l_s}"
        for t, (a, b) in enumerate(zip(w_s, w_p)):
            np.testing.assert_array_equal(b, a, err_msg=f"table {t}")


def test_pallas_train_step_bitexact_hot_rows():
    """Hot-rows axis of the matrix: with a replicated hot shard admitted
    mid-run (observe -> sync), the pallas and sort strategies still agree
    (to adagrad's rsqrt rounding) — the hot shard's dense psum update is
    strategy-independent and the sentinel-masked miss stream rides the
    same dedup seam."""
    specs = [(60, 8, "sum"), (90, 8, "sum")]
    rng = np.random.RandomState(31)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in specs]
    head = rng.randn(16, 1).astype(np.float32)
    data = np.random.RandomState(41)
    batches = []
    for _ in range(4):
        cats = [jnp.asarray(np.minimum(
            data.zipf(1.3, size=(BATCH, 2)) - 1, v - 1).astype(np.int32))
            for v, _, _ in specs]
        batches.append((cats, jnp.asarray(data.randn(BATCH)
                                          .astype(np.float32))))

    def run(strategy):
        model = TinyModel(specs, mesh, hot_rows=8,
                          input_max_hotness=[2, 2])
        init_fn, step_fn = make_sparse_train_step(model, "adagrad",
                                                  lr=0.05,
                                                  strategy=strategy)
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(head)}}
        state = init_fn(params)
        losses = []
        for i, (cats, labels) in enumerate(batches):
            model.embedding.observe_hot_ids(cats)
            if i == 1:      # admit mid-run: steps 2+ exercise hot hits
                p_emb, s_emb = model.embedding.sync_hot_rows(
                    params["embedding"], state["emb"], admit=True)
                params = {**params, "embedding": p_emb}
                state = {**state, "emb": s_emb}
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)), cats,
                                          labels)
            losses.append(float(loss))
        p_sync, _ = model.embedding.sync_hot_rows(params["embedding"],
                                                  state["emb"])
        return losses, model.embedding.get_weights(p_sync)

    l_p, w_p = run("pallas")
    l_s, w_s = run("sort")
    np.testing.assert_allclose(l_p, l_s, rtol=1e-6)
    _assert_adagrad_tables_match(w_s, w_p, lr=0.05, steps=len(batches))


def test_pallas_composes_with_lookahead():
    """LookaheadEngine(strategy='pallas') at lookahead=1 reproduces the
    monolithic pallas step (the drain stage dispatches through the same
    fused kernels) to f32 rounding, and compile counts hold at one
    executable per stage per (plan, batch-shape).

    Not bit-for-bit: the engine differentiates the dense stage w.r.t. the
    carried activations in its own program, and jaxlib 0.9.0's XLA:CPU
    rounds that backward (the 2*(logit-y)/B * w chain) one ulp apart from
    the monolithic program's at these shapes — the tap gradients entering
    the identical drain stage already differ by 3e-8 on step 0, with
    strategy='sort' exactly as with 'pallas'. The kernels' own
    bit-exactness against the sort path is pinned above, inside one
    program."""
    from distributed_embeddings_tpu.schedule import LookaheadEngine

    specs = [(80, 8, "sum"), (50, 8, "sum")]
    rng = np.random.RandomState(53)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in specs]
    head = rng.randn(16, 1).astype(np.float32)
    r2 = np.random.RandomState(59)
    batches = []
    for _ in range(4):
        cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 2)))
                for v, _, _ in specs]
        batches.append((jnp.zeros((BATCH, 1)), cats,
                        jnp.asarray(r2.randn(BATCH).astype(np.float32))))

    from jax.sharding import NamedSharding, PartitionSpec as P

    def params_for(model):
        # replicated head, like test_schedule._build: an uncommitted
        # single-device head would re-specialize the fused step once its
        # first output comes back replicated. One per model: the steps
        # donate their params
        return {"embedding": model.embedding.set_weights(weights),
                "head": {"w": jax.device_put(jnp.asarray(head),
                                             NamedSharding(mesh, P()))}}

    m1 = TinyModel(specs, mesh)
    init_fn, step_fn = make_sparse_train_step(m1, "adagrad", lr=0.05,
                                              strategy="pallas")
    p1 = params_for(m1)
    s1 = init_fn(p1)
    mono = []
    for num, cats, lab in batches:
        p1, s1, loss = step_fn(p1, s1, num, cats, lab)
        mono.append(float(loss))

    m2 = TinyModel(specs, mesh)
    # patch_capacity=BATCH: the compile-stability configuration (the
    # default capacity overflows at these tiny zipf-free shapes and the
    # full-reprefetch fallback re-specializes — same posture as
    # test_schedule.test_compile_count_stable)
    engine = LookaheadEngine(m2, "adagrad", lr=0.05, strategy="pallas",
                             patch_capacity=BATCH)
    p2 = params_for(m2)
    s2 = engine.init(p2)
    eng = []
    for i, b in enumerate(batches):
        nxt = batches[i + 1] if i + 1 < len(batches) else None
        p2, s2, loss = engine.step(p2, s2, b, nxt)
        eng.append(float(loss))
    np.testing.assert_allclose(eng, mono, rtol=2e-6, atol=0)
    assert engine.compile_counts() == {"prefetch": 1, "fused": 1}
    for t, (a, b) in enumerate(zip(m1.embedding.get_weights(
            p1["embedding"]), m2.embedding.get_weights(p2["embedding"]))):
        np.testing.assert_allclose(b, a, rtol=0, atol=2e-7,
                                   err_msg=f"table {t}")


# ------------------------------------------------- gate + dispatch edges
def test_kernel_check_failure_raises(monkeypatch):
    """A requested kernel family that cannot run is an error, never a
    quiet XLA run: a compile failure in the eager compiled check
    propagates as itself, a numerics mismatch raises, and neither is
    remembered as a verdict that a later dispatch could consult."""
    def boom(width):
        raise RuntimeError("Mosaic failed to compile TPU kernel (simulated)")

    check = su._KernelCheck(boom, "DET_SCATTER_IMPL=pallas (test)")
    monkeypatch.setattr(su, "_PALLAS_FUSED_CHECK", check)
    monkeypatch.setattr(su.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        su.prevalidate_active_impl(strategy="pallas", widths=(8,))
    assert not check.validated
    wrong = su._KernelCheck(lambda width: False, "test-check")
    with pytest.raises(RuntimeError, match="disagree with the XLA"):
        wrong.prevalidate(16)
    assert not wrong.validated


def test_kernel_check_shape_class_cache():
    """One compiled check per (process, width shape-class): a second
    prevalidate at the same class does not re-run the validator."""
    calls = []

    def validator(cls):
        calls.append(cls)
        return True

    check = su._KernelCheck(validator, "test-check")
    assert check.prevalidate(16)
    assert check.prevalidate(12)       # same pow2 class
    assert check.prevalidate(100)      # class 128
    assert calls == [16, 128]
    assert su._width_class(8) == 8 and su._width_class(9) == 16
    assert su._width_class(4096) == 512


def test_row_dma_kernels_refuse_unaddressable_widths(monkeypatch):
    """The per-row DMA kernels can address only float32 rows of width
    128 on the chip: compiled use at any other width raises, naming the
    kernel and the width — at the kernel, at prevalidation
    (DET_SCATTER_IMPL=pallas-dma) and at layer construction
    (DET_LOOKUP_PATH=pallas)."""
    from distributed_embeddings_tpu.ops import pallas_lookup as pll
    from distributed_embeddings_tpu.ops import pallas_scatter as ps
    table = jnp.zeros((64, 16), jnp.float32)
    ids = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match="scatter_add_sorted_unique.*width 16"):
        ps.scatter_add_sorted_unique(table, ids, jnp.ones((8, 16)),
                                     interpret=False)
    with pytest.raises(ValueError, match="adagrad_rows_sorted_unique.*width 16"):
        ps.adagrad_rows_sorted_unique(table, table, ids, jnp.ones((8, 16)),
                                      0.1, interpret=False)
    monkeypatch.setattr(su.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DET_SCATTER_IMPL", "pallas-dma")
    with pytest.raises(ValueError, match="pallas-dma.*width 16"):
        su.prevalidate_active_impl(widths=(16, 128))
    pll.check_lookup_kernel(100, 16, jnp.float32)      # one-hot MXU kernel
    pll.check_lookup_kernel(10 ** 6, 128, jnp.float32)  # row DMA, legal
    with pytest.raises(ValueError, match="_dma_gather_lookup.*width 256"):
        pll.check_lookup_kernel(10 ** 6, 256, jnp.float32)
    with pytest.raises(ValueError, match="dtype bfloat16"):
        pll.check_lookup_kernel(10 ** 6, 128, jnp.bfloat16)


def test_interpret_probe_cached_per_process(monkeypatch):
    """ISSUE 12 satellite bugfix: the interpret default is probed ONCE
    per process — a backend flip mid-process can no longer diverge the
    forward gather and the update kernels within one step."""
    assert pt._interpret_default(None) is True      # CPU test process
    monkeypatch.setattr(pt.jax, "default_backend", lambda: "tpu")
    assert pt._interpret_default(None) is True      # cached, not re-probed
    assert pt._interpret_default(False) is False    # explicit always wins
    assert pt._interpret_default(True) is True


def test_pallas_requested_env_inert_off_tpu(monkeypatch):
    """DET_SCATTER_IMPL=pallas via env is TPU-only: CPU runs keep the XLA
    path under strategy='auto' (the env route must never flip CPU test
    numerics); explicit strategy='pallas' opts into interpret kernels."""
    monkeypatch.setenv("DET_SCATTER_IMPL", "pallas")
    assert not su._pallas_requested("auto")
    assert su._scatter_route("auto") == "xla"
    assert su._pallas_requested("pallas")
    assert su._scatter_route("pallas") == "pallas"
    assert su.active_scatter_impl("auto") == "xla"
    assert su.active_scatter_impl("pallas") == "pallas"


TINY_BUCKET = dict(rows=70_200_000, width=16, n=2_883_584)


@pytest.mark.parametrize("backend,kind,shape,want", [
    ("tpu", "adagrad", TINY_BUCKET, "pallas"),      # Tiny V3's width-16 bucket
    ("tpu", "adagrad", dict(rows=60_160, width=8, n=2_700_000), "xla"),  # dense
    ("tpu", "sgd", dict(rows=11_849_058, width=128, n=106_496), "xla"),  # DLRM
    ("tpu", "adagrad", dict(rows=11_849_058, width=128, n=106_496), "xla"),
    ("tpu", "adagrad", dict(rows=10 ** 6, width=12, n=65_536), "xla"),
    ("tpu", "sgd", TINY_BUCKET, "xla"),
    ("tpu", "adam", TINY_BUCKET, "xla"),
    ("cpu", "adagrad", TINY_BUCKET, "xla"),
], ids=lambda v: v if isinstance(v, str) else f"w{v['width']}")
def test_active_scatter_impl_answers_for_the_shape(backend, kind, shape,
                                                   want, monkeypatch):
    """`active_scatter_impl` returns what the dispatch will do for an
    optimizer and a table's shape, not what was requested: the fused
    family's label for the one bucket whose sort branch takes the tile
    stream on a TPU, `xla` for a small (dense branch), a wide or an odd
    table, for sgd and adam, and on the CPU."""
    monkeypatch.setattr(su.jax, "default_backend", lambda: backend)
    assert su.active_scatter_impl("auto", kind=kind, **shape) == want
    # an explicit strategy is the request and nothing else
    assert su.active_scatter_impl("sort", kind=kind, **shape) == "xla"
    assert su.active_scatter_impl("pallas", kind=kind, **shape) == "pallas"


def test_tile_stream_check_runs_where_a_chip_is_attached(monkeypatch):
    """adagrad's default path is checked compiled before a step is traced,
    with no variable asking for it, at the lane widths and only there; a
    backend answered "tpu" over CPU devices (a described chip) runs
    nothing; `gate_verdicts` shows the fused family's check passed."""
    ran, summed = [], []
    check = su._KernelCheck(lambda cls: ran.append(cls) or True, "test")
    monkeypatch.setattr(su, "_TILE_STREAM_CHECK", check)
    # the dense aggregate's resident kernel (ISSUE 41) is checked beside
    # it, under adam too: both optimizers' dense branch sums through it
    monkeypatch.setattr(su, "_DENSE_SUM_CHECK", su._KernelCheck(
        lambda cls: summed.append(cls) or True, "test", classed=int))
    monkeypatch.setattr(su, "_PALLAS_FUSED_CHECK",
                        su._KernelCheck(lambda cls: True, "test"))
    monkeypatch.setattr(su.jax, "default_backend", lambda: "tpu")
    su.prevalidate_active_impl(strategy="auto", widths=(8, 16, 128),
                               kind="adagrad")
    assert ran == [] and su.gate_verdicts()["pallas"] == -1
    assert summed == [] and su.gate_verdicts()["dense-sum"] == -1

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(su.jax, "devices", lambda: [Chip()])
    su.prevalidate_active_impl(strategy="auto", widths=(8, 16, 128),
                               kind="sgd")
    su.prevalidate_active_impl(strategy="sort", widths=(8, 16, 128),
                               kind="adagrad")
    assert ran == []
    su.prevalidate_active_impl(strategy="auto", widths=(8, 16, 128),
                               kind="adagrad")
    su.prevalidate_active_impl(widths=(16,), kind="adagrad")
    assert ran == [8, 16] and su.gate_verdicts()["pallas"] == 1
    assert summed == [8, 16] and su.gate_verdicts()["dense-sum"] == 1
    su.prevalidate_active_impl(widths=(8, 128), kind="adam")
    assert ran == [8, 16] and summed == [8, 16]
    su._DENSE_SUM_CHECK.validated.clear()
    su.prevalidate_active_impl(widths=(8, 128), kind="adam")
    assert ran == [8, 16] and summed == [8, 16, 8]


@pytest.mark.parametrize("width,tile", [(24, 512), (96, 128), (120, None)])
def test_dense_sum_check_runs_at_the_width_itself(width, tile, monkeypatch):
    """The dense aggregate's check under an attached chip at a lane width
    that is no power of two: it runs the kernel at that width (a pair's
    operands are 3 w + 8 rows a block, so the pow2 class's form is
    another kernel, and at 72-120 the class is 128: no lane width at
    all) with the tile that `dense_sum_blocks` leaves room for, and runs
    nothing where no tile fits. The real check, its kernel in interpret
    mode; the tile stream's stays by class."""
    from distributed_embeddings_tpu.ops import pallas_tiled
    ran, tiles = [], []
    monkeypatch.setattr(su, "_TILE_STREAM_CHECK", su._KernelCheck(
        lambda cls: ran.append(cls) or True, "test"))
    monkeypatch.setattr(su, "_DENSE_SUM_CHECK", su._KernelCheck(
        su._validate_dense_sum, "test", classed=int))
    dense_sum = pallas_tiled.dense_sum

    def interpreted(kids, lo, hi, contribs, rows, tile, interpret):
        assert interpret is False and contribs.shape[1] == width
        tiles.append(tile)
        return dense_sum(kids, lo, hi, contribs, rows, tile, interpret=True)

    monkeypatch.setattr(pallas_tiled, "dense_sum", interpreted)
    monkeypatch.setattr(su.jax, "default_backend", lambda: "tpu")

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(su.jax, "devices", lambda: [Chip()])
    su.prevalidate_active_impl(widths=(width,), kind="adagrad")
    assert ran == [su._width_class(width)]
    assert tiles == ([tile] if tile else [])
    assert su._DENSE_SUM_CHECK.validated == ({width} if tile else set())
    assert (su._dense_walk(60_160 * 8 // width // 2, width, 4096)
            is None) == (tile is None)


def test_gate_verdicts_shape():
    v = su.gate_verdicts()
    assert set(v) == {"tiled", "pallas", "pallas-dma", "dense-sum"}
    assert all(x in (-1, 0, 1) for x in v.values())


def test_update_consumes_sort_pallas():
    """The fold planner must know the pallas strategy consumes the
    forward's canonical sort for ALL optimizer kinds (its dedup rides
    the artifact), and that explicit sort-strategy sgd now dedups."""
    for kind in ("sgd", "adagrad", "adam"):
        assert su.update_consumes_sort(kind, "pallas", 1000, 8)
    assert su.update_consumes_sort("sgd", "sort", 10**7, 8)
    assert not su.update_consumes_sort("sgd", "auto", 10**7, 8)


def test_pallas_step_hlo_sort_bound():
    """The lowered pallas-strategy tapped step holds the one-sort-per-
    exchange-group bound (dedup consumes the folded forward sort), and
    the fully-fused form (fused forward + pallas update) holds the
    tiled-forward 2-per-group bound."""
    import importlib.util as ilu
    import os
    spec = ilu.spec_from_file_location(
        "det_hlo_audit_pf", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "hlo_audit.py"))
    ha = ilu.module_from_spec(spec)
    spec.loader.exec_module(ha)
    rec = ha.audit_tapped_step(vocab=100_000, strategy="pallas")
    assert rec["hlo_sort"] <= rec["sort_bound"], rec
    rec2 = ha.audit_tapped_step(vocab=100_000, strategy="pallas",
                                lookup_path="fused")
    assert rec2["sort_bound"] == 2 * rec2["n_exchange_groups"]
    assert rec2["hlo_sort"] <= rec2["sort_bound"], rec2
