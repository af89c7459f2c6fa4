"""The order of a narrow bucket's id stream (ISSUE 39).

An exchange group's id slots `[B, f, k]` are flattened into the stream the
lookup gathers, the folded sort orders and the update's contributions
follow. Where the chip stores the bucket column-major (width under 128, a
multiple of 8) and the batch is a whole number of 128-lane vectors the
stream runs feature-major, (f, k, b); everywhere else batch-major,
(b, f, k). `sparse_update.feature_major_stream` owns the choice; forcing it
to batch-major gives the parent's program, which every case here is held
to: the same rows looked up, every duplicate summed, the same row after an
adagrad and an sgd step.
"""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.ops import sparse_update
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import make_sparse_train_step

BATCH = 256
VOCABS = (40, 60, 30)
# inputs 0 and 3 share table 0: duplicates across features as well as
# inside one (ids are drawn from a table's first 12 rows)
TABLE_MAP = (0, 1, 2, 0)


class _TapModel:
    def __init__(self, width, combiner, mesh):
        self.embedding = DistributedEmbedding(
            [Embedding(v, width, combiner=combiner) for v in VOCABS],
            input_table_map=list(TABLE_MAP), mesh=mesh)

    def loss_fn(self, params, numerical, cats, labels, taps=None,
                return_residuals=False):
        out = self.embedding(params["embedding"], list(cats), taps=taps,
                             return_residuals=return_residuals)
        outs, res = out if return_residuals else (out, None)
        x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                            axis=1).astype(jnp.float32)
        # another weight a column, so that no two slots' gradients agree
        col = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32) / x.shape[1]
        loss = jnp.mean((jnp.sum(x * col, axis=1) - labels.reshape(-1)) ** 2)
        return (loss, res) if return_residuals else loss


def _inputs(hotness, weighted, batch):
    data = np.random.RandomState(7)
    cats = []
    for _ in TABLE_MAP:
        ids = jnp.asarray(data.randint(0, 12, size=(batch, hotness)))
        if weighted:
            w = data.rand(batch, hotness).astype(np.float32) + 0.25
            cats.append((ids, jnp.asarray(w)))
        else:
            cats.append(ids)
    return cats, jnp.asarray(data.randn(batch).astype(np.float32))


def _force_batch_major(monkeypatch):
    monkeypatch.setattr(sparse_update, "feature_major_stream",
                        lambda width, batch: False)


def _run(width, hotness, combiner, weighted, world, batch=BATCH):
    """Forward outputs, and the tables after one adagrad and one sgd
    step, of the stream order the rule chooses."""
    mesh = create_mesh(jax.devices()[:world]) if world > 1 else None
    model = _TapModel(width, combiner, mesh)
    rng = np.random.RandomState(0)
    weights = [rng.randn(v, width).astype(np.float32) * 0.1 for v in VOCABS]
    cats, labels = _inputs(hotness, weighted, batch)
    emb = model.embedding
    outs = jax.jit(lambda p: emb(p, cats))(emb.set_weights(weights))
    tables = {}
    for optimizer in ("adagrad", "sgd"):
        params = {"embedding": emb.set_weights(weights)}
        init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.05)
        params, _, loss = step_fn(params, init_fn(params),
                                  jnp.zeros((batch, 1)), cats, labels)
        assert np.isfinite(float(loss))
        tables[optimizer] = emb.get_weights(params["embedding"])
    return [np.asarray(o) for o in outs], tables


def _hold_to_batch_major(monkeypatch, width, hotness, combiner, weighted,
                         world):
    asked = []
    rule = sparse_update.feature_major_stream

    def recorded(width, batch):
        asked.append(rule(width, batch))
        return asked[-1]

    monkeypatch.setattr(sparse_update, "feature_major_stream", recorded)
    outs, tables = _run(width, hotness, combiner, weighted, world)
    # (fewer tables than devices: a table is cut into column slices, and a
    # slice of width 4 is no narrow bucket's)
    assert any(asked), "no bucket ran feature-major"
    _force_batch_major(monkeypatch)
    outs_bm, tables_bm = _run(width, hotness, combiner, weighted, world)
    for i, (a, b) in enumerate(zip(outs, outs_bm)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                   err_msg=f"output {i}")
    moved = 0.0
    for optimizer, tabs in tables.items():
        for t, (a, b) in enumerate(zip(tabs, tables_bm[optimizer])):
            # a row's duplicates are summed in another order: f32 rounding
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=f"{optimizer} table {t}")
            moved = max(moved, float(np.abs(a[:12]).max()))
    assert moved > 0.0


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
@pytest.mark.parametrize("combiner", ["sum", "mean", None])
@pytest.mark.parametrize("hotness", [1, 10])
@pytest.mark.parametrize("width", [8, 16])
def test_feature_major_stream_is_the_batch_major_result(
        monkeypatch, width, hotness, combiner, weighted):
    _hold_to_batch_major(monkeypatch, width, hotness, combiner, weighted, 1)


@pytest.mark.parametrize("width,hotness,combiner,weighted", [
    (16, 10, "sum", False), (8, 10, "mean", True), (16, 1, None, False)])
@pytest.mark.parametrize("world", [2, 4])
def test_feature_major_stream_on_a_mesh(monkeypatch, world, width, hotness,
                                        combiner, weighted):
    """After the id exchange the rule is the same: the gathered stream is
    (f, k, B), the outputs go back to [B, f, w] before the exchange."""
    _hold_to_batch_major(monkeypatch, width, hotness, combiner, weighted,
                         world)


def _step_text(width, batch):
    model = _TapModel(width, "sum", None)
    cats, labels = _inputs(10, False, batch)
    params = {"embedding": model.embedding.init(jax.random.PRNGKey(0))}
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.05)
    return step_fn.lower(params, init_fn(params), jnp.zeros((batch, 1)),
                         cats, labels).as_text()


@pytest.mark.parametrize("width,batch,feature_major", [
    (128, 256, False),      # a row-major bucket gains nothing
    (16, 200, False),       # a batch that leaves a vector of lanes part full
    (16, 256, True)])
def test_only_a_narrow_bucket_and_whole_lane_vectors_change_the_program(
        monkeypatch, width, batch, feature_major):
    """A wide bucket and a batch not a multiple of 128 keep (b, f, k) and
    lower to the very StableHLO of the rule forced to batch-major; the
    narrow bucket at a whole batch does not."""
    assert sparse_update.feature_major_stream(width, batch) is feature_major
    sha = hashlib.sha256(_step_text(width, batch).encode()).hexdigest()
    _force_batch_major(monkeypatch)
    forced = hashlib.sha256(_step_text(width, batch).encode()).hexdigest()
    assert (sha != forced) is feature_major


def test_the_rule_reads_the_width_and_the_batch_alone():
    rule = sparse_update.feature_major_stream
    assert [w for w in (4, 8, 12, 16, 64, 120, 128, 256, 2304)
            if rule(w, 65536)] == [8, 16, 64, 120]
    assert [b for b in (0, 64, 128, 200, 1024, 65536) if rule(16, b)] \
        == [128, 1024, 65536]


def test_stream_order_gauge_says_where_the_mechanism_engages():
    """`lookup/stream_order{bucket=}` beside `update/dup_share{bucket=}`:
    1 for both of Tiny V3's buckets (widths 8 and 16, batch 65,536) as the
    benchmark builds them, 0 for DLRM's width-128 bucket; in the registry
    and in the catalog."""
    import os
    from benchmark.harness import spec
    from distributed_embeddings_tpu.obs.instrument import export_update_gauges
    from distributed_embeddings_tpu.obs.registry import MetricRegistry

    def orders(cell_name):
        cell = spec.load_cell(cell_name)
        built = spec.plugin("builders", cell.config["builder"]).build(
            cell.config, None, False)
        emb = built.model.embedding
        widths = [b.width for b in emb.plan.tp_buckets]
        return widths, emb.stream_orders(built.global_batch)

    widths, tiny = orders("tiny-v3.zipf")
    assert sorted(widths) == [8, 16] and tiny == {0: 1, 1: 1}
    widths, dlrm = orders("dlrm-mlperf.zipf")
    assert widths == [128] and dlrm == {0: 0}
    registry = MetricRegistry()
    assert export_update_gauges(registry, {}, stream_orders=tiny) == {}
    gauges = registry.snapshot()["gauges"]
    assert gauges["lookup/stream_order{bucket=0}"] == 1
    assert gauges["lookup/stream_order{bucket=1}"] == 1
    export_update_gauges(registry, {}, stream_orders=dlrm)
    assert registry.snapshot()["gauges"]["lookup/stream_order{bucket=0}"] == 0
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        assert "`lookup/stream_order{bucket=}`" in f.read()


@pytest.mark.parametrize("width,rows", [(8, 60160), (16, 40960)])
def test_dense_sum_pairs_follow_the_stream_order(width, rows):
    """`sparse_update.dense_sum_pairs` (ISSUE 41) over one exchange group's
    `[B, f, k]` id slots in either order: feature-major
    (`feature_major_stream` says so at this width and batch) a chunk of
    1,024 slots is one feature's, names one table's rows of the bucket
    and is paired with one tile; batch-major every chunk holds every
    feature's slots and is paired with every tile between the first
    table and the last, more pairs than the scatter costs: over Tiny
    V3's width-8 bucket, and over a width-16 target of the most rows
    that fit fast memory (73.7 ms against the scatter's 46.6 on the
    chip, my run, PR 41)."""
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        _as_stream)
    batch, k = 2048, 2
    # each table inside one tile of 1,024 rows, each in another
    offsets = np.linspace(0, rows - 1024, 6).astype(np.int64) // 1024 * 1024
    data = np.random.RandomState(width)
    ids = jnp.asarray(offsets[None, :, None]
                      + data.randint(0, 200, (batch, len(offsets), k)),
                      jnp.int32)
    assert sparse_update.feature_major_stream(width, batch)
    assert not sparse_update.feature_major_stream(width, batch + 8)
    count = jax.jit(lambda x: sparse_update.dense_sum_pairs(x, rows, width))
    chunks = ids.size // 1024
    pairs, kernel = count(_as_stream(ids, True).reshape(-1))
    assert (int(pairs), int(kernel)) == (chunks, 1)
    pairs, kernel = count(_as_stream(ids, False).reshape(-1))
    _, tile, most = sparse_update._dense_walk(rows, width, ids.size)
    assert int(pairs) == chunks * (offsets[-1] // tile + 1)
    assert int(kernel) == 0 and int(pairs) > most > 4 * chunks
