"""Serving subsystem: engine parity, HBM hot-row cache, refresh contract.

Acceptance contract (ISSUE 1): (a) `InferenceEngine.predict` is numerically
identical to the training forward (no optimizer state, taps disabled);
(b) zipfian traffic over an offloaded bucket serves bit-exact through the
hot-row cache with a >50% hit rate; (c) after a sparse train step mutates
an offloaded table, `refresh()` restores bit-exact serving.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.fleet import AdmissionController
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.obs import MetricRegistry
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.serving import (HotRowCache, InferenceEngine,
                                                MicroBatcher)
from distributed_embeddings_tpu.training import make_sparse_train_step
from distributed_embeddings_tpu.utils.metrics import LatencyHistogram

from test_sparse_train import TinyModel, BATCH

# same plan as tests/test_offload.py: one fused width-16 bucket whose two
# 5000-row tables blow the budget -> the whole bucket host-offloads
SPECS = [(5000, 16, "sum"), (40, 16, "sum"), (5000, 16, "sum"),
         (64, 16, "sum"), (128, 16, "sum"), (96, 16, "sum"),
         (80, 16, "sum"), (72, 16, "sum")]
BUDGET = 2500 * 16


def _zipf(rng, vocab, n, alpha=1.5):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -alpha
    p /= p.sum()
    return rng.choice(vocab, size=n, p=p).astype(np.int32)


def _build_offloaded(mesh, **kw):
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in SPECS], mesh=mesh,
        gpu_embedding_size=BUDGET, **kw)
    assert dist._offload_enabled
    assert any(b.offload for b in dist.plan.tp_buckets)
    return dist


@pytest.fixture(scope="module")
def std_dist():
    """One offloaded layer + weights shared by the engine tests (engines
    and caches are per-test; the layer itself is stateless per forward)."""
    rng = np.random.RandomState(1)
    mesh = create_mesh(jax.devices()[:8])
    dist = _build_offloaded(mesh)
    params = dist.set_weights(
        [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS])
    return dist, params


def test_engine_matches_training_forward():
    """(a) apply-only predict == the tapped training forward's outputs —
    same numerics with optimizer state stripped and taps disabled."""
    rng = np.random.RandomState(0)
    mesh = create_mesh(jax.devices()[:8])
    model = TinyModel(SPECS, mesh, gpu_embedding_size=BUDGET)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    params = {"embedding": model.embedding.set_weights(weights),
              "head": {"w": jnp.asarray(np.random.RandomState(7).randn(
                  sum(w for _, w, _ in SPECS), 1).astype(np.float32))}}

    # the engine strips a checkpoint-shaped {"params", "opt_state"} dict
    engine = InferenceEngine(model, {"params": params, "opt_state": {"x": 1}},
                             cache_capacity=0)
    assert engine.params is params

    numerical = np.zeros((BATCH, 1), np.float32)
    cats = [rng.randint(0, v, size=(BATCH,)).astype(np.int32)
            for v, _, _ in SPECS]
    got = np.asarray(engine.predict((numerical, cats)))

    # tapless reference forward, jitted like every training-path forward
    # (an eager CPU matmul fuses differently at the 1e-7 level)
    want = np.asarray(jax.jit(
        lambda p, n, c: model.apply(p, n, c))(
            params, jnp.asarray(numerical),
            [jnp.asarray(c) for c in cats]))
    np.testing.assert_array_equal(got, want)

    # and the TRAINING forward (zero taps + residual export) — identical
    taps = model.embedding.make_taps([jnp.asarray(c) for c in cats])
    tapped, _ = model.apply(params, jnp.asarray(numerical),
                            [jnp.asarray(c) for c in cats], taps=taps,
                            return_residuals=True)
    np.testing.assert_allclose(got, np.asarray(tapped), rtol=1e-6, atol=1e-7)


def test_cached_lookups_bitmatch_and_hit_rate(std_dist):
    """(b) zipfian stream over the offloaded bucket: cached lookups
    bit-match the uncached host path batch for batch, and the cumulative
    hit rate (cold start included) crosses 50%."""
    rng = np.random.RandomState(1)
    dist, params = std_dist

    engine = InferenceEngine(dist, params, cache_capacity=1024,
                             promote_threshold=1)
    engine.warmup([BATCH])
    # uncached reference: the stock host-lookup forward, compiled once
    uncached = jax.jit(lambda p, c: dist.apply(p, c))
    for step in range(24):
        cats = [_zipf(rng, v, BATCH) for v, _, _ in SPECS]
        got = engine.predict(cats)
        want = uncached(params, [jnp.asarray(c) for c in cats])
        for i, (a, b) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(
                np.asarray(b), np.asarray(a),
                err_msg=f"step {step} output {i} diverged from host path")
    stats = engine.cache_stats()
    assert stats["hit_rate"] > 0.5, stats
    assert stats["buckets"][0]["promotions"] > 0


def test_cache_weighted_and_multihot_bitmatch():
    """Cache numerics hold for multi-hot inputs with explicit weights and
    mean combiners (the `_effective_weights` path)."""
    rng = np.random.RandomState(2)
    mesh = create_mesh(jax.devices()[:8])
    specs = [(5000, 16, "mean"), (40, 16, "mean"), (5000, 16, "sum"),
             (64, 16, "mean"), (128, 16, "sum"), (96, 16, "mean"),
             (80, 16, "sum"), (72, 16, "mean")]
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in specs], mesh=mesh,
        gpu_embedding_size=BUDGET)
    assert any(b.offload for b in dist.plan.tp_buckets)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    params = dist.set_weights(weights)
    engine = InferenceEngine(dist, params, cache_capacity=512,
                             promote_threshold=1)
    uncached = jax.jit(lambda p, c: dist.apply(p, c))
    for _ in range(4):
        cats = [(_zipf(rng, v, BATCH * 3).reshape(BATCH, 3),
                 np.abs(rng.rand(BATCH, 3)).astype(np.float32))
                for v, _, _ in specs]
        got = engine.predict(cats)
        want = uncached(params, [(jnp.asarray(i), jnp.asarray(w))
                                 for i, w in cats])
        for i, (a, b) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                          err_msg=f"output {i}")
    assert engine.cache_stats()["hits"] > 0


def test_refresh_restores_bit_exact_serving():
    """(c) a sparse train step mutates the offloaded table; cached rows are
    stale until refresh(), after which serving is bit-exact again."""
    rng = np.random.RandomState(3)
    mesh = create_mesh(jax.devices()[:8])
    model = TinyModel(SPECS, mesh, gpu_embedding_size=BUDGET)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    params = {"embedding": model.embedding.set_weights(weights),
              "head": {"w": jnp.asarray(np.random.RandomState(7).randn(
                  sum(w for _, w, _ in SPECS), 1).astype(np.float32))}}

    engine = InferenceEngine(model, params, cache_capacity=1024,
                             promote_threshold=1)
    numerical = np.zeros((BATCH, 1), np.float32)
    # a small hot id set: guaranteed cached AND touched by the train step
    hot = [np.tile(np.arange(4, dtype=np.int32), BATCH // 4)
           for _ in SPECS]
    for _ in range(3):     # count -> promote -> serve from cache
        engine.predict((numerical, hot))
    assert engine.cache_stats()["hits"] > 0

    init_fn, step_fn = make_sparse_train_step(model, "sgd", lr=0.5,
                                              strategy="sort")
    opt_state = init_fn(params)
    labels = jnp.asarray(rng.randn(BATCH).astype(np.float32))
    new_params, _, _ = step_fn(params, opt_state, jnp.zeros((BATCH, 1)),
                               [jnp.asarray(c) for c in hot], labels)
    fresh = np.asarray(jax.jit(
        lambda p, n, c: model.apply(p, n, c))(
            new_params, jnp.asarray(numerical),
            [jnp.asarray(c) for c in hot]))

    engine.set_params(new_params)
    stale = np.asarray(engine.predict((numerical, hot)))
    assert not np.array_equal(stale, fresh), \
        "cached rows must be stale after the table mutated"

    refreshed_rows = engine.refresh()
    assert refreshed_rows > 0
    again = np.asarray(engine.predict((numerical, hot)))
    np.testing.assert_array_equal(again, fresh)


def test_warmup_pads_and_slices(std_dist):
    """Compile-ahead shapes: a smaller request pads to the warmed shape and
    outputs slice back to the true batch, matching the unpadded forward."""
    rng = np.random.RandomState(4)
    dist, params = std_dist
    engine = InferenceEngine(dist, params, cache_capacity=64)
    assert engine.warmup([BATCH]) == [BATCH]
    small = 5
    cats = [rng.randint(0, v, size=(small,)).astype(np.int32)
            for v, _, _ in SPECS]
    got = engine.predict(cats)
    # unpadded reference at a world-divisible batch: pad manually, slice
    padded = [np.concatenate([c, np.zeros((BATCH - small,), c.dtype)])
              for c in cats]
    want = jax.jit(lambda p, c: dist.apply(p, c))(
        params, [jnp.asarray(c) for c in padded])
    for a, b in zip(want, got):
        assert np.asarray(b).shape[0] == small
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a)[:small])
    assert engine.rows_padded == BATCH - small


def test_micro_batcher_coalesces_and_records(std_dist):
    rng = np.random.RandomState(5)
    dist, params = std_dist
    engine = InferenceEngine(dist, params, cache_capacity=256,
                             promote_threshold=1)
    engine.warmup([BATCH])

    now = [0.0]
    batcher = MicroBatcher(engine, max_batch=BATCH, clock=lambda: now[0])
    reqs = {}
    for n in (3, 5, 2, 7, 4):          # 21 rows -> two coalesced forwards
        cats = [_zipf(rng, v, n) for v, _, _ in SPECS]
        reqs[batcher.submit(cats)] = cats
        now[0] += 0.001
    assert batcher.queue_depth == 5
    now[0] += 0.010
    results = batcher.flush()
    assert batcher.queue_depth == 0
    assert set(results) == set(reqs)
    uncached = jax.jit(lambda p, c: dist.apply(p, c))
    for handle, cats in reqs.items():
        want = uncached(params, [
            jnp.asarray(np.concatenate([c, np.zeros((BATCH - len(c),),
                                                    c.dtype)]))
            for c in cats])
        for a, b in zip(want, results[handle]):
            assert np.asarray(b).shape[0] == len(cats[0])
            np.testing.assert_array_equal(np.asarray(b),
                                          np.asarray(a)[:len(cats[0])])
    s = batcher.summary()
    assert s["requests"] == 5 and s["batches"] == 2
    assert s["queue_depth_max"] == 5
    assert 0 < s["batch_occupancy"] <= 1
    assert s["count"] == 5 and s["p99_ms"] >= s["p50_ms"] > 0
    assert "hit_rate" in s
    with pytest.raises(ValueError, match="max_batch"):
        batcher.submit([np.zeros((BATCH + 1,), np.int32)
                        for _ in SPECS])


def test_hot_row_cache_admission_and_eviction(std_dist):
    """Counter-based admission: rows promote when the threshold crosses;
    at capacity, only strictly hotter rows evict the coldest resident."""
    rng = np.random.RandomState(6)
    dist, params = std_dist
    b = next(i for i, bk in enumerate(dist.plan.tp_buckets) if bk.offload)
    table = params["tp"][b]
    cache = HotRowCache(dist, b, capacity=2, promote_threshold=2)

    keys = np.asarray([10, 11, 12], np.int64)
    assert (cache.lookup_slots(keys) == -1).all()        # all cold
    assert cache.admit(table) == 0                       # below threshold
    cache.lookup_slots(keys)                             # counts -> 2 each
    assert cache.admit(table) == 2                       # capacity-bound
    slots = cache.lookup_slots(keys)
    assert (slots[:2] >= 0).sum() + (slots[2] >= 0) == 2
    # the cached rows are bit-exact copies of the table rows
    rows_max = max(dist.plan.tp_buckets[b].rows_max, 1)
    for key, slot in cache._index.items():
        w_idx, row = divmod(int(key), rows_max)
        want = np.asarray(table)[w_idx, row]
        np.testing.assert_array_equal(cache._slots_np[slot], want)
    # a strictly hotter newcomer evicts the coldest resident
    hot_key = np.asarray([99], np.int64)
    for _ in range(6):
        cache.lookup_slots(hot_key)
    assert cache.admit(table) == 1
    assert cache.evictions == 1
    assert (cache.lookup_slots(hot_key) >= 0).all()
    # invalid lanes never count or map
    before = cache.hits + cache.misses
    out = cache.lookup_slots(np.asarray([99, 99]),
                             valid=np.asarray([True, False]))
    assert out[1] == -1 and cache.hits + cache.misses == before + 1


def test_hot_row_cache_counter_pruning(std_dist):
    """Long-lived-server bound: the counter dict prunes back to the
    hottest half (residents always kept) instead of growing with every
    unique id ever seen."""
    dist, params = std_dist
    b = next(i for i, bk in enumerate(dist.plan.tp_buckets) if bk.offload)
    cache = HotRowCache(dist, b, capacity=4, promote_threshold=1,
                        max_tracked=64)
    hot = np.asarray([1, 2, 3, 4], np.int64)
    for _ in range(5):
        cache.lookup_slots(hot)
    cache.admit(params["tp"][b])
    assert set(cache._index) == set(hot.tolist())
    rng = np.random.RandomState(0)
    for i in range(40):
        cache.lookup_slots(rng.randint(100, 3000, size=8).astype(np.int64))
    assert len(cache._counts) <= 64
    # residents survive pruning; their counts still rank evictions
    assert set(hot.tolist()) <= set(cache._counts)


def test_masked_two_source_gather_unit():
    from distributed_embeddings_tpu.ops.embedding_ops import (
        masked_two_source_gather, miss_only_ids)
    slots = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    slot_idx = jnp.asarray([[0, -1], [3, -1]])
    fallback = jnp.full((2, 2, 2), 9.0)
    out = np.asarray(masked_two_source_gather(slots, slot_idx, fallback))
    np.testing.assert_array_equal(out[0, 0], [0.0, 1.0])
    np.testing.assert_array_equal(out[1, 0], [6.0, 7.0])
    np.testing.assert_array_equal(out[0, 1], [9.0, 9.0])
    ids = jnp.asarray([[5, 6], [7, 8]])
    np.testing.assert_array_equal(np.asarray(miss_only_ids(ids, slot_idx)),
                                  [[0, 6], [0, 8]])


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in range(1, 101):                    # 1..100 ms uniform
        h.record(ms / 1e3)
    s = h.summary()
    assert s["count"] == 100
    assert 0.040 <= h.percentile(50) <= 0.060
    assert 0.090 <= h.percentile(95) <= 0.105
    assert 0.094 <= h.percentile(99) <= 0.107
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"] <= s["max_ms"]
    assert LatencyHistogram().percentile(99) == 0.0


def test_micro_batcher_admission_pressure_instruments(std_dist):
    """Fleet admission control (ISSUE 16 satellite) reads the batcher's
    queue instruments at submit time: `queue_depth` high-water survives
    the flush, `queued_rows` tracks TRUE rows (not padded), and a
    depth/row-capped `AdmissionController` sheds typed over them."""
    rng = np.random.RandomState(8)
    dist, params = std_dist
    engine = InferenceEngine(dist, params, cache_capacity=128,
                             promote_threshold=1)
    engine.warmup([BATCH])
    batcher = MicroBatcher(engine, max_batch=BATCH)
    sizes = (3, 5, 2, 7)
    for n in sizes:
        batcher.submit([_zipf(rng, v, n) for v, _, _ in SPECS])
    assert batcher.queue_depth == 4
    assert batcher.queued_rows == sum(sizes)

    adm = AdmissionController(max_queue_depth=4, max_queue_rows=None)
    assert adm.shed_reason(batcher, 1) == "queue_depth"
    adm = AdmissionController(max_queue_depth=64,
                              max_queue_rows=sum(sizes) + 2)
    assert adm.shed_reason(batcher, 3) == "queue_rows"
    assert adm.shed_reason(batcher, 2) is None

    batcher.flush()
    assert batcher.queue_depth == 0 and batcher.queued_rows == 0
    assert batcher.queue_depth_max == 4          # high-water survives
    assert adm.shed_reason(batcher, 3) is None   # pressure released


def test_micro_batcher_partial_batch_flush_ordering(std_dist):
    """A queue larger than max_batch splits across several forwards;
    every handle still gets ITS rows (order-preserving slicing across
    the partial-batch boundary), bit-matching the per-request forward."""
    rng = np.random.RandomState(9)
    dist, params = std_dist
    engine = InferenceEngine(dist, params, cache_capacity=0)
    engine.warmup([16])
    batcher = MicroBatcher(engine, max_batch=16)
    reqs = {}
    for n in (10, 9, 12, 5, 11):       # never two whole requests fit
        cats = [rng.randint(0, v, size=(n,)).astype(np.int32)
                for v, _, _ in SPECS]
        reqs[batcher.submit(cats)] = cats
    results = batcher.flush()
    assert set(results) == set(reqs)
    assert batcher.batches == 4        # 10 | 9+5 | 12 | 11 fills
    uncached = jax.jit(lambda p, c: dist.apply(p, c))
    for handle, cats in reqs.items():
        n = len(cats[0])
        padded = [np.concatenate([c, np.zeros((16 - n,), c.dtype)])
                  for c in cats]
        want = uncached(params, [jnp.asarray(c) for c in padded])
        for a, b in zip(want, results[handle]):
            assert np.asarray(b).shape[0] == n
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a)[:n])


def test_micro_batcher_shed_keeps_latency_accounting_clean(std_dist):
    """A shed decided over the instruments (without submitting) leaves
    NO trace in the latency family: histogram count == admitted
    requests, and the shed wait never contaminates p50/p99."""
    rng = np.random.RandomState(10)
    dist, params = std_dist
    engine = InferenceEngine(dist, params, cache_capacity=0)
    engine.warmup([BATCH])
    reg = MetricRegistry()
    now = [0.0]
    batcher = MicroBatcher(engine, max_batch=BATCH, clock=lambda: now[0],
                           registry=reg)
    adm = AdmissionController(max_queue_depth=2)
    admitted = 0
    for i in range(6):
        cats = [_zipf(rng, v, 3) for v, _, _ in SPECS]
        if adm.shed_reason(batcher, 3) is None:
            batcher.submit(cats)
            admitted += 1
        now[0] += 5.0          # sheds "wait" forever; must not be timed
    assert admitted == 2
    now[0] += 0.001
    batcher.flush()
    h = reg.histogram("serve/request_seconds")
    assert h.count == admitted
    assert reg.counter("serve/requests").value == admitted
    # queueing time of the ADMITTED requests is still accounted: the
    # first queued 10.001s before the flush stamped completion
    assert h.summary()["max_ms"] >= 10000


def test_micro_batcher_replica_labels_coexist(std_dist):
    """Two replicas' batchers on ONE registry: the `replica=` label
    keeps their serve families separate (per-replica p50/count stay
    addressable), and the unlabeled family stays untouched."""
    rng = np.random.RandomState(11)
    dist, params = std_dist
    reg = MetricRegistry()
    engines = {name: InferenceEngine(dist, params, cache_capacity=0,
                                     registry=reg, replica=name)
               for name in ("ra", "rb")}
    for e in engines.values():
        e.warmup([BATCH])
    # replica= defaults from the engine: no explicit batcher arg needed
    batchers = {name: MicroBatcher(e, max_batch=BATCH, registry=reg)
                for name, e in engines.items()}
    assert batchers["ra"].replica == "ra"
    for name, b in batchers.items():
        for _ in range(3 if name == "ra" else 1):
            b.submit([_zipf(rng, v, 4) for v, _, _ in SPECS])
        b.flush()
    assert reg.histogram("serve/request_seconds", replica="ra").count == 3
    assert reg.histogram("serve/request_seconds", replica="rb").count == 1
    assert reg.counter("serve/requests", replica="ra").value == 3
    assert reg.counter("serve/batches", replica="rb").value == 1
    assert reg.histogram("serve/request_seconds").count == 0


def test_quantized_bucket_cache_decode_seam():
    """ISSUE 17 satellite: quantized buckets cache through the decode
    seam — the PR 16 bypass (and its RuntimeWarning) is gone, slots hold
    DECODED f32 rows, cached serving bit-matches the stock
    decode-at-gather host lookup, and `serve/cache_bypassed_buckets`
    is pinned at 0."""
    import warnings

    rng = np.random.RandomState(12)
    mesh = create_mesh(jax.devices()[:8])
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in SPECS], mesh=mesh,
        gpu_embedding_size=BUDGET, storage_dtype="int8")
    quant = [b for b, bk in enumerate(dist.plan.tp_buckets)
             if bk.offload and bk.storage_dtype != "f32"]
    assert quant, "plan must quantize the offloaded bucket"
    W = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    params = dist.set_weights(W)
    reg = MetricRegistry()
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # the bypass warning is GONE
        engine = InferenceEngine(dist, params, cache_capacity=1024,
                                 promote_threshold=1, registry=reg)
    assert set(engine.caches) == set(quant)
    assert reg.gauge("serve/cache_bypassed_buckets").value == 0
    # slots are decoded f32 regardless of the at-rest payload dtype
    cache = engine.caches[quant[0]]
    assert cache.store_dtype == "int8"
    assert cache.slots.dtype == jnp.float32
    # cached serving bit-matches the uncached quantized host lookup —
    # hit lanes (decoded slots) and miss lanes (decode in the host
    # region) agree with the stock path's decode-at-gather numerics
    uncached = jax.jit(lambda p, c: dist.apply(p, c))
    for step in range(16):
        cats = [_zipf(rng, v, BATCH) for v, _, _ in SPECS]
        got = engine.predict(cats)
        want = uncached(params, [jnp.asarray(c) for c in cats])
        for i, (a, b) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(
                np.asarray(b), np.asarray(a),
                err_msg=f"step {step} output {i} diverged from host path")
    stats = engine.cache_stats()
    assert stats["hit_rate"] > 0.5, stats
    assert stats["buckets"][0]["promotions"] > 0
