"""Versioned table store: train-to-serve weight streaming (ISSUE 6).

Acceptance contract: (a) a training job publishing row-deltas every N
steps and a concurrently-running consumer stay within BIT-exact parity
at each consumed version; (b) versions are monotonic and per-table;
(c) the delta chain is integrity-checked (out-of-order apply raises,
snapshots resync); (d) host-offloaded buckets consume deltas through
the XLA-free host row-set seam and HBM cache slots patch straight off
the wire; (e) `get_weights`'s hot overlay and the store's versioned
`read_rows` share ONE resident-row derivation, so the old two-path
staleness cannot occur.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.serving import InferenceEngine
from distributed_embeddings_tpu.store import (DeltaChainError, DeltaConsumer,
                                              TableStore,
                                              restore_from_published,
                                              scan_published)
from distributed_embeddings_tpu.training import make_sparse_train_step

SIZES = [(96, 8), (50, 8), (1000, 16), (2000, 16)]
BATCH = 16


class EmbOnlyModel:
    """Embedding-only tapped model (the serve idiom): loss over the
    concatenated embedding outputs, no dense head."""

    def __init__(self, emb):
        self.embedding = emb

    def loss_fn(self, p, numerical, cats, labels, taps=None,
                return_residuals=False):
        out = self.embedding(p["embedding"], list(cats), taps=taps,
                             return_residuals=return_residuals)
        outs, res = out if return_residuals else (out, None)
        x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                            axis=1)
        loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
        return (loss, res) if return_residuals else loss


def make_dist(**kw):
    mesh = create_mesh(jax.devices()[:8])
    return DistributedEmbedding([Embedding(v, w) for v, w in SIZES],
                                mesh=mesh, strategy="memory_balanced",
                                row_slice_threshold=30000, **kw)


def test_touched_row_keys_cover_update():
    """The host-side touched mirror is a superset of the rows one sparse
    step actually changes — and every key maps back into a real table
    row (OOB ids excluded)."""
    dist = make_dist()
    rng = np.random.RandomState(0)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w in SIZES]
    params = dist.set_weights(weights)
    model = EmbOnlyModel(dist)
    # donate=False: the test reads the tables it passed in after the step
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.1,
                                              donate=False)
    p = {"embedding": params}
    s = init_fn(p)
    cats = [jnp.asarray(rng.randint(0, v, (BATCH,)).astype(np.int32))
            for v, _ in SIZES]
    touched = dist.touched_row_keys(cats)
    assert all(len(v) for v in touched.values())
    p2, _, _ = step_fn(p, s, jnp.zeros((BATCH, 1)), cats,
                       jnp.asarray(rng.randn(BATCH).astype(np.float32)))

    # the superset property a SET-payload delta needs: every row the
    # update changed carries a touched key (equivalently: rows OUTSIDE
    # the touched set are bit-identical before/after), and something did
    # change inside it
    changed_inside = 0
    for b, bk in enumerate(dist.plan.tp_buckets):
        rows_max = max(bk.rows_max, 1)
        before = np.asarray(p["embedding"]["tp"][b])
        after = np.asarray(p2["embedding"]["tp"][b])
        keys = touched.get(("tp", b), np.zeros((0,), np.int64))
        assert ((keys >= 0) & (keys < before.shape[0] * rows_max)).all()
        mask = np.zeros(before.shape[:2], bool)
        mask[keys // rows_max, keys % rows_max] = True
        diff = (before != after).any(axis=-1)
        assert not (diff & ~mask).any(), f"bucket {b}: untouched row moved"
        changed_inside += int((diff & mask).sum())
    for t, rt in enumerate(dist.plan.row_tables):
        before = np.asarray(p["embedding"]["row"][t])
        after = np.asarray(p2["embedding"]["row"][t])
        keys = touched.get(("row", t), np.zeros((0,), np.int64))
        base = np.asarray(rt.row_base, np.int64)
        w_idx = np.searchsorted(base, keys, side="right") - 1
        mask = np.zeros(before.shape[:2], bool)
        mask[w_idx, keys - base[w_idx]] = True
        diff = (before != after).any(axis=-1)
        assert not (diff & ~mask).any(), f"row table {t}: untouched moved"
        changed_inside += int((diff & mask).sum())
    assert changed_inside > 0
    # an over-range id neither appears nor crashes
    bad = [jnp.asarray(np.full((BATCH,), 10 ** 6, np.int32))
           for _ in SIZES]
    assert dist.touched_row_keys(bad) == {}


def test_store_publish_consume_roundtrip(tmp_path):
    """Train-publish-consume: snapshot anchor + chained deltas reproduce
    the live tables BIT-exactly; versions are monotonic per table; the
    chain guard rejects replays; restore_from_published rebuilds from
    (snapshot + deltas)."""
    dist = make_dist()
    rng = np.random.RandomState(1)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w in SIZES]
    model = EmbOnlyModel(dist)
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.1)
    p = {"embedding": dist.set_weights(weights)}
    s = init_fn(p)
    store = TableStore(dist, p["embedding"], s["emb"])
    d = str(tmp_path / "stream")

    assert store.version == 0 and store.table_versions == [0] * len(SIZES)
    store.commit(p["embedding"], s["emb"])
    info0 = store.publish(d)
    assert info0["kind"] == "snapshot" and info0["version"] == 1

    # double publish without a commit is refused (stream files are
    # keyed by version)
    with pytest.raises(ValueError, match="nothing committed"):
        store.publish(d)

    czero = dist.set_weights([np.zeros_like(w) for w in weights])
    cstore = TableStore(dist, czero)
    cons = DeltaConsumer(cstore, d)
    assert [i["kind"] for i in cons.poll()] == ["snapshot"]

    versions = [cstore.version]
    delta_infos = []
    for _ in range(2):
        cats = [jnp.asarray(rng.randint(0, v, (BATCH,)).astype(np.int32))
                for v, _ in SIZES]
        labels = jnp.asarray(rng.randn(BATCH).astype(np.float32))
        store.observe(cats)
        p, s, _ = step_fn(p, s, jnp.zeros((BATCH, 1)), cats, labels)
        store.commit(p["embedding"], s["emb"])
        delta_infos.append(store.publish(d))
    applied = cons.poll()
    assert [i["kind"] for i in applied] == ["delta", "delta"]
    versions += [i["version"] for i in applied]
    assert versions == sorted(versions) and len(set(versions)) == 3
    stats = cons.stats()
    assert stats["version_monotonic"] and stats["applied"] == 3
    assert stats["rows_applied"] > 0 and stats["delta_bytes_total"] > 0

    # bit-exact at the consumed version — the acceptance property
    for t, (a, b) in enumerate(zip(dist.get_weights(p["embedding"]),
                                   dist.get_weights(cstore.params))):
        np.testing.assert_array_equal(b, a, err_msg=f"table {t}")

    # delta bytes stay far under a full copy at these touched rates
    d_bytes = [i["bytes"] for i in delta_infos]
    assert max(d_bytes) < 0.1 * store.full_table_bytes(), (
        d_bytes, store.full_table_bytes())

    # chain integrity: replaying an already-consumed delta raises
    with pytest.raises(DeltaChainError):
        cstore.apply_published(delta_infos[0]["path"])

    # per-table versions: every table this workload touches moved
    assert all(v == store.version for v in store.table_versions)

    # (snapshot + deltas) checkpoint restore
    rstore = restore_from_published(dist, d)
    assert rstore.version == store.version
    for a, b in zip(dist.get_weights(p["embedding"]),
                    dist.get_weights(rstore.params)):
        np.testing.assert_array_equal(b, a)

    # compaction + resync: snapshot the stream, delete the (now
    # superseded) delta files, and a consumer that fell off the chain
    # recovers from the snapshot alone
    import os
    store.commit(p["embedding"], s["emb"],
                 touched=dist.touched_row_keys(
                     [jnp.asarray(np.zeros((4,), np.int32))
                      for _ in SIZES]))
    snap = store.publish(d, force_snapshot=True)
    for di in delta_infos:
        os.remove(di["path"])
    lost = TableStore(dist, dist.set_weights(
        [np.zeros_like(w) for w in weights]))
    lost.version = 2                         # mid-chain orphan
    out = DeltaConsumer(lost, d).poll()
    assert [i["kind"] for i in out] == ["snapshot"]
    assert lost.version == snap["version"]
    for a, b in zip(dist.get_weights(p["embedding"]),
                    dist.get_weights(lost.params)):
        np.testing.assert_array_equal(b, a)
    assert len(scan_published(d)) == 2


def test_store_sig_guard_and_replace(tmp_path):
    """A stream published for a different model is refused; `replace`
    breaks the chain so the next publish snapshots."""
    dist = make_dist()
    rng = np.random.RandomState(2)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w in SIZES]
    store = TableStore(dist, dist.set_weights(weights))
    d = str(tmp_path / "s")
    store.commit(store.params)
    store.publish(d)
    store.commit(store.params, touched={("tp", 0): np.arange(4)})
    info = store.publish(d)
    assert info["kind"] == "delta"

    other = DistributedEmbedding([Embedding(7, 4)], mesh=None)
    ostore = TableStore(other, other.set_weights(
        [np.zeros((7, 4), np.float32)]))
    with pytest.raises(ValueError, match="different model"):
        ostore.apply_published(info["path"])

    store.replace(store.params)
    assert all(v == store.version for v in store.table_versions)
    assert store.publish(d)["kind"] == "snapshot"


def test_consistency_seam_single_source():
    """(e) `read_rows` and `get_weights` agree on hot-resident rows by
    construction — and the test pins the OLD two-path failure mode: a
    canonical-only table read IS stale while rows are hot-resident, so
    any consumer that bypasses the shared `hot_resident_rows` source
    (as `get_weights`/`refresh` used to) serves wrong bytes."""
    vocab, width, B = 500, 8, 32
    rng = np.random.RandomState(3)
    emb = DistributedEmbedding([Embedding(vocab, width, combiner="sum")],
                               mesh=None, hot_rows=16)
    model = EmbOnlyModel(emb)
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.1)
    p = {"embedding": emb.init(jax.random.PRNGKey(0))}
    s = init_fn(p)
    store = TableStore(emb, p["embedding"], s["emb"])

    warm = (rng.zipf(1.3, size=(B, 2)) % vocab).astype(np.int32)
    emb.observe_hot_ids([warm])
    v0 = store.version
    store.sync_hot_rows(admit=True)
    assert store.version == v0 + 1           # consistency step is versioned
    p = {"embedding": store.params}
    s = {**s, "emb": store.opt_states}

    # train so hot-resident rows drift away from their canonical copies
    for _ in range(2):
        cats = [jnp.asarray((rng.zipf(1.3, size=(B, 2)) % vocab)
                            .astype(np.int32))]
        p, s, _ = step_fn(p, s, jnp.zeros((B, 1)), cats,
                          jnp.asarray(rng.randn(B).astype(np.float32)))
    store.commit(p["embedding"], s["emb"])

    keys, rows = emb.hot_resident_rows(store.params)[0]
    assert len(keys) > 0
    # one-source property: versioned read == hot shard == get_weights
    np.testing.assert_array_equal(store.read_rows(0, keys), rows)
    merged = emb.get_weights(store.params)[0]
    rows_max = max(emb.plan.tp_buckets[0].rows_max, 1)
    np.testing.assert_array_equal(merged[(keys % rows_max)], rows)

    # the pinned failure case: the canonical table alone (what the old
    # two-path consumers read) is STALE for resident rows mid-residency
    canonical = np.asarray(store.params["tp"][0])[
        (keys // rows_max).astype(int), (keys % rows_max).astype(int)]
    assert not np.array_equal(canonical, rows), \
        "expected canonical copies to lag the authoritative hot rows"

    # after the store-routed sync, canonical catches up and the merged
    # view is unchanged (sync is invisible to read_rows)
    before = store.read_rows(0, keys)
    store.sync_hot_rows()
    np.testing.assert_array_equal(store.read_rows(0, keys), before)
    canonical2 = np.asarray(store.params["tp"][0])[
        (keys // rows_max).astype(int), (keys % rows_max).astype(int)]
    np.testing.assert_array_equal(canonical2, rows)

    # a consumer with live hot residents refuses deltas (its overlay
    # would shadow the canonical writes)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        store.commit(store.params, touched={("tp", 0): np.arange(4)})
        store.publish(d)                      # snapshot (first publish)
        store.commit(store.params, touched={("tp", 0): np.arange(4)})
        info = store.publish(d)
        assert info["kind"] == "delta"
        hot_consumer = TableStore(emb, store.params)
        hot_consumer.version = info["base_version"]
        with pytest.raises(ValueError, match="EMPTY hot set"):
            hot_consumer.apply_published(info["path"])


def test_engine_streaming_consumption(tmp_path):
    """Serving replica consumption without training: the engine polls a
    publish directory, applies a snapshot then a delta (offloaded bucket
    -> the XLA-free host row-set path), patches resident HBM cache slots
    straight off the wire, and serves BIT-exactly at the new version."""
    from test_serving import SPECS, _build_offloaded

    rng = np.random.RandomState(4)
    mesh = create_mesh(jax.devices()[:8])
    dist = _build_offloaded(mesh)
    w0 = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    prod = TableStore(dist, dist.set_weights(w0))
    d = str(tmp_path / "pub")
    prod.commit(prod.params)
    prod.publish(d)

    engine = InferenceEngine(
        dist, dist.set_weights([np.zeros_like(w) for w in w0]),
        cache_capacity=1024, promote_threshold=1)
    assert [i["kind"] for i in engine.poll_updates(d)] == ["snapshot"]
    assert engine.store.version == 1

    hot = [np.tile(np.arange(4, dtype=np.int32), BATCH // 4)
           for _ in SPECS]
    for _ in range(3):                        # count -> promote -> cache
        engine.predict(hot)
    assert engine.cache_stats()["hits"] > 0

    # publisher mutates the rows the cache holds, publishes a DELTA
    w1 = [w.copy() for w in w0]
    for w in w1:
        w[:4] += 1.0
    prod.commit(dist.set_weights(w1), touched=dist.touched_row_keys(hot))
    info = prod.publish(d)
    assert info["kind"] == "delta"
    assert [i["version"] for i in engine.poll_updates(d)] == [2]

    got = [np.asarray(o) for o in engine.predict(hot)]
    uncached = jax.jit(lambda pp, c: dist.apply(pp, c))
    want = uncached(prod.params, [jnp.asarray(c) for c in hot])
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(b, np.asarray(a), err_msg=f"out {i}")
    stats = engine.update_stats(d)
    assert stats["version_monotonic"] and stats["applied"] == 2
    assert stats["staleness_versions_max"] >= 1
    assert engine.cache_stats()["store_version"] == 2
    for cache in engine.caches.values():
        assert cache.refreshed_version == 2

    # set_params breaks the chain — including the ALIASING interleaving
    # where the publisher's next-next delta's base_version numerically
    # equals the consumer's post-replace version (engine at 2 ->
    # set_params bumps to 3; publisher's second delta below is v4 with
    # base 3): a bare version match must NOT let it chain onto the
    # swapped-in tables. A poll then recovers by re-anchoring on the
    # newest snapshot and replaying the chain from it.
    engine.set_params(dist.set_weights(w0), refresh=True)
    assert engine.store.version == 3
    prod.commit(prod.params, touched=dist.touched_row_keys(hot))
    assert prod.publish(d)["kind"] == "delta"         # v3 (base 2)
    prod.commit(prod.params, touched=dist.touched_row_keys(hot))
    aliasing = prod.publish(d)
    assert aliasing["kind"] == "delta"                # v4 (base 3)
    assert aliasing["base_version"] == engine.store.version
    with pytest.raises(DeltaChainError, match="out of band"):
        engine.store.apply_published(aliasing["path"])
    applied = engine.poll_updates(d)
    assert [i["kind"] for i in applied] == ["snapshot", "delta", "delta",
                                            "delta"]
    assert engine.store.version == prod.version == 4
    for a, b in zip(prod.get_weights(), engine.store.get_weights()):
        np.testing.assert_array_equal(b, a)


def test_vocab_binding_sidecar_roundtrip(tmp_path):
    """Dynamic-vocabulary sidecars (ISSUE 7): the binding table + slot
    free-list publish next to the row stream (`vocab_v{V}.npz`), scan by
    version, and rebuild a fresh manager's binding bit-exactly — the
    piece of vocab state that must survive train-to-serve handoff and
    checkpoint restore alongside the rows."""
    from distributed_embeddings_tpu.vocab import (VocabManager,
                                                  latest_vocab_state,
                                                  vocab_state_path)

    mesh = create_mesh(jax.devices()[:8])
    emb = DistributedEmbedding(
        [Embedding(v, w, combiner="sum") for v, w in SIZES],
        mesh=mesh, strategy="memory_balanced", row_slice_threshold=30000,
        vocab_slack=8)
    mgr = VocabManager(emb, admit_threshold=1, decay=0.9, use_native=False)
    rng = np.random.RandomState(5)
    params = emb.init(jax.random.PRNGKey(0))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for r in range(4):
            raw = [rng.randint(10**8 + r * 30, 10**8 + r * 30 + 40,
                               (16, 2)).astype(np.int64) for _ in SIZES]
            mgr.translate(raw, observe=True)
            params, _ = mgr.maintain(params)
    assert mgr.stats()["admissions"] > 0

    d = str(tmp_path)
    mgr.save_state(vocab_state_path(d, 3))
    mgr.save_state(vocab_state_path(d, 7))
    assert latest_vocab_state(d) == vocab_state_path(d, 7)
    assert latest_vocab_state(d, upto=5) == vocab_state_path(d, 3)
    assert latest_vocab_state(d, upto=1) is None

    fresh = VocabManager(emb, use_native=False)
    fresh.load_state(latest_vocab_state(d))
    probe = rng.randint(10**8, 10**8 + 200, 256).astype(np.int64)
    for t in mgr.vocabs:
        np.testing.assert_array_equal(fresh.vocabs[t].resident_keys(),
                                      mgr.vocabs[t].resident_keys())
        np.testing.assert_array_equal(
            fresh.vocabs[t].binding.free_slots(),
            mgr.vocabs[t].binding.free_slots())
        np.testing.assert_array_equal(fresh.vocabs[t].binding.lookup(probe),
                                      mgr.vocabs[t].binding.lookup(probe))
        # decayed counters survive too (eviction ranking after restore)
        np.testing.assert_allclose(
            fresh.vocabs[t].tracker.counts_for(probe),
            mgr.vocabs[t].tracker.counts_for(probe))

    # the ADMISSION POLICY restores with the state: a manager built with
    # different defaults resumes the SAVED threshold/decay, not its own
    assert fresh.admit_threshold == 1
    assert all(mv.tracker.promote_threshold == 1
               and mv.tracker.decay == 0.9
               for mv in fresh.vocabs.values())

    # a manager over a DIFFERENT slack (capacity) refuses the state
    emb2 = DistributedEmbedding(
        [Embedding(v, w, combiner="sum") for v, w in SIZES],
        mesh=mesh, strategy="memory_balanced", row_slice_threshold=30000,
        vocab_slack=32)
    other = VocabManager(emb2, use_native=False)
    with pytest.raises(ValueError, match="capacity"):
        other.load_state(latest_vocab_state(d))
