"""Fault injection + hardened delta streaming (ISSUE 13).

The contract under test: (a) the `faults.FaultPlan` seam is
deterministic per seed and validates scenarios at construction; (b) the
stream-file container (v2) carries verifiable checksums and legacy
(v1) files still load, counted; (c) every injected fault DEGRADES
instead of crashing — corrupt files (delta AND snapshot kinds)
quarantine inside `DeltaConsumer.poll`, transient read errors retry
with bounded backoff, crash-before-rename leaves a swept orphan and a
retryable publisher, pause keeps pending keys riding; (d) the consumer
recovers BIT-exactly once a clean snapshot re-anchors the chain, and
`InferenceEngine.poll_updates` never raises — it mirrors degradation
into the ``serve/degraded{reason=}`` gauges and clears them on heal;
(e) the ingest pipeline retries transient stage errors in place; (f)
SLO rules opt into presence-conditional gating with ``if_present``.
"""

import json
import os
import warnings

import numpy as np
import jax
import pytest

from distributed_embeddings_tpu import faults
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.obs.registry import MetricRegistry
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.serving import InferenceEngine
from distributed_embeddings_tpu.store import (DeltaConsumer, TableStore,
                                              scan_published)
from distributed_embeddings_tpu.utils import checkpoint as ckpt_lib

SIZES = [(96, 8), (200, 8)]


def make_dist():
    mesh = create_mesh(jax.devices()[:8])
    return DistributedEmbedding([Embedding(v, w) for v, w in SIZES],
                                mesh=mesh, strategy="memory_balanced",
                                row_slice_threshold=30000)


def _weights(rng):
    return [rng.randn(v, w).astype(np.float32) * 0.1 for v, w in SIZES]


def _touched(dist, rng, n=8):
    import jax.numpy as jnp
    cats = [jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))
            for v, _ in SIZES]
    return dist.touched_row_keys(cats)


def _spec(point, kind, **kw):
    return faults.FaultSpec(point, kind, **kw)


# ------------------------------------------------------------- fault plan
def test_fault_plan_validates_at_construction():
    """A scenario naming an impossible fault refuses at load, not
    mid-soak (a fault that can never fire voids the reconciliation
    ledger silently)."""
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.FaultPlan([{"point": "nope", "kind": "truncate",
                           "at": [0]}])
    with pytest.raises(ValueError, match="cannot fire at point"):
        faults.FaultPlan([{"point": "store.scan", "kind": "bit_flip",
                           "at": [0]}])
    with pytest.raises(ValueError, match="never fires"):
        faults.FaultPlan([{"point": "store.load", "kind": "io_error"}])
    with pytest.raises(ValueError, match="'at' must be a"):
        faults.FaultPlan([{"point": "store.load", "kind": "io_error",
                           "at": 3}])


def test_fault_plan_deterministic_per_seed():
    """Two plans from the same JSON fire on identical occurrence
    sequences — the property that makes a soak run replayable from its
    scenario file alone."""
    doc = {"seed": 11, "faults": [{"point": "store.load",
                                   "kind": "io_error", "prob": 0.3,
                                   "max_fires": 50}]}
    fires = []
    for _ in range(2):
        plan = faults.FaultPlan.from_json(json.dumps(doc))
        fires.append([bool(plan.check("store.load"))
                      for _ in range(200)])
    assert fires[0] == fires[1]
    assert 20 < sum(fires[0]) <= 50          # prob actually draws, capped


def test_ledger_kind_survives_caller_context():
    """The event ledger's identity fields win over caller context keys:
    `TableStore.publish` passes its own stream kind, and a collision
    used to clobber event["kind"] — breaking `corrupted_paths()` and
    every downstream reconciliation."""
    plan = faults.FaultPlan([{"point": "store.publish",
                              "kind": "bit_flip", "at": [0]}])
    spec = plan.check("store.publish", path="/x/f.npz", kind="delta",
                      occurrence="shadow")
    assert spec is not None and spec.kind == "bit_flip"
    (ev,) = plan.events
    assert ev["kind"] == "bit_flip" and ev["point"] == "store.publish"
    assert ev["occurrence"] == 0
    assert plan.corrupted_paths() == ["/x/f.npz"]
    assert plan.counts(kind="bit_flip") == 1


def test_env_var_and_scoped_install(monkeypatch):
    """DET_FAULT_PLAN installs a plan process-wide (inline JSON);
    `use_plan` scopes one and restores the previous state."""
    faults.reset_plan()
    monkeypatch.setenv("DET_FAULT_PLAN", json.dumps(
        {"faults": [{"point": "consumer.poll", "kind": "io_error",
                     "at": [0]}]}))
    try:
        plan = faults.active_plan()
        assert plan is not None and len(plan.specs) == 1
        with faults.use_plan(None):
            assert faults.active_plan() is None
            assert faults.check("consumer.poll") is None
        assert faults.active_plan() is plan
        with pytest.raises(faults.InjectedIOError):
            faults.check_raise("consumer.poll", path="p")
    finally:
        faults.reset_plan()
        monkeypatch.delenv("DET_FAULT_PLAN")
        faults.reset_plan()


# ------------------------------------------------------- container v2
def test_container_v2_checksums_roundtrip_and_detect(tmp_path):
    """v2 stream files verify on load; a payload bit-flip and a
    mid-payload truncation both raise (zip CRC or container checksum —
    either way the consumer's corrupt classification), and a tampered
    header fails its own crc even through the meta-only read."""
    arrays = {"a": np.arange(24, dtype=np.float32).reshape(4, 6),
              "b": np.ones((3,), np.int64)}
    path = ckpt_lib.save_row_delta(str(tmp_path / "f.npz"),
                                   {"kind": "delta", "version": 3}, arrays)
    meta, back = ckpt_lib.load_row_delta(path)
    assert meta["container"] == ckpt_lib.STREAM_CONTAINER_VERSION
    assert set(meta["crc"]) == {"a", "b"}
    np.testing.assert_array_equal(back["a"], arrays["a"])
    assert ckpt_lib.verify_stream_payload(meta, back, path)

    # every parse-level damage class funnels into StreamIntegrityError
    # — the ONE type the consumer classifies as corrupt, so config
    # errors (e.g. a shape-signature mismatch) cannot be mistaken for
    # corruption
    flip = str(tmp_path / "flip.npz")
    trunc = str(tmp_path / "trunc.npz")
    for dst in (flip, trunc):
        with open(path, "rb") as s, open(dst, "wb") as d:
            d.write(s.read())
    faults.corrupt_file(flip, _spec("store.publish", "bit_flip", at=[0]))
    with pytest.raises(ckpt_lib.StreamIntegrityError):
        ckpt_lib.load_row_delta(flip)
    faults.corrupt_file(trunc, _spec("store.publish", "truncate", at=[0]))
    with pytest.raises(ckpt_lib.StreamIntegrityError):
        ckpt_lib.load_row_delta(trunc)
    with open(str(tmp_path / "junk.npz"), "wb") as f:
        f.write(b"not a zip at all")
    with pytest.raises(ckpt_lib.StreamIntegrityError):
        ckpt_lib.load_row_delta_meta(str(tmp_path / "junk.npz"))

    # header tamper: rewrite __meta__ with a changed field, keep crc
    data = dict(np.load(path, allow_pickle=False))
    meta2 = json.loads(str(data["__meta__"]))
    meta2["version"] = 999
    data["__meta__"] = np.asarray(json.dumps(meta2))
    hdr = str(tmp_path / "hdr.npz")
    np.savez(hdr, **data)
    with pytest.raises(ckpt_lib.StreamIntegrityError, match="header"):
        ckpt_lib.load_row_delta_meta(hdr)

    # verify must also catch a checksummed array going missing
    meta3, back3 = ckpt_lib.load_row_delta(path)
    del back3["b"]
    with pytest.raises(ckpt_lib.StreamIntegrityError, match="missing"):
        ckpt_lib.verify_stream_payload(meta3, back3, path)


def test_legacy_v1_files_load_with_counter(tmp_path):
    """Checksum-less (pre-v2) stream files still load — warned once,
    counted — so a rolling upgrade's old publishers keep serving."""
    path = str(tmp_path / "legacy.npz")
    np.savez(path, __meta__=np.asarray(json.dumps(
        {"kind": "delta", "version": 1})),
        a=np.zeros((2, 2), np.float32))
    before = ckpt_lib.legacy_load_count()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        meta, arrays = ckpt_lib.load_row_delta(path)
    assert "crc" not in meta and "a" in arrays
    assert ckpt_lib.legacy_load_count() == before + 1


def test_publish_atomic_and_orphan_sweep(tmp_path):
    d = str(tmp_path)
    tmp = os.path.join(d, "stream_v00000009_delta.npz.tmp")
    with open(tmp, "wb") as f:
        f.write(b"partial")
    keep = os.path.join(d, "stream_v00000001_snapshot.npz")
    with open(keep, "wb") as f:
        f.write(b"x")
    # tmp names never match the stream pattern: invisible to consumers
    assert [p for _, _, p in scan_published(d)] == [keep]
    removed = ckpt_lib.sweep_orphan_tmp(d)
    assert removed == [tmp] and not os.path.exists(tmp)
    assert os.path.exists(keep)
    assert ckpt_lib.sweep_orphan_tmp(str(tmp_path / "missing")) == []

    src = os.path.join(d, "w.tmp")
    with open(src, "wb") as f:
        f.write(b"payload")
    dst = os.path.join(d, "w.npz")
    assert ckpt_lib.publish_atomic(src, dst) == dst
    assert not os.path.exists(src)
    with open(dst, "rb") as f:
        assert f.read() == b"payload"


# ------------------------------------------- quarantine + re-anchor
def test_corrupt_delta_and_snapshot_quarantined_then_bitexact(tmp_path):
    """The acceptance spine: a bit-flipped DELTA and a truncated
    SNAPSHOT are quarantined (not raised) with one warning each, the
    consumer stays on its last-good version and reports degradation,
    and the publisher's next clean snapshot re-anchors it BIT-exactly.
    Quarantined files evict from bookkeeping once compaction deletes
    them."""
    dist = make_dist()
    rng = np.random.RandomState(3)
    reg = MetricRegistry()
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)                              # v1 clean snapshot

    w2 = [w + 0.5 for w in dist.get_weights(store.params)]
    store.commit(dist.set_weights(w2), touched=_touched(dist, rng))
    info2 = store.publish(d)                      # v2 delta -> bit-flip
    assert info2["kind"] == "delta"
    faults.corrupt_file(info2["path"],
                        _spec("store.publish", "bit_flip", at=[0]))

    w3 = [w - 0.25 for w in w2]
    store.commit(dist.set_weights(w3))
    info3 = store.publish(d, force_snapshot=True)  # v3 snap -> truncate
    assert info3["kind"] == "snapshot"
    faults.corrupt_file(info3["path"],
                        _spec("store.publish", "truncate", at=[0]))

    cons_store = TableStore(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]), registry=reg)
    cons = DeltaConsumer(cons_store, d)
    with pytest.warns(RuntimeWarning, match="quarantined"):
        applied = cons.poll()
    # only the clean v1 snapshot applied; both corrupt files quarantined
    assert [i["version"] for i in applied] == [1]
    assert sorted(cons.quarantined) == sorted(
        [info2["path"], info3["path"]])
    assert reg.counter("store/corrupt_files_total").value == 2
    assert cons.degraded_reasons() == frozenset({"corrupt_stream"})
    # second poll: nothing new, still behind the publisher -> degraded
    assert cons.poll() == [] and cons.degraded_reasons()

    # the publisher's next snapshot re-anchors the chain
    store.commit(store.params, touched=_touched(dist, rng, 4))
    store.publish(d, force_snapshot=True)          # v4 clean
    out = cons.poll()
    assert [i["kind"] for i in out] == ["snapshot"]
    assert cons.degraded_reasons() == frozenset()
    for t, (a, b) in enumerate(zip(dist.get_weights(store.params),
                                   dist.get_weights(cons_store.params))):
        np.testing.assert_array_equal(b, a, err_msg=f"table {t}")
    st = cons.stats()
    assert st["quarantined_files"] == 2
    assert st["degraded_reasons"] == []

    # compaction deletes the corrupt files: quarantine + meta cache
    # follow the live stream
    os.remove(info2["path"])
    os.remove(info3["path"])
    cons.poll()
    assert cons.quarantined == {}
    assert all(os.path.exists(p) for p in cons._meta_cache)


def test_transient_io_error_retries_then_applies(tmp_path):
    """An injected transient read error (an `OSError`) retries with
    backoff inside ONE poll and the file still applies — no quarantine,
    no crash, retries counted."""
    dist = make_dist()
    rng = np.random.RandomState(4)
    reg = MetricRegistry()
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)
    cons_store = TableStore(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]), registry=reg)
    cons = DeltaConsumer(cons_store, d, retry_backoff_s=1e-4)
    plan = faults.FaultPlan([{"point": "store.load", "kind": "io_error",
                              "at": [0], "repeat": 2}])
    with faults.use_plan(plan):
        applied = cons.poll()
    assert [i["version"] for i in applied] == [1]
    assert cons._retries_total == 2
    assert reg.counter("store/poll_retries_total").value == 2
    assert cons.quarantined == {}
    assert cons.degraded_reasons() == frozenset()
    for a, b in zip(dist.get_weights(store.params),
                    dist.get_weights(cons_store.params)):
        np.testing.assert_array_equal(b, a)


def test_exhausted_retries_give_up_this_poll_only(tmp_path):
    """When the transient error outlives the in-poll retry budget the
    consumer reports io_transient and serves last-good — and the NEXT
    poll (fault gone) catches up."""
    dist = make_dist()
    rng = np.random.RandomState(5)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)
    cons_store = TableStore(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]))
    cons = DeltaConsumer(cons_store, d, max_transient_retries=1,
                         retry_backoff_s=1e-4)
    plan = faults.FaultPlan([{"point": "store.load", "kind": "io_error",
                              "prob": 1.0, "max_fires": 100}])
    with faults.use_plan(plan):
        assert cons.poll() == []
    assert cons.degraded_reasons() == frozenset({"io_transient"})
    assert cons_store.version == 0
    assert [i["version"] for i in cons.poll()] == [1]
    assert cons.degraded_reasons() == frozenset()


def test_crash_before_rename_orphan_swept_and_retryable(tmp_path):
    """An injected crash between write and rename leaves exactly one
    orphaned tmp, no stream file, and a publisher whose pending state
    survives — the retried publish ships the same rows, and a restarted
    publisher sweeps the orphan."""
    dist = make_dist()
    rng = np.random.RandomState(6)
    reg = MetricRegistry()
    store = TableStore(dist, dist.set_weights(_weights(rng)),
                       registry=reg)
    d = str(tmp_path / "pub")
    plan = faults.FaultPlan([{"point": "store.publish",
                              "kind": "crash_before_rename", "at": [0]}])
    store.commit(store.params)
    with faults.use_plan(plan):
        with pytest.raises(faults.InjectedCrash):
            store.publish(d)
    orphans = [n for n in os.listdir(d) if ".tmp" in n]
    assert len(orphans) == 1
    assert scan_published(d) == []               # invisible to consumers
    assert plan.counts(kind="crash_before_rename") == 1

    # same publisher retries (occurrence 1: clean) without a new commit;
    # the version is unchanged, so the retry's tmp write lands on the
    # orphan's own name and the rename consumes it
    info = store.publish(d)
    assert info["kind"] == "snapshot" and os.path.exists(info["path"])
    assert [n for n in os.listdir(d) if ".tmp" in n] == []

    # restart: a crashed publisher that never retried leaves its orphan
    # for the NEXT publisher's startup sweep
    orphan = os.path.join(d, "stream_v00000007_delta.npz.tmp")
    with open(orphan, "wb") as f:
        f.write(b"dead")
    store2 = TableStore(dist, store.params, registry=reg)
    store2.commit(store2.params)
    with pytest.warns(RuntimeWarning, match="swept"):
        store2.publish(d)
    assert [n for n in os.listdir(d) if ".tmp" in n] == []
    assert reg.counter("store/orphan_tmp_swept_total").value == 1


def test_publisher_pause_keeps_pending_keys(tmp_path):
    """A paused publish writes nothing and advances nothing; the
    pending touched keys ride into the resumed publish and a consumer
    ends bit-exact."""
    dist = make_dist()
    rng = np.random.RandomState(7)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)                              # v1 anchor
    cons_store = TableStore(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]))
    cons = DeltaConsumer(cons_store, d)
    cons.poll()

    import jax.numpy as jnp
    w2 = [w.copy() for w in dist.get_weights(store.params)]
    for w in w2:
        w[:4] += 1.0                             # only touched rows move
    hot = [jnp.asarray(np.arange(4, dtype=np.int32)) for _ in SIZES]
    store.commit(dist.set_weights(w2),
                 touched=dist.touched_row_keys(hot))
    plan = faults.FaultPlan([{"point": "store.publish", "kind": "pause",
                              "at": [0]}])
    with faults.use_plan(plan):
        info = store.publish(d)
    assert info["kind"] == "paused" and info["path"] is None
    assert len(scan_published(d)) == 1           # nothing new on disk
    assert cons.poll() == []

    resumed = store.publish(d)                   # pending keys ride here
    assert resumed["kind"] == "delta" and resumed["rows"] > 0
    assert [i["version"] for i in cons.poll()] == [resumed["version"]]
    for a, b in zip(dist.get_weights(store.params),
                    dist.get_weights(cons_store.params)):
        np.testing.assert_array_equal(b, a)


def test_delayed_visibility_hides_then_reveals(tmp_path):
    """The store.scan fault hides a fresh file for N scans (lagging
    directory views); the consumer just stays on last-good and catches
    up when the file appears."""
    dist = make_dist()
    rng = np.random.RandomState(8)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)
    plan = faults.FaultPlan([{"point": "store.scan",
                              "kind": "delay_visibility", "at": [0],
                              "arg": 2}])
    with faults.use_plan(plan):
        assert scan_published(d) == []           # hidden scan 1
        assert scan_published(d) == []           # hidden scan 2
        assert len(scan_published(d)) == 1       # revealed
    assert plan.counts(kind="delay_visibility") == 1


def test_meta_cache_bounded_by_live_stream(tmp_path):
    """ISSUE 13 satellite: `_meta_cache` entries whose files left the
    directory evict at poll end — cache size tracks the live stream,
    not run length."""
    dist = make_dist()
    rng = np.random.RandomState(9)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)
    cons_store = TableStore(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]))
    cons = DeltaConsumer(cons_store, d)
    deltas = []
    for i in range(3):
        store.commit(store.params, touched=_touched(dist, rng, 4))
        deltas.append(store.publish(d))
        cons.poll()
    assert set(cons._meta_cache) == {i["path"] for i in deltas}
    # compaction: snapshot supersedes, deltas deleted
    store.commit(store.params, touched=_touched(dist, rng, 4))
    store.publish(d, force_snapshot=True)
    for i in deltas:
        os.remove(i["path"])
    cons.poll()
    assert cons._meta_cache == {}                # only deltas were cached


def test_config_errors_propagate_not_quarantined(tmp_path):
    """A stream published for a DIFFERENT model raises out of the
    consumer loudly (config error), it is never quarantined — only
    parse-level damage (`StreamIntegrityError`) is corruption. The
    engine still converts it to degraded serving (reason poll_error)
    rather than crashing the request loop."""
    dist = make_dist()
    rng = np.random.RandomState(12)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)

    other = DistributedEmbedding([Embedding(7, 4)], mesh=None)
    ostore = TableStore(other, other.set_weights(
        [np.zeros((7, 4), np.float32)]))
    cons = DeltaConsumer(ostore, d)
    with pytest.raises(ValueError, match="different model"):
        cons.poll()
    assert cons.quarantined == {}

    eng = InferenceEngine(other, other.set_weights(
        [np.zeros((7, 4), np.float32)]))
    assert eng.poll_updates(d) == []             # degraded, no raise
    assert eng.degraded_reasons() == frozenset({"poll_error"})
    assert "different model" in eng.last_poll_error


def test_stream_dtype_stamped_and_unsupported_refused(tmp_path):
    """ISSUE 15 satellite: every written container header carries the
    payload ``dtype`` (stamped 'f32' when the publisher set none, so
    legacy-shaped saves stay self-describing), and a dtype the consumer
    does not support refuses LOUDLY as ValueError — a config error,
    never `StreamIntegrityError`, never a quarantine (the file is
    healthy; the fleet is mismatched)."""
    arrays = {"a": np.arange(8, dtype=np.float32).reshape(2, 4)}
    path = ckpt_lib.save_row_delta(str(tmp_path / "f.npz"),
                                   {"kind": "delta", "version": 1}, arrays)
    assert ckpt_lib.load_row_delta_meta(path)["dtype"] == "f32"

    # the save layer refuses a non-registry dtype at write time
    with pytest.raises(ValueError, match="not a stream container dtype"):
        ckpt_lib.save_row_delta(str(tmp_path / "bad.npz"),
                                {"kind": "delta", "dtype": "int4"}, arrays)

    # a future publisher's dtype (crafted header, valid checksums):
    # both read layers refuse with the config error, NOT the corrupt one
    import zlib
    meta = {"kind": "delta", "version": 2, "dtype": "int4",
            "container": ckpt_lib.STREAM_CONTAINER_VERSION,
            "crc": {"a": zlib.crc32(
                np.ascontiguousarray(arrays["a"]).tobytes()) & 0xFFFFFFFF}}
    meta["header_crc"] = zlib.crc32(
        json.dumps(meta, sort_keys=True).encode()) & 0xFFFFFFFF
    future = str(tmp_path / "future.npz")
    np.savez(future, __meta__=np.asarray(json.dumps(meta)), **arrays)
    with pytest.raises(ValueError, match="not supported"):
        ckpt_lib.load_row_delta(future)
    with pytest.raises(ValueError, match="not supported"):
        ckpt_lib.load_row_delta_meta(future)
    try:
        ckpt_lib.load_row_delta(future)
    except ValueError as e:
        assert not isinstance(e, ckpt_lib.StreamIntegrityError)

    # consumer path: the refusal PROPAGATES (config class), the file is
    # not quarantined — exactly the sig-mismatch contract
    dist = make_dist()
    rng = np.random.RandomState(5)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    pub = str(tmp_path / "pub")
    os.makedirs(pub)
    import shutil
    shutil.copy(future, os.path.join(pub, "stream_v00000001_delta.npz"))
    cons = DeltaConsumer(store, pub)
    with pytest.raises(ValueError, match="not supported"):
        cons.poll()
    assert cons.quarantined == {}

    # an fp8 stream on a backend without float8 refuses the same way
    with pytest.MonkeyPatch.context() as mp:
        from distributed_embeddings_tpu.ops import wire as wire_ops
        mp.setattr(wire_ops, "fp8_supported", lambda: False)
        meta8 = {"kind": "delta", "version": 3, "dtype": "fp8"}
        p8 = ckpt_lib.save_row_delta(str(tmp_path / "f8.npz"), meta8,
                                     arrays)
        with pytest.raises(ValueError, match="float8"):
            ckpt_lib.load_row_delta(p8)


# ------------------------------------------------- engine degradation
def test_engine_poll_never_raises_and_degraded_gauge(tmp_path):
    """`poll_updates` converts every consumer-side fault into degraded
    serving: the injected poll error and a corrupt stream both land in
    the `serve/degraded{reason=}` gauges (1 while active) and clear on
    heal, `serve/poll_errors_total` counts, and predictions keep
    serving the last-good version throughout."""
    dist = make_dist()
    rng = np.random.RandomState(10)
    reg = MetricRegistry()
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)

    eng = InferenceEngine(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]), registry=reg)
    plan = faults.FaultPlan([{"point": "consumer.poll",
                              "kind": "io_error", "at": [0]}])
    with faults.use_plan(plan):
        assert eng.poll_updates(d) == []         # injected: no raise
    assert eng.degraded_reasons() == frozenset({"poll_error"})
    assert reg.gauge("serve/degraded", reason="poll_error").value == 1
    assert reg.counter("serve/poll_errors_total").value == 1
    assert "InjectedIOError" in eng.last_poll_error
    # still serving (the last-good all-zeros tables)
    req = [np.zeros((4,), np.int32) for _ in SIZES]
    outs = eng.predict(req)
    assert all(np.asarray(o).shape[0] == 4 for o in outs)

    # healthy poll: catches up, gauge resets to 0
    assert [i["version"] for i in eng.poll_updates(d)] == [1]
    assert eng.degraded_reasons() == frozenset()
    assert reg.gauge("serve/degraded", reason="poll_error").value == 0

    # corrupt DELTA mid-stream: degraded while behind, healed after the
    # re-anchoring snapshot, final tables bit-exact
    store.commit(store.params, touched=_touched(dist, rng))
    bad = store.publish(d)
    faults.corrupt_file(bad["path"],
                        _spec("store.publish", "bit_flip", at=[0]))
    with pytest.warns(RuntimeWarning, match="quarantined"):
        assert eng.poll_updates(d) == []
    assert eng.degraded_reasons() == frozenset({"corrupt_stream"})
    assert reg.gauge("serve/degraded", reason="corrupt_stream").value == 1
    store.commit(store.params, touched=_touched(dist, rng, 4))
    store.publish(d, force_snapshot=True)
    assert [i["kind"] for i in eng.poll_updates(d)] == ["snapshot"]
    assert eng.degraded_reasons() == frozenset()
    assert reg.gauge("serve/degraded",
                     reason="corrupt_stream").value == 0
    for a, b in zip(dist.get_weights(store.params),
                    dist.get_weights(eng.store.params)):
        np.testing.assert_array_equal(b, a)


# ---------------------------------------------------- ingest pipeline
def test_ingest_stage_transient_error_retries_in_place():
    """An injected `OSError` in a stage body retries in place (counted)
    and the pipeline's output stays bit-identical to serial; a
    persistent error still propagates via drain-then-raise."""
    from distributed_embeddings_tpu.utils.pipeline import (IngestPipeline,
                                                           SerialPipeline)

    def batches(n):
        for i in range(n):
            yield np.full((4,), i, np.float32)

    stages = [("xform", lambda b: b * 2.0)]
    reg = MetricRegistry()
    serial = list(SerialPipeline(batches(5), stages))
    plan = faults.FaultPlan([{"point": "ingest.stage",
                              "kind": "io_error", "at": [1, 3]}])
    with faults.use_plan(plan):
        with IngestPipeline(batches(5), stages, registry=reg) as pipe:
            got = list(pipe)
    assert len(got) == len(serial) == 5
    for a, b in zip(serial, got):
        np.testing.assert_array_equal(a, b)
    assert reg.counter("ingest/stage_retries_total",
                       stage="xform").value == 2

    # a fault outliving the retry budget propagates (contract unchanged)
    plan = faults.FaultPlan([{"point": "ingest.stage",
                              "kind": "io_error", "prob": 1.0,
                              "max_fires": 1000}])
    with faults.use_plan(plan):
        with pytest.raises(OSError):
            list(IngestPipeline(batches(3), stages))


# ------------------------------------------------------ SLO if_present
def test_slo_if_present_gates_only_when_metric_exists():
    from distributed_embeddings_tpu.obs import slo

    rules = [{"name": "opt", "metric": "lookahead/compiles",
              "op": "==", "threshold": 1, "if_present": True},
             {"name": "req", "metric": "train/steps",
              "op": ">=", "threshold": 1}]
    snap = {"counters": {"train/steps": 4}, "gauges": {}, "histograms": {}}
    assert slo.evaluate_rules(rules, snap) == []   # absent + opted out
    snap["gauges"]["lookahead/compiles"] = 3
    bad = slo.evaluate_rules(rules, snap)
    assert [f.fid for f in bad] == ["slo:opt"]     # present: it gates
    with pytest.raises(ValueError, match="if_present"):
        slo.validate_rule({"name": "x", "metric": "m", "op": "==",
                           "threshold": 0, "if_present": "yes"})

    # windowed: a breach observed while the metric WAS present is not
    # silenced by a later absent snapshot (the subsystem going quiet
    # must not launder an earlier recompile)
    wrules = [{"name": "w", "metric": "g", "op": "==", "threshold": 1,
               "if_present": True, "window": 2}]
    breach = {"counters": {}, "gauges": {"g": 2}, "histograms": {}}
    absent = {"counters": {}, "gauges": {}, "histograms": {}}
    assert [f.fid for f in slo.evaluate_rules(wrules, [breach, absent])] \
        == ["slo:w"]
    assert slo.evaluate_rules(wrules, [absent, absent]) == []
