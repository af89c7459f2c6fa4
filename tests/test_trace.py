"""Flight recorder, version lineage and postmortem artifacts (ISSUE 14;
the device-time attribution of that issue went with ISSUE 25, whose stage
scopes are held by tests/test_stage_scopes.py).

The contracts under test: (a) the recorder ring stays within its
configured bound under a long synthetic run and the chrome-trace
export ALWAYS balances (orphaned ends dropped, open spans/tracks
synthetically closed) — including after eviction cut the window;
(b) `obs.span` feeds the recorder, so the exported timeline reproduces
the `span_seconds{span=}` nesting; (c) a store version's life is one
async lineage track — commit opens, publish/scan/apply ride,
the first predict at >= V closes — version-monotonic across a real
publish->poll->predict loop; (d) degraded-mode ENTRY dumps a postmortem
artifact (ring + snapshot) when `DET_OBS_POSTMORTEM_DIR` is set; (e) the
registry export satellite — per-line JSONL flush/fsync."""

import json
import os
import threading

import numpy as np
import jax
import pytest

from distributed_embeddings_tpu import faults, obs
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.obs.trace import FlightRecorder
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.serving import InferenceEngine
from distributed_embeddings_tpu.store import TableStore

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


def _balance(doc):
    """Per-thread B/E depth check; returns the final depths."""
    depth = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "B":
            depth[ev["tid"]] = depth.get(ev["tid"], 0) + 1
        elif ev["ph"] == "E":
            depth[ev["tid"]] = depth.get(ev["tid"], 0) - 1
            assert depth[ev["tid"]] >= 0, "E without a B"
    return depth


def _async_balance(doc):
    """Nestable-async b/e pairing per id; returns open ids (must be
    empty for a balanced export)."""
    open_ids = set()
    for ev in doc["traceEvents"]:
        if ev["ph"] == "b":
            assert ev["id"] not in open_ids, "double async begin"
            open_ids.add(ev["id"])
        elif ev["ph"] == "n":
            assert ev["id"] in open_ids, "async instant off-track"
        elif ev["ph"] == "e":
            assert ev["id"] in open_ids, "async end without begin"
            open_ids.discard(ev["id"])
    return open_ids


# ---------------------------------------------------------------- ring
def test_ring_bounded_under_long_run_and_export_balances(tmp_path):
    """A long synthetic span stream must hold the ring at its bound
    (no unbounded growth) and still export a balanced, loadable
    chrome trace despite the eviction cut."""
    rec = FlightRecorder(capacity=64)
    reg = obs.MetricRegistry()
    for i in range(500):
        rec.begin(f"step{i}")
        rec.instant("tick", i=i)
        rec.end(f"step{i}")
    assert len(rec.events()) == 64
    assert rec.dropped == 500 * 3 - 64
    doc = rec.to_chrome_trace()
    assert _balance(doc) == {} or all(
        v == 0 for v in _balance(doc).values())
    assert _async_balance(doc) == set()
    # a cut mid-span: begin evicted, orphan end must be dropped; open
    # begin at export must be synthetically closed
    rec2 = FlightRecorder(capacity=4)
    rec2.begin("a")
    for i in range(10):
        rec2.instant(f"x{i}")       # evicts the begin
    rec2.end("a")                   # orphan: its B left the ring
    rec2.begin("open")              # never closed before export
    doc2 = rec2.to_chrome_trace()
    assert all(v == 0 for v in _balance(doc2).values())
    names = [e["name"] for e in doc2["traceEvents"] if e["ph"] == "E"]
    assert "a" not in names and "open" in names
    # export file round-trips as plain JSON
    path = tmp_path / "t.json"
    rec2.export(str(path))
    assert json.load(open(path))["traceEvents"]
    del reg


def test_capacity_validation_and_env_default(monkeypatch):
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=1)
    monkeypatch.setenv("DET_OBS_TRACE_EVENTS", "128")
    assert FlightRecorder().capacity == 128


# ------------------------------------------------------- span -> ring
def test_spans_feed_recorder_and_nesting_matches_histogram_paths():
    obs.reset_default_recorder()
    reg = obs.MetricRegistry()
    with obs.span("train", reg):
        with obs.span("step", reg):
            pass
        with obs.span("publish", reg):
            pass
    doc = obs.default_recorder().to_chrome_trace()
    seq = [(e["ph"], e["name"]) for e in doc["traceEvents"]
           if e["ph"] in "BE"]
    assert seq == [("B", "train"), ("B", "train/step"),
                   ("E", "train/step"), ("B", "train/publish"),
                   ("E", "train/publish"), ("E", "train")]
    # the recorded names ARE the registry's span_seconds paths
    hist_paths = {k[len("span_seconds{span="):-1]
                  for k in reg.snapshot()["histograms"]}
    assert {n for _, n in seq} == hist_paths
    assert all(v == 0 for v in _balance(doc).values())


def test_recorder_is_thread_safe_across_span_threads():
    obs.reset_default_recorder()
    reg = obs.MetricRegistry()

    def worker(i):
        for _ in range(50):
            with obs.span(f"w{i}", reg):
                pass

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    doc = obs.default_recorder().to_chrome_trace()
    assert all(v == 0 for v in _balance(doc).values())
    assert sum(1 for e in doc["traceEvents"] if e["ph"] == "B") == 200


# ------------------------------------------------------------- lineage
def test_lineage_tracks_through_publish_poll_predict(tmp_path):
    """The real seams: commit opens V's async track, publish/scan/apply
    ride it, the first predict at >= V closes it — version-monotonic
    begins, balanced pairing, later versions closed by one predict."""
    obs.reset_default_recorder()
    dist = make_dist()
    rng = np.random.RandomState(3)
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)                               # v1 snapshot
    store.commit(store.params, touched=_touched(dist, rng))
    store.publish(d)                               # v2 delta
    eng = InferenceEngine(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]))
    assert [i["version"] for i in eng.poll_updates(d)] == [1, 2]
    req = [np.zeros((4,), np.int32) for _ in SIZES]
    eng.predict(req)                               # closes v1 AND v2

    rec = obs.default_recorder()
    assert rec.lineage_versions() == [1, 2]
    assert rec.lineage_open_versions() == []       # predict closed both
    evs = [e for e in rec.to_chrome_trace()["traceEvents"]
           if e.get("cat") == "version"]
    begins = [e["id"] for e in evs if e["ph"] == "b"]
    assert begins == sorted(begins) == [1, 2]      # version-monotonic
    assert _async_balance({"traceEvents": evs}) == set()
    phases = {(e["id"], e.get("args", {}).get("phase")) for e in evs}
    for v in (1, 2):
        assert (v, "publish") in phases
        assert (v, "scan") in phases
        assert (v, "apply") in phases
    # the serve close carries the version it was answered at
    closes = [e for e in evs if e["ph"] == "e"]
    assert {e["id"] for e in closes} == {1, 2}
    # a SECOND predict at the same version must not re-close anything
    # (its serve/predict span edges still record; lineage stays quiet)
    n_lineage = sum(1 for e in rec.events() if e[4] == "version")
    eng.predict(req)
    assert sum(1 for e in rec.events() if e[4] == "version") == n_lineage


def test_lineage_rejects_unknown_phase_and_autoopens_consumer_side():
    rec = FlightRecorder(capacity=64)
    with pytest.raises(ValueError, match="phase"):
        rec.lineage(1, "observe")
    # a consumer that never saw the publisher's commit still gets a
    # track (synthetic open on first sight)
    rec.lineage(7, "apply")
    evs = rec.events()
    assert [e[0] for e in evs] == ["b", "n"]
    assert rec.lineage_versions() == [7]


# ---------------------------------------------------------- postmortem
def test_degraded_entry_dumps_postmortem_artifact(tmp_path, monkeypatch):
    """Entering a serve/degraded{reason=} state writes the incident
    artifact — ring + snapshot + context — once per reason activation;
    a healthy->degraded->healthy->degraded cycle dumps twice."""
    pm = str(tmp_path / "pm")
    monkeypatch.setenv("DET_OBS_POSTMORTEM_DIR", pm)
    obs.reset_default_recorder()
    dist = make_dist()
    rng = np.random.RandomState(5)
    reg = obs.MetricRegistry()
    store = TableStore(dist, dist.set_weights(_weights(rng)))
    d = str(tmp_path / "pub")
    store.commit(store.params)
    store.publish(d)
    eng = InferenceEngine(
        dist, dist.set_weights([np.zeros((v, w), np.float32)
                                for v, w in SIZES]), registry=reg)
    plan = faults.FaultPlan([{"point": "consumer.poll",
                              "kind": "io_error", "at": [0, 1, 3]}])
    with faults.use_plan(plan):
        eng.poll_updates(d)                  # occ 0: degraded entry #1
        assert len(eng.postmortems) == 1
        eng.poll_updates(d)                  # occ 1: STILL degraded —
        assert len(eng.postmortems) == 1     # an active reason never re-dumps
        eng.poll_updates(d)                  # occ 2: healthy, heals
        assert eng.degraded_reasons() == frozenset()
        eng.poll_updates(d)                  # occ 3: entry #2, dumps again
    assert len(eng.postmortems) == 2
    doc = json.load(open(eng.postmortems[0]))
    assert doc["reason"] == "degraded:poll_error"
    assert doc["snapshot"]["gauges"][
        "serve/degraded{reason=poll_error}"] == 1
    assert doc["extra"]["publish_dir"] == d
    assert isinstance(doc["trace"]["traceEvents"], list)
    # the ring marked the entry as an instant event too
    marks = [e for e in doc["trace"]["traceEvents"]
             if e.get("name") == "serve/degraded_entry"]
    assert marks and marks[0]["args"]["reason"] == "poll_error"
    assert reg.counter("obs/postmortems_total",
                       reason="degraded_poll_error").value == 2
    # two dumps in the same second must not collide
    assert len(set(eng.postmortems)) == 2


def test_postmortem_not_dumped_without_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("DET_OBS_POSTMORTEM_DIR", raising=False)
    dist = make_dist()
    rng = np.random.RandomState(6)
    eng = InferenceEngine(dist, dist.set_weights(_weights(rng)))
    plan = faults.FaultPlan([{"point": "consumer.poll",
                              "kind": "io_error", "at": [0]}])
    with faults.use_plan(plan):
        eng.poll_updates(str(tmp_path / "nowhere"))
    assert eng.degraded_reasons() == frozenset({"poll_error"})
    assert eng.postmortems == []


# --------------------------------------------- registry export satellites
def test_export_jsonl_flushes_per_line_and_fsyncs_final(tmp_path):
    reg = obs.MetricRegistry()
    reg.counter("n").inc()
    path = str(tmp_path / "m.jsonl")
    reg.export_jsonl(path)
    reg.export_jsonl(path, extra={"source": "final"}, fsync=True)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2 and lines[1]["source"] == "final"
