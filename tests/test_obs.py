"""Unified runtime telemetry (ISSUE 11): the metric registry, span
tracing, SLO evaluation, and the fit-loop integration."""

import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu import obs, training
from distributed_embeddings_tpu.obs.registry import LatencyHistogram
from distributed_embeddings_tpu.parallel.mesh import create_mesh

from test_sparse_train import TinyModel


# ------------------------------------------------------------- registry
def test_registry_families_and_identity():
    reg = obs.MetricRegistry()
    c = reg.counter("train/steps")
    c.inc()
    c.inc(3)
    assert reg.counter("train/steps") is c and c.value == 4
    # labels split families into distinct instruments
    g0 = reg.gauge("vocab/occupancy", table=0)
    g1 = reg.gauge("vocab/occupancy", table=1)
    assert g0 is not g1
    g0.set(0.5)
    g1.set(0.9)
    h = reg.histogram("serve/request_seconds")
    assert isinstance(h, LatencyHistogram)
    assert reg.histogram("serve/request_seconds") is h


def test_registry_kind_conflict_raises():
    reg = obs.MetricRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_registry_histogram_layout_first_wins():
    reg = obs.MetricRegistry()
    reg.histogram("h", bins_per_decade=32)
    with pytest.raises(ValueError, match="bucket layout"):
        reg.histogram("h", bins_per_decade=8)
    with pytest.raises(ValueError, match="bucket layout"):
        reg.histogram("h", hi=1.0)      # bins derive from hi: refuses too
    assert reg.histogram("h") is reg.histogram("h")   # same layout: fine


def test_snapshot_schema_and_flat_keys():
    reg = obs.MetricRegistry()
    reg.counter("a/b").inc(2)
    reg.gauge("g", table=3, stage="x").set(1.5)
    reg.histogram("lat").record(0.01)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"] == {"a/b": 2}
    # labels sorted into the flat key
    assert snap["gauges"] == {"g{stage=x,table=3}": 1.5}
    assert snap["histograms"]["lat"]["count"] == 1
    assert {"p50_ms", "p95_ms", "p99_ms", "mean_ms",
            "max_ms"} <= set(snap["histograms"]["lat"])


def test_jsonl_export_appends_parseable_lines(tmp_path):
    reg = obs.MetricRegistry()
    reg.counter("n").inc()
    path = str(tmp_path / "m.jsonl")
    reg.export_jsonl(path, extra={"source": "test"})
    reg.counter("n").inc()
    reg.export_jsonl(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0]["source"] == "test" and lines[0]["counters"]["n"] == 1
    assert lines[1]["counters"]["n"] == 2
    assert all("ts" in ln for ln in lines)


def test_default_registry_process_local():
    obs.reset_default_registry()
    try:
        a = obs.default_registry()
        assert obs.default_registry() is a
        a.counter("x").inc()
        obs.reset_default_registry()
        assert obs.default_registry() is not a
    finally:
        obs.reset_default_registry()


# --------------------------------------------- histogram merge property
def test_latency_histogram_merge_matches_concatenated_samples():
    """merge(a, b) must equal the histogram over the concatenated
    sample stream: identical bucket counts, hence identical
    percentiles (the interpolation reads only counts/edges/max), max
    exact, mean within float-summation tolerance. Property-tested over
    random log-uniform streams including overflow-bucket values."""
    rng = np.random.RandomState(7)
    for trial in range(5):
        s1 = 10.0 ** rng.uniform(-6.5, 2.5, size=rng.randint(1, 400))
        s2 = 10.0 ** rng.uniform(-6.5, 2.5, size=rng.randint(1, 400))
        a, b, ref = (LatencyHistogram(), LatencyHistogram(),
                     LatencyHistogram())
        for v in s1:
            a.record(v)
        for v in s2:
            b.record(v)
        for v in np.concatenate([s1, s2]):
            ref.record(v)
        merged = a.merge(b)
        assert merged is a                      # in-place, chainable
        np.testing.assert_array_equal(merged._counts, ref._counts)
        for p in (1, 25, 50, 90, 95, 99, 100):
            assert merged.percentile(p) == ref.percentile(p), (trial, p)
        assert merged._max == ref._max
        assert merged.summary()["mean_ms"] == pytest.approx(
            ref.summary()["mean_ms"], rel=1e-9)


def test_latency_histogram_merge_layout_mismatch_raises():
    a = LatencyHistogram()
    b = LatencyHistogram(bins_per_decade=8)
    with pytest.raises(ValueError, match="bucket layouts"):
        a.merge(b)


# ----------------------------------------------------------------- spans
def test_spans_nest_paths_and_record():
    reg = obs.MetricRegistry()
    with obs.span("train", reg):
        assert obs.current_span() == "train"
        with obs.span("step", reg) as path:
            assert path == "train/step"
            assert obs.current_span() == "train/step"
    assert obs.current_span() is None
    h = reg.snapshot()["histograms"]
    assert h["span_seconds{span=train}"]["count"] == 1
    assert h["span_seconds{span=train/step}"]["count"] == 1


def test_span_records_on_exception():
    reg = obs.MetricRegistry()
    with pytest.raises(RuntimeError):
        with obs.span("boom", reg):
            raise RuntimeError("x")
    assert reg.histogram("span_seconds", span="boom").count == 1
    assert obs.current_span() is None           # stack unwound


def test_span_stack_is_thread_local():
    reg = obs.MetricRegistry()
    seen = {}

    def worker():
        with obs.span("worker", reg):
            seen["inner"] = obs.current_span()

    with obs.span("outer", reg):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the worker thread's span must NOT nest under the main thread's
    assert seen["inner"] == "worker"
    assert reg.histogram("span_seconds", span="worker").count == 1


# ------------------------------------------------------------------- slo
def _snap(**over):
    base = {"counters": {"train/steps": 8},
            "gauges": {"lookahead/compiles{stage=fused}": 1.0},
            "histograms": {"serve/request_seconds": {
                "count": 10, "mean_ms": 1.0, "p50_ms": 1.0,
                "p95_ms": 2.0, "p99_ms": 3.0, "max_ms": 4.0}}}
    base.update(over)
    return base


def test_slo_green_and_violation():
    rules = [{"name": "one-compile",
              "metric": "lookahead/compiles{stage=fused}",
              "op": "==", "threshold": 1},
             {"name": "p99", "metric": "serve/request_seconds:p99_ms",
              "op": "<=", "threshold": 5}]
    assert obs.evaluate_rules(rules, _snap()) == []
    bad = _snap(gauges={"lookahead/compiles{stage=fused}": 2.0})
    findings = obs.evaluate_rules(rules, bad)
    assert len(findings) == 1
    f = findings[0]
    # analysis.passes.Finding shape — gated like audit findings
    from distributed_embeddings_tpu.analysis.passes import Finding
    assert isinstance(f, Finding)
    assert f.pass_name == "slo" and f.fid == "slo:one-compile"
    assert f.severity == "error" and "2" in f.message


def test_slo_absent_metric_is_a_finding():
    rules = [{"name": "occ", "metric": "vocab/occupancy", "op": "<=",
              "threshold": 0.9}]
    findings = obs.evaluate_rules(rules, _snap())
    assert [f.fid for f in findings] == ["slo:occ:absent"]


def test_slo_window_over_snapshot_sequence():
    rules = [{"name": "p99", "metric": "serve/request_seconds:p99_ms",
              "op": "<=", "threshold": 5, "window": 2}]
    spike = _snap(histograms={"serve/request_seconds": {
        "count": 10, "mean_ms": 1.0, "p50_ms": 1.0, "p95_ms": 2.0,
        "p99_ms": 50.0, "max_ms": 60.0}})
    # spike outside the window: green
    assert obs.evaluate_rules(rules, [spike, _snap(), _snap()]) == []
    # spike inside the window: violation
    assert len(obs.evaluate_rules(rules, [_snap(), _snap(), spike])) == 1


def test_slo_malformed_rules_raise():
    with pytest.raises(ValueError, match="missing"):
        obs.evaluate_rules([{"metric": "x", "op": "==", "threshold": 1}],
                           _snap())
    with pytest.raises(ValueError, match="op"):
        obs.evaluate_rules([{"name": "n", "metric": "x", "op": "~",
                             "threshold": 1}], _snap())
    # histogram addressed without a field = rule bug, loud
    with pytest.raises(ValueError, match="summary field"):
        obs.evaluate_rules([{"name": "n",
                             "metric": "serve/request_seconds",
                             "op": "<=", "threshold": 1}], _snap())


def test_slo_load_rules_file(tmp_path):
    path = tmp_path / "rules.json"
    path.write_text(json.dumps({"rules": [
        {"name": "a", "metric": "train/steps", "op": ">=",
         "threshold": 1}]}))
    rules = obs.load_rules(str(path))
    assert rules[0]["name"] == "a"
    assert obs.evaluate_rules(rules, _snap()) == []


def test_checked_in_tier1_rule_file_is_valid():
    # the CI smoke's rule file must always load/validate — a malformed
    # checked-in rule would otherwise only fail inside the smoke
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "slo_tier1.json")
    rules = obs.load_rules(path)
    assert len(rules) >= 6
    names = [r["name"] for r in rules]
    assert "one-fused-compile" in names and "zero-audit-findings" in names


# ------------------------------------------------------- fit integration
SPECS = [(50, 8, "sum")] * 6


def _data(step):
    r = np.random.RandomState(step % 4)
    cats = [r.randint(0, 50, (16, 2)) for _ in SPECS]
    return (np.zeros((16, 1), np.float32), cats,
            r.randn(16).astype(np.float32))


def test_fit_reports_through_one_registry():
    mesh = create_mesh(jax.devices()[:8])
    model = TinyModel(SPECS, mesh)
    rng = np.random.RandomState(0)
    params = {
        "embedding": model.embedding.init(jax.random.PRNGKey(0)),
        "head": {"w": jnp.asarray(rng.randn(48, 1).astype(np.float32)
                                  * 0.1)},
    }
    reg = obs.MetricRegistry()
    params, _, hist = training.fit(
        model, params, (_data(i) for i in range(6)), steps=6,
        optimizer="adagrad", lr=0.1, log_every=0, registry=reg)
    snap = hist["metrics_snapshot"]
    assert snap["counters"]["train/steps"] == 6
    assert snap["counters"]["train/examples"] == 6 * 16
    assert snap["histograms"]["span_seconds{span=train/step}"][
        "count"] == 6
    assert snap["gauges"]["train/examples_per_sec"] > 0
    # ingest stage histograms share the SAME registry (and agree with
    # the history's own stage accounting)
    assert snap["histograms"]["ingest/stage_seconds{stage=read}"][
        "count"] == 6
    assert (hist["ingest_stages"]["read"]
            == snap["histograms"]["ingest/stage_seconds{stage=read}"])
    # the static exchange gauges rode along
    assert "exchange/touched_rows_per_step" in snap["gauges"]
    assert snap == reg.snapshot()
