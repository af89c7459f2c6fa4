"""Tier-1 observability smoke (ISSUE 11/14/25): one registry across a
real fit -> publish -> serve loop, schema-checked, SLO-gated, and the
train step's stage scopes checked in its compiled program.

What it drives (tiny shapes, CPU, ~a minute):

  1. `training.fit` with the lookahead engine AND a publishing
     `TableStore`, all reporting into ONE `obs.MetricRegistry` — train
     spans/counters, ingest stage histograms, lookahead patch/compile
     metrics, store publish counters land in the same namespace.
  2. An `InferenceEngine` replica consuming the published stream
     (`poll_updates`) and serving requests through a `MicroBatcher` on
     the SAME registry — apply/staleness/latency metrics join the
     snapshot.
  3. The static audit matrix (tools/hlo_audit.py), its finding count
     exported as the ``audit/findings`` gauge.
  4. Snapshot SCHEMA assertions (the keys the soak harness will script
     against), a JSONL export/parse round trip, and the checked-in
     SLO rule file
     (tools/slo_tier1.json) evaluated over the snapshot — compile-count
     and audit-findings rules active, NO perf rules (CI hosts are
     steal-noisy; perf gates live in docs/perf_model.md).
  5. Stage scopes (ISSUE 25): the smoke's own train step
     (`make_sparse_train_step` over the same model and batch shapes),
     lowered through the step's handle `step_fn.lower` and compiled, is
     named ``jit_det_train_step`` and its operations carry every
     ``det.*`` stage of `obs.stages.STAGES` that a tables-only model
     runs in their ``op_name`` paths.
  6. Flight-recorder checks: the ring holds the run's spans, the
     chrome-trace export loads and balances, and the lineage tracks
     cover every published version.
  7. Metric-catalog drift gate: every metric FAMILY this driven run
     observes in the snapshot must appear in docs/observability.md's
     catalog — a new metric can no longer ship undocumented.

Exit 1 on any schema violation or SLO finding. Run:

    env JAX_PLATFORMS=cpu python tools/obs_smoke.py
"""

import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the CPU suite's donation posture (see training.default_donate):
# donated executables + the persistent cache are not trustworthy on
# jaxlib 0.4.36 XLA:CPU
os.environ.setdefault("DET_STEP_DONATE", "0")

from distributed_embeddings_tpu.analysis import programs as _programs  # noqa: E402

# meshed lowerings need the virtual world BEFORE the backend wakes
WORLD = _programs.ensure_world(8)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from distributed_embeddings_tpu import obs, training  # noqa: E402
from distributed_embeddings_tpu.serving import (InferenceEngine,  # noqa: E402
                                                MicroBatcher)
from distributed_embeddings_tpu.store import TableStore  # noqa: E402

VOCAB, WIDTH, TABLES, HOTNESS = 2000, 16, 4, 2
BATCH, STEPS, PUBLISH_EVERY = 256, 8, 4
REQUESTS = 6


def make_batches(rng, n):
    out = []
    for _ in range(n):
        num = np.zeros((BATCH, 1), np.float32)
        cats = [rng.randint(0, VOCAB, size=(BATCH, HOTNESS))
                .astype(np.int32) for _ in range(TABLES)]
        lab = rng.randn(BATCH).astype(np.float32)
        out.append((num, cats, lab))
    return out


def check(cond, msg):
    if not cond:
        print(f"obs smoke FAIL: {msg}", file=sys.stderr)
        sys.exit(1)


def main() -> int:
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(jax.devices()[:WORLD])
    rng = np.random.RandomState(0)
    reg = obs.default_registry()
    obs.reset_default_recorder()      # this run's ring only (check 6)
    tmp = tempfile.mkdtemp(prefix="det_obs_smoke_")
    try:
        # ---- 1. publisher fit: lookahead engine + weight streaming --
        model = _programs.build_model(VOCAB, WIDTH, "sum", tables=TABLES,
                                      mesh=mesh)
        params = {"embedding": model.embedding.init(jax.random.PRNGKey(0))}
        store = TableStore(model.embedding, params["embedding"])
        params, opt_state, history = training.fit(
            model, params, make_batches(rng, STEPS), steps=STEPS,
            optimizer="adagrad", lr=0.05, log_every=0, lookahead=1,
            store=store, publish_every=PUBLISH_EVERY, publish_dir=tmp,
            registry=reg)
        check("metrics_snapshot" in history,
              "fit history has no metrics_snapshot")
        check("metrics_error" not in history,
              f"fit metrics_error: {history.get('metrics_error')}")

        # ---- 2. serving replica consuming the published stream ------
        emb2 = _programs.build_model(VOCAB, WIDTH, "sum", tables=TABLES,
                                     mesh=mesh).embedding
        engine = InferenceEngine(emb2, emb2.init(jax.random.PRNGKey(1)),
                                 registry=reg)
        applied = engine.poll_updates(tmp)
        check(len(applied) >= 1, "replica applied no published files")
        engine.warmup([64])
        batcher = MicroBatcher(engine, max_batch=64, registry=reg)
        for _ in range(REQUESTS):
            n = int(rng.randint(1, 32))
            batcher.submit([rng.randint(0, VOCAB, size=(n, HOTNESS))
                            .astype(np.int64) for _ in range(TABLES)])
        batcher.flush()

        # ---- 3. static audit -> gauge ------------------------------
        import importlib.util as ilu
        spec = ilu.spec_from_file_location(
            "det_hlo_audit", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "hlo_audit.py"))
        ha = ilu.module_from_spec(spec)
        spec.loader.exec_module(ha)
        recs, _ = ha.run_matrix(ha.load_baseline(), world=WORLD)
        audit_ids = sorted({f"{r['program']}:{f['fid']}"
                            for r in recs for f in r["findings"]})
        reg.gauge("audit/findings").set(len(audit_ids))
        if audit_ids:
            print(f"audit findings: {audit_ids}", file=sys.stderr)

        # ---- 5. stage scopes in the step's own compiled program -----
        from distributed_embeddings_tpu.obs import stages
        init_fn, step_fn = training.make_sparse_train_step(
            model, "adagrad", lr=0.05)
        num, cats, lab = make_batches(rng, 1)[0]
        compiled = step_fn.lower(params, init_fn(params), num, cats,
                                 lab).compile()
        text = compiled.as_text()
        check(step_fn.name == stages.STEP_NAME
              and f"HloModule jit_{stages.STEP_NAME}" in text,
              f"the step is not named {stages.STEP_NAME}")
        import re as _re
        staged = set(_re.findall(
            r'op_name="[^"]*/' + _re.escape(stages.PREFIX) + r'([a-z_]+)/',
            text))
        # the smoke's model is tables only: no dense optimizer runs
        want = set(stages.STAGES) - {"dense_opt"}
        check(staged == want,
              f"compiled step's stages {sorted(staged)} != {sorted(want)}")

        # ---- 6. flight recorder: ring, export, lineage --------------
        rec = obs.default_recorder()
        doc = rec.export(os.path.join(tmp, "flight_trace.json"))
        with open(os.path.join(tmp, "flight_trace.json")) as f:
            doc2 = json.load(f)
        check(doc2["traceEvents"] == doc["traceEvents"],
              "flight-recorder export round trip")
        depth = {}
        for ev in doc["traceEvents"]:
            if ev["ph"] == "B":
                depth[ev["tid"]] = depth.get(ev["tid"], 0) + 1
            elif ev["ph"] == "E":
                depth[ev["tid"]] = depth.get(ev["tid"], 0) - 1
                check(depth[ev["tid"]] >= 0, "unbalanced E in export")
        check(all(v == 0 for v in depth.values()),
              f"unbalanced spans in export: {depth}")
        pub_versions = {i["version"] for i in history.get("published", [])
                        if i["kind"] != "paused"}
        lineage = set(rec.lineage_versions())
        check(pub_versions <= lineage,
              f"published versions {sorted(pub_versions - lineage)} "
              "missing from lineage tracks")

        # ---- 4a. snapshot schema -----------------------------------
        snap = reg.snapshot()
        for section in ("counters", "gauges", "histograms"):
            check(section in snap, f"snapshot missing {section!r}")
        c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
        check(c.get("train/steps") == STEPS,
              f"train/steps {c.get('train/steps')} != {STEPS}")
        check(c.get("train/examples") == STEPS * BATCH, "train/examples")
        check(c.get("lookahead/steps") == STEPS, "lookahead/steps")
        check(c.get("store/publishes", 0) >= 2, "store/publishes")
        check(c.get("store/applies", 0) >= 1, "store/applies")
        check(c.get("serve/requests") == REQUESTS, "serve/requests")
        check(g.get("lookahead/compiles{stage=fused}") == 1.0,
              f"fused compiles {g.get('lookahead/compiles{stage=fused}')}")
        check(g.get("train/examples_per_sec", 0) > 0, "examples_per_sec")
        check("exchange/touched_rows_per_step" in g, "exchange gauges")
        # ISSUE 12: the run must say which sparse-update kernel family
        # it could dispatch to (-1 = CPU interpret, the expected value
        # here) and which path the step spans were attributed to
        check("kernels/gate_verdict{impl=pallas}" in g,
              "kernel gate-verdict gauges")
        check(any(k.startswith("update/impl{impl=") for k in g),
              "per-strategy update-path gauge")
        check(h["span_seconds{span=train/step}"]["count"] == STEPS,
              "train/step span count")
        check(h["serve/request_seconds"]["count"] == REQUESTS,
              "request latency count")
        check(any(k.startswith("ingest/stage_seconds") for k in h),
              "ingest stage histograms")
        # ---- 4b. export round trips --------------------------------
        jsonl = os.path.join(tmp, "metrics.jsonl")
        reg.export_jsonl(jsonl, extra={"source": "obs_smoke"})
        reg.export_jsonl(jsonl)
        lines = [json.loads(ln) for ln in open(jsonl)]
        check(len(lines) == 2 and lines[0]["counters"] == snap["counters"],
              "JSONL export round trip")

        # ---- 4c. the checked-in SLO rules --------------------------
        rules_path = os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "slo_tier1.json")
        findings = obs.evaluate_rules(obs.load_rules(rules_path), snap)
        for f in findings:
            print(f"SLO violation: {f.fid}: {f.message}", file=sys.stderr)
        check(not findings, f"{len(findings)} SLO finding(s)")

        # ---- 7. metric-catalog drift gate --------------------------
        # every family name this driven run observes must appear in
        # docs/observability.md's catalog (wildcard rows like
        # ``exchange/*`` cover their prefix) — new metrics can no
        # longer ship undocumented
        doc_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")
        with open(doc_path) as f:
            doc_text = f.read()
        wildcards = [m.group(1) + "/"
                     for m in _re.finditer(r"`([\w/]+)/\*`", doc_text)]
        families = sorted({key.split("{", 1)[0]
                           for section in snap.values()
                           for key in section})
        undocumented = [fam for fam in families
                        if fam not in doc_text
                        and not any(fam.startswith(w) for w in wildcards)]
        check(not undocumented,
              f"metric families missing from docs/observability.md: "
              f"{undocumented}")

        print(json.dumps({
            "obs_smoke": "ok", "world": WORLD,
            "train_steps": c["train/steps"],
            "publishes": c["store/publishes"],
            "applies": c["store/applies"],
            "requests": c["serve/requests"],
            "fused_compiles": g["lookahead/compiles{stage=fused}"],
            "audit_findings": len(audit_ids),
            "slo_rules_evaluated": len(obs.load_rules(rules_path)),
            "step_stages": sorted(staged),
            "flight_events": len(doc["traceEvents"]),
            "lineage_versions": sorted(lineage),
            "metric_families_checked": len(families),
        }))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
