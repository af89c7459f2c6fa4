"""Device-time attribution CLI (ISSUE 14): parse a jax profiler
capture, attribute device-op time to the span annotations that
dispatched it, and print the measured-vs-perf_model reconciliation
table — the artifact every chip-run arm files next to its bench
record (docs/perf_model.md "Chip-run runbook").

Usage:

    python tools/device_attribution.py <logdir> \
        [--snapshot metrics_snapshot.json] \
        [--projections projections.json] [--tolerance 0.5] [--json]

`<logdir>` is the directory `utils.profiling.trace` (or `bench.py
--profile`) captured into — the newest ``plugins/profile/<run>/
*.trace.json.gz`` under it is parsed. ``--snapshot`` (a bench record's
``metrics_snapshot`` or a bare registry snapshot) pins the span-window
set to the run's recorded ``span_seconds{span=}`` paths; without it a
shape-based fallback matches annotation-looking events.
``--projections`` is a flat ``{phase: projected_ms}`` JSON (e.g. the
``kernels_tpu_projections`` block of a kernels record); each row
settles or falsifies against the measured per-span device time.
Exit 0 always unless parsing fails — the table is evidence, not a
gate; pipe ``--json`` into jq for gating.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_embeddings_tpu.obs import attribution  # noqa: E402


def _span_paths_from_snapshot(path: str):
    with open(path) as f:
        doc = json.load(f)
    return attribution.span_paths_from_snapshot(doc)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="attribute profiler device time to span annotations")
    p.add_argument("logdir", help="profiler capture directory")
    p.add_argument("--snapshot", default=None,
                   help="bench record / registry snapshot JSON whose "
                        "span_seconds keys pin the window set")
    p.add_argument("--projections", default=None,
                   help="{phase: projected_ms} JSON to reconcile against")
    p.add_argument("--tolerance", type=float, default=0.5,
                   help="relative tolerance for a projection to settle")
    p.add_argument("--json", action="store_true",
                   help="emit the attribution dict as one JSON line")
    args = p.parse_args(argv)

    span_paths = (_span_paths_from_snapshot(args.snapshot)
                  if args.snapshot else None)
    try:
        att = attribution.attribute_logdir(args.logdir,
                                           span_paths=span_paths)
    except FileNotFoundError as e:
        print(f"device_attribution: {e}", file=sys.stderr)
        return 1
    if args.projections:
        with open(args.projections) as f:
            proj = json.load(f)
        att["reconciliation"] = attribution.reconciliation_table(
            att, proj, tolerance_frac=args.tolerance)
    if args.json:
        print(json.dumps(att))
        return 0

    total_ms = att["total_device_seconds"] * 1e3
    print(f"trace: {att['trace_file']}")
    print(f"device total: {total_ms:.3f} ms over "
          f"{att['device_op_count']} ops; "
          f"{att['span_window_count']} span windows; "
          f"coverage {att['coverage_frac']:.1%}")
    width = max([len(s) for s in att["spans"]] + [12])
    for span, sec in sorted(att["spans"].items(),
                            key=lambda kv: -kv[1]):
        print(f"  {span:<{width}}  {sec * 1e3:10.3f} ms"
              f"  {sec * 1e3 / max(total_ms, 1e-9):6.1%}")
    print(f"  {'(unattributed)':<{width}}  "
          f"{att['unattributed_seconds'] * 1e3:10.3f} ms")
    coll = att["collective"]
    if coll["device_seconds"]:
        print(f"collectives: {coll['device_seconds'] * 1e3:.3f} ms, "
              f"exposed {coll['exposed_seconds'] * 1e3:.3f} ms "
              f"(fraction {coll['exposed_fraction']})")
    for row in att.get("reconciliation", []):
        print(f"  [{row['verdict']:>10}] {row['phase']}: projected "
              f"{row['projected_ms']} ms, measured {row['measured_ms']} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
