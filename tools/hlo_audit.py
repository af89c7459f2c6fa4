"""Static program auditor driver: the full pass suite over the standard
program matrix (ISSUE 10).

The heavy lifting lives in `distributed_embeddings_tpu.analysis`:
`ir` parses a lowered StableHLO module ONCE, `passes` proves the repo's
invariants over it (sort bounds, exact collective bytes vs the
padding-report model, overlap classification, wire-seam coverage,
donation policy, dtype promotion, dead/duplicate collectives — run
``--list-passes`` for the catalog, docs/analysis.md for the long form),
and `programs` builds the audited matrix: monolithic train step (f32 +
bf16 wire), lookahead fused + prefetch, serve forward, vocab-slack
plan, each lowered once over an 8-virtual-device mesh and shared across
all passes (the <=60s CI budget).

This file is the thin CLI on top:

  python tools/hlo_audit.py                # one JSON line per record
  python tools/hlo_audit.py --assert      # CI gate: exit 1 on any
                                           # finding not allowlisted in
                                           # tools/audit_baseline.json,
                                           # any legacy arm over bound,
                                           # or any mutation fixture its
                                           # pass FAILS to flag
  python tools/hlo_audit.py --list-passes  # pass catalog

The baseline (``tools/audit_baseline.json``) is a checked-in allowlist
of ``"program:finding-id"`` strings, diffed like a snapshot — it ships
EMPTY: every known invariant violation is a bug, not an exception. The
mutation arm is the auditor auditing itself: for every pass, a program
seeded with the violation it exists to catch (a naked lax.all_to_all
around the seam, a forced f64 upcast, a self-duplicated collective, ...)
must produce exactly the expected finding — an auditor that cannot fail
is not a gate.

Legacy per-arm records (`audit_tapped_step` sort gates at 30M-row
vocabs/tiled/hot shards, `wire_byte_arms`, `audit_lookahead_overlap`)
still run and still gate.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_embeddings_tpu.analysis import programs as _programs  # noqa: E402
from distributed_embeddings_tpu.analysis import ir, passes  # noqa: E402

# the test suite reaches these by their historical names
_build_model = _programs.build_model
_head_params = _programs.head_params
_ensure_world = _programs.ensure_world
audit_tapped_step = _programs.audit_tapped_step
audit_exchange_bytes = _programs.audit_exchange_bytes
audit_lookahead_overlap = _programs.audit_lookahead_overlap
wire_byte_arms = _programs.wire_byte_arms
WIRE_BYTE_MIN_REDUCTION = _programs.WIRE_BYTE_MIN_REDUCTION

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "audit_baseline.json")

DEFAULT_ARMS = (
    # (optimizer, strategy, lookup_path, hot_rows)
    ("adagrad", "sort", None, 0),
    ("adagrad", "tiled", None, 0),
    ("adam", "sort", None, 0),
    ("sgd", "tiled", None, 0),
    ("adagrad", "tiled", "tiled", 0),
    # fused pallas strategy (ISSUE 12): the deduped-row tile walk must
    # consume the folded forward sort — same one-sort-per-group bound as
    # the sort/tiled arms; the fully-fused arm (fused forward + pallas
    # update) shares the tiled-forward 2/group bound (the residual
    # inverse-permute sort)
    ("adagrad", "pallas", None, 0),
    ("adam", "pallas", None, 0),
    ("sgd", "pallas", None, 0),
    ("adagrad", "pallas", "fused", 0),
    # hot-row replication (ISSUE 4): same sort bound as the hot-less arm —
    # the membership split (searchsorted) and the replicated dense hot
    # update must add ZERO sort instructions per exchange group
    ("adagrad", "sort", None, 1024),
    ("sgd", "sort", None, 1024),
)


def load_baseline(path: str = BASELINE_PATH) -> set:
    """The allowlist: a set of "program:finding-id" strings."""
    try:
        with open(path) as f:
            return set(json.load(f).get("allow", []))
    except FileNotFoundError:
        return set()


def run_matrix(baseline: set, **kw) -> tuple:
    """Lower the program matrix once, run every applicable pass on each
    parsed module; returns (records, failures) where a failure is any
    finding whose "program:fid" key is not allowlisted."""
    records, failures = [], []
    for prog in _programs.program_matrix(**kw):
        names = [n for n in passes.PASS_REGISTRY
                 if n not in prog.skip_passes]
        findings = passes.run_passes(prog.module, prog.ctx, passes=names)
        rec = {"program": prog.name, "passes_run": len(names),
               "findings": [f.to_dict() for f in findings]}
        for f in findings:
            key = f"{prog.name}:{f.fid}"
            if key not in baseline:
                failures.append({"program": prog.name, **f.to_dict()})
        records.append(rec)
    return records, failures


def run_mutations() -> tuple:
    """Every pass must FLAG its seeded violation — a mutation that does
    NOT produce exactly its expected findings is itself a failure (the
    gate went blind)."""
    records, failures = [], []
    for case in _programs.mutation_cases():
        mod = ir.parse_module(case.text)
        got = tuple(f.fid for f in passes.run_passes(
            mod, case.ctx, passes=[case.pass_name]))
        ok = got == case.expect_fids
        rec = {"mutation": case.name, "pass": case.pass_name,
               "expected_findings": list(case.expect_fids),
               "got_findings": list(got), "flagged": ok}
        records.append(rec)
        if not ok:
            failures.append(rec)
    return records, failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--assert", dest="do_assert", action="store_true",
                   help="exit 1 on any non-allowlisted finding, legacy "
                        "arm over bound, or unflagged mutation")
    p.add_argument("--list-passes", action="store_true",
                   help="print the pass catalog and exit")
    p.add_argument("--baseline", default=BASELINE_PATH,
                   help="allowlist JSON (default tools/audit_baseline.json)")
    p.add_argument("--vocab", type=int, default=30_000_000)
    p.add_argument("--width", type=int, default=8)
    p.add_argument("--unfolded", action="store_true",
                   help="also report the fold_sort=False baseline arms")
    p.add_argument("--skip-wire", action="store_true",
                   help="skip the meshed collective-byte wire arms")
    p.add_argument("--skip-lookahead", action="store_true",
                   help="skip the meshed lookahead overlap arm")
    p.add_argument("--skip-matrix", action="store_true",
                   help="skip the pass-framework program matrix")
    p.add_argument("--skip-mutations", action="store_true",
                   help="skip the mutation-fixture self-check")
    args = p.parse_args(argv)

    if args.list_passes:
        for name, doc in passes.list_passes():
            print(f"{name:22s} {doc}")
        return 0

    import jax
    jax.config.update("jax_platforms",
                      os.environ.get("JAX_PLATFORMS") or "cpu")
    # meshed lowerings need the virtual world BEFORE the backend wakes
    if not (args.skip_wire and args.skip_lookahead and args.skip_matrix
            and args.skip_mutations):
        _ensure_world(8)
    failures = []

    # ---- legacy per-arm sort gates
    for optimizer, strategy, lookup, hot_rows in DEFAULT_ARMS:
        folds = (True, False) if args.unfolded else (True,)
        for fold in folds:
            rec = audit_tapped_step(vocab=args.vocab, width=args.width,
                                    optimizer=optimizer, strategy=strategy,
                                    lookup_path=lookup, fold=fold,
                                    hot_rows=hot_rows)
            if fold and rec["hlo_sort"] > rec["sort_bound"]:
                rec["over_bound"] = True
                failures.append(rec)
            print(json.dumps(rec), flush=True)

    # ---- legacy wire byte arms (ratio + zero-bf16 contract)
    if not args.skip_wire:
        arms = wire_byte_arms()
        for rec in arms:
            print(json.dumps(rec), flush=True)
        base, comp = arms
        if "skipped" not in comp:
            if base.get("bf16_collective_bytes"):
                base["over_bound"] = True
                failures.append(base)
            red = comp.get("float_bytes_reduction_vs_f32")
            if red is None or red < WIRE_BYTE_MIN_REDUCTION:
                comp["over_bound"] = True
                failures.append(comp)

    # ---- legacy lookahead overlap arm
    if not args.skip_lookahead:
        rec = audit_lookahead_overlap()
        print(json.dumps(rec), flush=True)
        if "skipped" not in rec and rec.get("over_bound"):
            failures.append(rec)

    # ---- the pass-framework matrix (ISSUE 10)
    if not args.skip_matrix:
        baseline = load_baseline(args.baseline)
        records, fs = run_matrix(baseline)
        for rec in records:
            print(json.dumps(rec), flush=True)
        failures.extend(fs)

    # ---- mutation self-check: every pass must flag its seeded violation
    if not args.skip_mutations:
        records, fs = run_mutations()
        print(json.dumps({
            "mutations_total": len(records),
            "mutations_flagged": sum(r["flagged"] for r in records),
            "unflagged": [r for r in records if not r["flagged"]],
        }), flush=True)
        failures.extend(fs)

    if args.do_assert and failures:
        print(f"hlo_audit: {len(failures)} failure(s) — non-allowlisted "
              "findings, arms over bound, or blind mutation gates",
              file=sys.stderr)
        for f in failures:
            print(f"  {json.dumps(f)[:300]}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
