"""Device-time attribution: profiler trace -> per-span device seconds
(ISSUE 14).

`obs.span` opens a `jax.profiler.TraceAnnotation` for every span path
(PR 11), so a profiler capture (`utils.profiling.trace`) already
contains the span windows AND the device-op events side by side — but
nothing ever consumed the match. This module closes the loop: parse
the capture's Chrome-trace export (`plugins/profile/<run>/*.trace.
json.gz`, written by jax's profiler on `stop_trace`), classify events
into span windows (host-side annotation events whose names are span
paths) and device ops (events carrying ``hlo_op``/``hlo_module`` args,
or living in a ``/device:*`` process — TPU op tracks and XLA:CPU thunk
executions both match), and attribute every device op to the INNERMOST
span window containing its midpoint. The result answers the question
every bench record since r03 has begged: where did this step's DEVICE
time actually go, per phase?

Attribution is exhaustive by construction: every device op lands in
exactly one span bucket or in ``unattributed`` (dispatched outside any
open span — async-dispatch tail on TPU, profiler warmup, compile-time
autotuning), so ``sum(spans) + unattributed == total`` exactly. The
collective breakdown additionally classifies exchange ops
(all-to-all / all-gather / reduce-scatter / collective-permute /
all-reduce) and measures how much of their device time is EXPOSED
(not covered by concurrent dense-compute ops on other device tracks) —
the lookahead arm's headline metric (docs/perf_model.md "Lookahead
prefetch": projected speedup = (E + D) / max(E, D) where E is exactly
this exposed fraction times the exchange term).

Outputs:
  * `attribute_logdir(logdir, registry=)` — the ``device_attribution``
    dict bench records embed, and (with a registry) the
    ``device/span_seconds{span=}`` / ``device/unattributed_seconds`` /
    ``device/total_seconds`` gauges SLO rules can address.
  * `reconciliation_table(att, projections)` — measured-vs-perf_model
    rows: each projection either SETTLES (within tolerance) or
    FALSIFIES, the chip-run record of docs/perf_model.md.
  * `tools/device_attribution.py` — the CLI over both.
"""

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["find_trace_file", "load_trace_events", "attribute_device_time",
           "export_device_gauges", "attribute_logdir",
           "reconciliation_table", "span_paths_from_snapshot",
           "COLLECTIVE_RE", "COMPUTE_RE"]

# HLO op-name fingerprints. Collectives match the exchange family the
# wire/overlap audits track (`utils.profiling._COLLECTIVES`, dash form
# as HLO spells them); compute matches the dense ops the overlap audit
# treats as hideable-under (dot/conv and the fusions XLA folds them
# into).
COLLECTIVE_RE = re.compile(
    r"(ragged-)?all-to-all|all-gather|all-reduce|reduce-scatter"
    r"|collective-permute", re.IGNORECASE)
COMPUTE_RE = re.compile(r"\b(dot|convolution|cudnn|fusion)", re.IGNORECASE)


def find_trace_file(logdir: str) -> str:
    """The newest profiler run's ``*.trace.json(.gz)`` under `logdir`
    (jax writes ``plugins/profile/<timestamp>/<host>.trace.json.gz``
    on `stop_trace`). Raises FileNotFoundError when no capture
    landed."""
    pats = [os.path.join(logdir, "plugins", "profile", "*", p)
            for p in ("*.trace.json.gz", "*.trace.json")]
    pats += [os.path.join(logdir, p)
             for p in ("*.trace.json.gz", "*.trace.json")]
    hits: List[str] = []
    for pat in pats:
        hits.extend(glob.glob(pat))
    if not hits:
        raise FileNotFoundError(
            f"no profiler chrome trace (*.trace.json[.gz]) under "
            f"{logdir!r} — did the capture run?")
    return max(hits, key=os.path.getmtime)


def load_trace_events(path: str) -> List[dict]:
    """The `traceEvents` list of one Chrome-trace JSON file (gzipped or
    plain; object form or bare event list)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        doc = json.loads(f.read().decode("utf-8", errors="replace"))
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def _device_pids(events: Sequence[dict]) -> set:
    """Process ids whose metadata names them a device timeline."""
    pids = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and str(e.get("args", {}).get("name", ""))
                .startswith("/device:")):
            pids.add(e.get("pid"))
    return pids


def _is_device_op(e: dict, device_pids: set) -> bool:
    args = e.get("args")
    if isinstance(args, dict) and ("hlo_op" in args
                                   or "hlo_module" in args
                                   or "hlo_category" in args):
        return True
    return e.get("pid") in device_pids


def _span_windows(events: Sequence[dict], span_paths,
                  device_pids: set
                  ) -> List[Tuple[float, float, str, object]]:
    """(start_us, end_us, path, host_tid) for every span-annotation
    event.

    With `span_paths` (the registry's recorded span set) the match is
    exact. Without, fall back to the shape of an annotation: a
    complete host event whose name contains ``/`` and is neither a
    python-tracer frame (``$``-prefixed) nor a device op."""
    wins = []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        name = e.get("name", "")
        if span_paths is not None:
            if name not in span_paths:
                continue
        else:
            if ("/" not in name or name.startswith("$")
                    or "::" in name
                    or _is_device_op(e, device_pids)):
                continue
        ts = float(e["ts"])
        wins.append((ts, ts + float(e["dur"]), name, e.get("tid")))
    return wins


def _merged_intervals(ivs: List[Tuple[float, float]]
                      ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, t in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def _overlap(s: float, t: float,
             merged: List[Tuple[float, float]]) -> float:
    """Length of [s, t] covered by the merged interval list (us)."""
    cov = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= t:
            break
        cov += min(b, t) - max(a, s)
    return cov


def attribute_device_time(events: Sequence[dict],
                          span_paths: Optional[set] = None) -> dict:
    """Attribute device-op time to enclosing span annotations.

    Args:
      events: Chrome-trace `traceEvents` (from `load_trace_events`).
      span_paths: the span paths to treat as attribution windows
        (typically the registry's ``span_seconds{span=}`` key set);
        None = shape-based fallback (see `_span_windows`).

    Returns the ``device_attribution`` dict: ``total_device_seconds``,
    ``spans`` {path: seconds}, ``unattributed_seconds``,
    ``coverage_frac``, op/window counts, a per-op-category split, and
    the ``collective`` exposure block (global and per-span) —
    seconds rounded to 9 places; the sum identity holds exactly in the
    unrounded accumulators and within 1e-6 after rounding.

    Concurrent-span honesty: time-midpoint containment cannot tell
    WHICH host thread dispatched a device op, so when windows from
    more than one host thread contain an op's midpoint (e.g. a serving
    span overlapping a background trainer's step span in wall time)
    the shortest-window assignment is a guess. ``ambiguous_seconds``
    totals the device time in that state — a large value means the
    per-span split should be read as approximate, not that the
    measurement failed (the sum identity is unaffected).
    """
    events = [e for e in events if isinstance(e, dict)]
    device_pids = _device_pids(events)
    wins = _span_windows(events, span_paths, device_pids)
    # innermost-first candidate order: shortest window wins a midpoint
    wins_sorted = sorted(wins, key=lambda w: w[1] - w[0])
    # ambiguity zones: time ranges where windows from DIFFERENT host
    # threads coexist (precomputed once — a per-op full window scan
    # would make big traces quadratic)
    by_tid: Dict[object, List[Tuple[float, float]]] = {}
    for s, t, _, wtid in wins:
        by_tid.setdefault(wtid, []).append((s, t))
    amb_zones: List[Tuple[float, float]] = []
    if len(by_tid) > 1:
        merged = {tid: _merged_intervals(iv) for tid, iv in by_tid.items()}
        tids = list(merged)
        for i, ta in enumerate(tids):
            for tb in tids[i + 1:]:
                for a1, b1 in merged[ta]:
                    for a2, b2 in merged[tb]:
                        lo, hi = max(a1, a2), min(b1, b2)
                        if lo < hi:
                            amb_zones.append((lo, hi))
        amb_zones = _merged_intervals(amb_zones)

    ops = [e for e in events
           if e.get("ph") == "X" and "dur" in e
           and _is_device_op(e, device_pids)]
    total = 0.0
    per_span: Dict[str, float] = {}
    unattributed = 0.0
    ambiguous = 0.0
    categories: Dict[str, float] = {}
    compute_ivs: List[Tuple[float, float]] = []
    coll_ops: List[Tuple[float, float, Optional[str]]] = []
    for e in ops:
        ts, dur = float(e["ts"]), float(e["dur"])
        total += dur
        mid = ts + dur / 2.0
        name = str(e.get("name", ""))
        hlo = str((e.get("args") or {}).get("hlo_op", name))
        assigned = None
        for s, t, path, _ in wins_sorted:
            if s <= mid <= t:
                assigned = path
                break
        if assigned is not None and _overlap(mid, mid + 1e-9,
                                             amb_zones) > 0:
            ambiguous += dur
        if assigned is None:
            unattributed += dur
        else:
            per_span[assigned] = per_span.get(assigned, 0.0) + dur
        if COLLECTIVE_RE.search(hlo) or COLLECTIVE_RE.search(name):
            categories["collective"] = (categories.get("collective", 0.0)
                                        + dur)
            coll_ops.append((ts, ts + dur, assigned))
        elif COMPUTE_RE.search(hlo) or COMPUTE_RE.search(name):
            categories["compute"] = categories.get("compute", 0.0) + dur
            compute_ivs.append((ts, ts + dur))
        else:
            categories["other"] = categories.get("other", 0.0) + dur

    merged_compute = _merged_intervals(compute_ivs)
    coll_total = 0.0
    coll_exposed = 0.0
    per_span_coll: Dict[str, Dict[str, float]] = {}
    for s, t, path in coll_ops:
        dur = t - s
        exp = dur - _overlap(s, t, merged_compute)
        coll_total += dur
        coll_exposed += exp
        if path is not None:
            d = per_span_coll.setdefault(path, {"seconds": 0.0,
                                                "exposed_seconds": 0.0})
            d["seconds"] += dur
            d["exposed_seconds"] += exp

    us = 1e-6

    def sec(v):
        return round(v * us, 9)

    att = {
        "total_device_seconds": sec(total),
        "spans": {p: sec(v) for p, v in sorted(per_span.items())},
        "unattributed_seconds": sec(unattributed),
        "ambiguous_seconds": sec(ambiguous),
        "coverage_frac": round((total - unattributed) / total, 6)
        if total else 0.0,
        "device_op_count": len(ops),
        "span_window_count": len(wins),
        "categories_seconds": {k: sec(v)
                               for k, v in sorted(categories.items())},
        "collective": {
            "device_seconds": sec(coll_total),
            "exposed_seconds": sec(coll_exposed),
            "overlapped_seconds": sec(coll_total - coll_exposed),
            "exposed_fraction": round(coll_exposed / coll_total, 6)
            if coll_total else None,
            "per_span": {
                p: {"seconds": sec(d["seconds"]),
                    "exposed_seconds": sec(d["exposed_seconds"]),
                    "exposed_fraction": round(
                        d["exposed_seconds"] / d["seconds"], 6)
                    if d["seconds"] else None}
                for p, d in sorted(per_span_coll.items())},
        },
    }
    return att


def export_device_gauges(att: dict, registry) -> None:
    """Publish an attribution onto a registry: one
    ``device/span_seconds{span=}`` gauge per attributed span, plus
    ``device/unattributed_seconds`` and ``device/total_seconds`` — the
    device-true twins of the host-side ``span_seconds`` histograms,
    SLO-addressable like everything else."""
    for path, seconds in att.get("spans", {}).items():
        registry.gauge("device/span_seconds", span=path).set(seconds)
    registry.gauge("device/unattributed_seconds").set(
        att.get("unattributed_seconds", 0.0))
    registry.gauge("device/total_seconds").set(
        att.get("total_device_seconds", 0.0))
    coll = att.get("collective", {})
    if coll.get("exposed_fraction") is not None:
        registry.gauge("device/exposed_exchange_fraction").set(
            coll["exposed_fraction"])


def span_paths_from_snapshot(snapshot: dict) -> Optional[set]:
    """The span paths a registry snapshot (or a bench record carrying a
    ``metrics_snapshot``) has recorded — the ``span_seconds{span=}``
    histogram keys, parsed ONCE here for every consumer (the
    `attribute_logdir` registry path and the CLI's ``--snapshot``
    mode must never drift on the key format)."""
    snap = snapshot.get("metrics_snapshot", snapshot)
    paths = set()
    for key in snap.get("histograms", {}):
        m = re.match(r"^span_seconds\{span=(.+)\}$", key)
        if m:
            paths.add(m.group(1))
    return paths or None


def _registry_span_paths(registry) -> Optional[set]:
    if registry is None:
        return None
    return span_paths_from_snapshot(registry.snapshot())


def attribute_logdir(logdir: str, registry=None,
                     span_paths: Optional[set] = None) -> dict:
    """Parse the newest capture under `logdir` and attribute it. With a
    `registry`: the span window set defaults to the registry's recorded
    span paths and the ``device/*`` gauges are exported onto it.
    Returns the attribution dict (plus ``trace_file``)."""
    path = find_trace_file(logdir)
    if span_paths is None:
        span_paths = _registry_span_paths(registry)
    att = attribute_device_time(load_trace_events(path),
                                span_paths=span_paths)
    att["trace_file"] = os.path.basename(path)
    if registry is not None:
        export_device_gauges(att, registry)
    return att


def reconciliation_table(att: dict, projections: Dict[str, float],
                         tolerance_frac: float = 0.5) -> List[dict]:
    """Measured-vs-projection rows: for each perf_model projection
    ``{phase_or_span: projected_ms}``, find the measured per-span
    device milliseconds (exact span-path match, else substring match
    over attributed spans, else the total) and mark it ``settled``
    (within ``tolerance_frac`` relative) or ``falsified``. Rows with no
    measured signal are ``unmeasured`` — a projection the capture
    cannot speak to stays open rather than silently passing."""
    spans_ms = {p: s * 1e3 for p, s in att.get("spans", {}).items()}
    rows = []
    for phase, projected_ms in sorted(projections.items()):
        measured = spans_ms.get(phase)
        if measured is None:
            hits = [v for p, v in spans_ms.items() if phase in p]
            measured = sum(hits) if hits else None
        if measured is None and phase in ("total", "step"):
            measured = att.get("total_device_seconds", 0.0) * 1e3
        if measured is None or projected_ms is None:
            verdict = "unmeasured"
        else:
            rel = (abs(measured - float(projected_ms))
                   / max(abs(float(projected_ms)), 1e-9))
            verdict = "settled" if rel <= tolerance_frac else "falsified"
        rows.append({
            "phase": phase,
            "projected_ms": (round(float(projected_ms), 3)
                             if projected_ms is not None else None),
            "measured_ms": (round(measured, 3)
                            if measured is not None else None),
            "verdict": verdict,
        })
    return rows
