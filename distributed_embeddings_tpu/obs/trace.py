"""Flight recorder: bounded in-memory trace of structured events
(ISSUE 14).

`obs.span` gave every host-side region two outputs — a registry
histogram and an XPlane `TraceAnnotation` — but both are lossy in the
direction a postmortem needs: the histogram keeps only the
distribution, and the XPlane trace exists only while a profiler session
is running (and never on CI or a serving replica). The
`FlightRecorder` is the third output: a BOUNDED ring of span/begin/end/
instant events that is always on (a flight recorder that must be
switched on before the incident is a black box that records nothing),
cheap enough to feed from every span (one lock + deque append per
span), and exportable at any moment as Chrome-trace-format JSON that
loads directly in Perfetto (ui.perfetto.dev) or chrome://tracing.

Event kinds (Chrome trace `ph` phases on export):

  * ``span`` (X in the ring, a B/E pair on export) — ONE entry per
    `obs.span`, written when the span ends (ISSUE 40): its path, start
    and end (`time.perf_counter_ns`), the span that was open on the
    thread when it began (its parent) and the thread's step ordinal
    as the span ended (a span around a dispatch carries that
    dispatch's ordinal).
    `spans()` gives them as `SpanRecord`s; the export puts the edges
    back in time order, so the timeline reproduces the nesting
    `span_seconds{span=}` paths describe, per thread (publisher loop,
    pipeline workers, consumer pollers each get their own track).
  * ``begin``/``end`` (B/E) — edges of a region opened by hand
    (`begin(name)` .. `end(name)`).
  * ``instant`` (i) — point annotations (degraded-entry, SLO breach,
    fault injection...).
  * ``lineage`` (b/n/e nestable-async, ``cat="version"``) — a store
    version's LIFE as one async track keyed by the version number:
    ``commit`` opens the track, ``publish``/``scan``/``apply`` land as
    async instants on it, and the FIRST ``serve`` (a predict answered
    at >= that version) closes it. Because publisher and replica
    report into one process-wide recorder, the track spans threads and
    components: the scalar ``store/publish_to_apply_seconds``
    histogram becomes an inspectable per-version breakdown of where
    commit->predict latency went. Later phases on a closed track (a
    second replica applying the same version) record as instants, so
    the async begin/end pairing stays balanced.

The ring is bounded (``DET_OBS_TRACE_EVENTS``, default 16384 events):
old events fall off the front and the drop count is kept, so a
week-long soak holds the LAST window of activity in constant memory —
exactly the flight-recorder contract. `export()` re-balances on the
way out (an `end` whose `begin` was evicted is dropped; a still-open
`begin` gets a synthetic close at the export timestamp), so the
exported JSON always validates regardless of where the ring was cut.

`dump_postmortem` is the incident artifact: ring + registry snapshot +
caller context in one timestamped JSON file. `InferenceEngine.
poll_updates` calls it on every degraded-mode ENTRY when
``DET_OBS_POSTMORTEM_DIR`` is set — see docs/observability.md "Flight
recorder & postmortems".
"""

import collections
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional

__all__ = ["FlightRecorder", "SpanRecord", "default_recorder",
           "reset_default_recorder", "dump_postmortem", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 16384

# lineage phases in life order; "commit" opens the async track and
# "serve" closes it (first occurrence only — see class docstring)
LINEAGE_PHASES = ("commit", "publish", "scan", "apply", "serve")


class SpanRecord(NamedTuple):
    """One finished `obs.span`, as the ring holds it."""
    name: str                    # the span's path
    start_ns: int                # time.perf_counter_ns() at entry
    end_ns: int                  # ... and at exit
    parent: Optional[str]        # the path open on the thread at entry
    step: Optional[int]          # the thread's step ordinal at EXIT
    tid: int


class FlightRecorder:
    """Bounded ring of trace events; see module docstring.

    Args:
      capacity: max events held (oldest evicted first). Default:
        ``DET_OBS_TRACE_EVENTS`` or 16384.

    Every mutator is thread-safe (one lock around the deque); the
    recording cost is one `time.perf_counter()` read plus an append,
    so spans can feed it unconditionally.
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(os.environ.get("DET_OBS_TRACE_EVENTS",
                                          DEFAULT_CAPACITY))
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._events = collections.deque(maxlen=self.capacity)
        self._dropped = 0
        self._thread_names: Dict[int, str] = {}
        # lineage state: version -> "open" | "closed" (versions the ring
        # has begun an async track for; bounded by eviction reconcile at
        # export, and by being integers — a few bytes per version)
        self._lineage: Dict[int, str] = {}
        # perf_counter at construction: export timestamps are relative
        # to this origin (Chrome trace ts is an arbitrary-epoch us).
        # perf_counter and perf_counter_ns read one clock
        self._t0_ns = time.perf_counter_ns()
        self._t0 = self._t0_ns * 1e-9
        # wall-clock twin of _t0 so exported args can carry absolute time
        self._wall0 = time.time()

    # ------------------------------------------------------------ record
    def _append_locked(self, ph: str, name: str, ts: float, tid: int,
                       cat: Optional[str] = None,
                       eid: Optional[int] = None,
                       args: Optional[dict] = None):
        """Caller holds self._lock. Split out so `lineage` can make its
        state transition AND its event append one atomic step — a
        check-then-act gap there lets two threads first-sighting the
        same version emit a duplicate async begin (or land an 'n'
        before its 'b'), breaking the balanced-export contract."""
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        if len(self._events) == self.capacity:
            self._dropped += 1
        self._events.append((ph, name, ts, tid, cat, eid, args))

    def _append(self, ph: str, name: str, cat: Optional[str] = None,
                eid: Optional[int] = None, args: Optional[dict] = None):
        tid = threading.get_ident()
        ts = time.perf_counter() - self._t0
        with self._lock:
            self._append_locked(ph, name, ts, tid, cat, eid, args)

    def span(self, name: str, start_ns: int, end_ns: int,
             parent: Optional[str] = None,
             step: Optional[int] = None) -> None:
        """One finished span, as ONE ring entry (`obs.span` calls this
        at exit; the times are `time.perf_counter_ns()` readings)."""
        tid = threading.get_ident()
        # ring layout of a span: the slots `_append_locked` names
        # cat / eid / args hold parent / step / end_ns
        with self._lock:
            self._append_locked("X", name, start_ns, tid, parent, step,
                                end_ns)

    def begin(self, name: str) -> None:
        """Open a region by hand. Paired with `end(name)`."""
        self._append("B", name)

    def end(self, name: str) -> None:
        """Close a region opened by `begin`."""
        self._append("E", name)

    def instant(self, name: str, **args) -> None:
        """A point event (degraded entry, SLO breach, fault fired...)."""
        self._append("i", name, args=args or None)

    def lineage(self, version: int, phase: str, **args) -> None:
        """One step of store version `version`'s life (see module
        docstring). Unknown-to-the-recorder versions auto-open (a
        consumer can watch a stream whose publisher lives elsewhere);
        the first ``serve`` closes the track, later phases on a closed
        version record as async instants."""
        if phase not in LINEAGE_PHASES:
            raise ValueError(
                f"lineage phase {phase!r} not in {LINEAGE_PHASES}")
        version = int(version)
        name = f"v{version}"
        tid = threading.get_ident()
        # state transition + event append under ONE lock hold: two
        # threads first-sighting a version must serialize into exactly
        # one 'b' followed by the other's 'n'/'e'
        with self._lock:
            ts = time.perf_counter() - self._t0
            state = self._lineage.get(version)
            if state is None:
                # open the async track (commit, or first sight on a
                # consumer that never saw the publisher's commit)
                self._lineage[version] = "open"
                self._append_locked(
                    "b", name, ts, tid, cat="version", eid=version,
                    args={"phase": "commit"} if phase == "commit"
                    else None)
                if phase == "commit":
                    return
                state = "open"
            if phase == "serve" and state == "open":
                self._lineage[version] = "closed"
                self._append_locked(
                    "e", name, ts, tid, cat="version", eid=version,
                    args={"phase": "serve", **args} if args
                    else {"phase": "serve"})
                return
            self._append_locked("n", name, ts, tid, cat="version",
                                eid=version,
                                args={"phase": phase, **args})

    # ------------------------------------------------------------- views
    def events(self) -> List[tuple]:
        """The current ring contents, oldest first (tuples of
        (ph, name, ts_seconds, tid, cat, id, args); a span's entry is
        ("X", path, start_ns, tid, parent, step, end_ns): `spans()`)."""
        with self._lock:
            return list(self._events)

    def spans(self, name: Optional[str] = None) -> List[SpanRecord]:
        """The finished spans the ring holds, in the order they ended;
        those of path `name` alone where one is given."""
        return [SpanRecord(n, start, end, parent, step, tid)
                for ph, n, start, tid, parent, step, end in self.events()
                if ph == "X" and (name is None or n == name)]

    def instants(self, name: str) -> List[tuple]:
        """The ring's instants called `name`, oldest first, as
        (``time.perf_counter_ns()`` reading, args dict)."""
        return [(self._t0_ns + int(ts * 1e9), args or {})
                for ph, n, ts, _tid, _cat, _eid, args in self.events()
                if ph == "i" and n == name]

    @property
    def dropped(self) -> int:
        """Events evicted from the ring so far (0 = nothing lost)."""
        with self._lock:
            return self._dropped

    def lineage_versions(self) -> List[int]:
        """Versions whose lineage track this ring has opened, sorted."""
        with self._lock:
            return sorted(self._lineage)

    def lineage_open_versions(self) -> List[int]:
        """Versions whose track is begun but not yet closed by a
        ``serve`` phase, sorted — the serving seam closes every open
        version <= the version a predict was answered at (a predict at
        V is also the first predict at >= every version below it)."""
        with self._lock:
            return sorted(v for v, s in self._lineage.items()
                          if s == "open")

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0
            self._lineage.clear()

    # ------------------------------------------------------------ export
    def to_chrome_trace(self) -> dict:
        """The ring as a Chrome-trace-format dict (`traceEvents` JSON
        object form — what Perfetto and chrome://tracing load).

        Balanced by construction: a span is one entry and exports as
        its own B/E pair, placed in time order among the other events
        (a span carries its step ordinal as ``args.step``); per-thread
        `E` events whose `B` was evicted from the ring are dropped,
        still-open `B` events get a synthetic close at the export
        timestamp, and lineage tracks likewise (an evicted async begin
        is re-synthesized at the track's first surviving event; an open
        track closes at export). Span timestamps are microseconds
        relative to the recorder's construction.
        """
        with self._lock:
            events = list(self._events)
            thread_names = dict(self._thread_names)
            wall0 = self._wall0
        events = _span_edges(events, self._t0_ns)
        pid = os.getpid()
        now_us = (time.perf_counter() - self._t0) * 1e6
        out: List[dict] = [{
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "flight_recorder"}}]
        for tid, tname in thread_names.items():
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": tname}})
        open_spans: Dict[int, List[dict]] = {}
        open_async: Dict[int, dict] = {}
        for ph, name, ts, tid, cat, eid, args in events:
            ev = {"ph": ph, "name": name, "pid": pid, "tid": tid,
                  "ts": round(ts * 1e6, 3)}
            if cat is not None:
                ev["cat"] = cat
            if eid is not None:
                ev["id"] = eid
            if args:
                ev["args"] = dict(args)
            if ph == "B":
                open_spans.setdefault(tid, []).append(ev)
                out.append(ev)
            elif ph == "E":
                stack = open_spans.get(tid)
                if not stack:
                    continue             # begin evicted: drop the orphan
                stack.pop()
                out.append(ev)
            elif ph == "b":
                open_async[eid] = ev
                out.append(ev)
            elif ph in ("n", "e"):
                if eid not in open_async:
                    # async begin evicted: re-open the track just before
                    # this first surviving event so the id still groups
                    synth = {"ph": "b", "name": name, "pid": pid,
                             "tid": tid, "cat": cat or "version",
                             "id": eid, "ts": ev["ts"],
                             "args": {"synthesized": "begin-evicted"}}
                    open_async[eid] = synth
                    out.append(synth)
                if ph == "e":
                    open_async[eid] = None   # closed
                out.append(ev)
            else:                            # "i" and any future phases
                ev["s"] = "t"
                out.append(ev)
        # close whatever export caught mid-flight, deepest first
        for tid, stack in open_spans.items():
            for ev in reversed(stack):
                out.append({"ph": "E", "name": ev["name"], "pid": pid,
                            "tid": tid, "ts": round(now_us, 3),
                            "args": {"synthesized": "open-at-export"}})
        for eid, ev in open_async.items():
            if ev is not None:
                out.append({"ph": "e", "name": ev["name"], "pid": pid,
                            "tid": ev["tid"], "cat": ev.get("cat",
                                                            "version"),
                            "id": eid, "ts": round(now_us, 3),
                            "args": {"synthesized": "open-at-export"}})
        return {
            "displayTimeUnit": "ms",
            "metadata": {"source": "distributed_embeddings_tpu.obs.trace",
                         "wall_time_origin": wall0,
                         "dropped_events": self._dropped},
            "traceEvents": out,
        }

    def export(self, path: str) -> dict:
        """Write `to_chrome_trace()` to `path` (overwrite; the ring is
        a window, not a log — repeated exports supersede). Returns the
        exported dict."""
        doc = self.to_chrome_trace()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc


def _span_edges(events: List[tuple], t0_ns: int) -> List[tuple]:
    """The ring with every span entry replaced by its B and E edges, all
    in time order. A span is written when it ENDS, so the ring holds a
    child before its parent; on equal timestamps a parent still opens
    before its child and closes after it, a sibling closes before the
    next opens, and an empty span's B precedes its own E."""
    keyed = []
    for seq, ev in enumerate(events):
        if ev[0] != "X":
            keyed.append(((ev[2], 1, 0.0, seq), ev))
            continue
        _, name, start_ns, tid, _parent, step, end_ns = ev
        start, end = (start_ns - t0_ns) * 1e-9, (end_ns - t0_ns) * 1e-9
        args = None if step is None else {"step": step}
        keyed.append(((start, 1, -end, -seq),
                      ("B", name, start, tid, None, None, args)))
        keyed.append(((end, 0 if end > start else 2, -start, seq),
                      ("E", name, end, tid, None, None, None)))
    keyed.sort(key=lambda k: k[0])
    return [ev for _, ev in keyed]


_default_lock = threading.Lock()
_default: Optional[FlightRecorder] = None


def default_recorder() -> FlightRecorder:
    """The process-wide recorder `obs.span`, the store/consumer lineage
    seams, and the serving engine feed — one ring so a postmortem sees
    publisher, pipeline and replica activity on one timeline."""
    global _default
    with _default_lock:
        if _default is None:
            _default = FlightRecorder()
        return _default


def reset_default_recorder() -> None:
    """Drop the process-wide recorder (tests)."""
    global _default
    with _default_lock:
        _default = None


def dump_postmortem(directory: str, reason: str, registry=None,
                    recorder: Optional[FlightRecorder] = None,
                    extra: Optional[dict] = None) -> str:
    """Write the incident artifact: flight-recorder ring (as a chrome
    trace) + registry snapshot + caller context, one timestamped JSON
    file in `directory`. Returns the artifact path.

    The filename carries a monotonic-per-process sequence number so two
    dumps in the same second (two reasons activating on one poll) never
    collide or overwrite."""
    rec = recorder if recorder is not None else default_recorder()
    os.makedirs(directory, exist_ok=True)
    with _default_lock:
        global _postmortem_seq
        _postmortem_seq += 1
        seq = _postmortem_seq
    safe = "".join(c if c.isalnum() or c in "-_" else "_"
                   for c in str(reason))[:60]
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    path = os.path.join(directory,
                        f"postmortem_{stamp}_{seq:04d}_{safe}.json")
    doc = {
        "ts": round(time.time(), 3),
        "reason": str(reason),
        "snapshot": (registry.snapshot() if registry is not None else None),
        "trace": rec.to_chrome_trace(),
        "lineage_versions": rec.lineage_versions(),
        "dropped_events": rec.dropped,
    }
    if extra:
        doc["extra"] = extra
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)       # atomic: a watcher never sees a torn dump
    if registry is not None:
        registry.counter("obs/postmortems_total", reason=safe).inc()
    return path


_postmortem_seq = 0
