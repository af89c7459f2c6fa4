"""From the profiler's ``.xplane.pb`` to device time per class of operation.

The file is an ``XSpace`` protocol buffer (planes > lines > events, with the
events' names and properties in per-plane metadata tables). It is decoded
here, by a few lines that read the wire format: ``jax.profiler.ProfileData``
gives the events but not the metadata table's properties, and those are what
says where an operation comes from (looked at by hand on the first chip
traces of PR 23, `PERF.md` section 6):

* a TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` has one event
  per executed HLO instruction; a control-flow instruction (``while``,
  ``conditional``) spans the events of its body on the same line. Its line
  ``Async XLA Ops`` has the asynchronous operations (``copy-start`` ..
  ``-done``, collectives) for as long as they are in flight, beside the
  core's own work;
* an instruction's metadata carries ``hlo_category`` (``custom fusion``,
  ``convolution fusion``, ``data formatting``, ``sort``, ``all-to-all`` ...),
  ``tf_op`` (the JAX primitive and its path, ``jit(step_fn)/jvp(jit(_take))/gather:``)
  and ``source`` (file and line of the program that made it). Copies that
  the compiler inserts by itself have neither of the last two;
* the host's plane ``/host:CPU`` has the loop's own annotations
  (``dispatch``, ``sync``, ``fetch``) on the same clock, to within a
  millisecond.

A device nanosecond belongs to the innermost event that covers it: every
event is charged its duration less what the events nested in it cover, so
the charges of a chip add up to the union of its events, which is its busy
time. Each event goes to a class (``benchmark/op_classes/*.json``) by its
signature ``name=<instruction> cat=<hlo_category> op=<tf_op> src=<source>``:
first by what it is (a class's ``patterns``: category and JAX primitive, in
the files' ``order``), and only if no class claims it so, by where it comes
from (``fallback_patterns``: the program's source file, or nothing of the
program at all). What no class claims is ``other``. A file of the program
that moves therefore moves no gather, scatter, sort, matrix product or
collective between classes.

``python3 -m benchmark.harness.xplane <file.xplane.pb>`` prints what a trace
holds, for looking at one by hand.
"""

import dataclasses
import glob
import json
import os
import re
import struct
import sys

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
ANNOTATIONS = ("dispatch", "sync", "fetch")
_CLASSES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "op_classes")


# ----------------------------------------------------------- wire format
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _raw_fields(buf):
    """(field number, wire type, value) of one message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, wire, value


def _fields(buf):
    return ((number, value) for number, _, value in _raw_fields(buf))


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _string_field(message, number):
    return next((_text(v) for f, v in _fields(message) if f == number), "")


def _stat(buf, stat_names):
    """XStat -> (name, value); a `ref_value` is the name of another stat."""
    name = value = None
    for field, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif field == 5:
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entries(plane_fields, field_no):
    for field, entry in plane_fields:
        if field == field_no:
            parts = dict(_fields(entry))
            yield parts[1], parts[2]


@dataclasses.dataclass
class Event:
    name: str             # the metadata's display name, else its name
    start: float          # ns
    end: float
    stats: dict           # the metadata's properties


def read_planes(path, wanted=lambda plane, line: True):
    """{plane name: {line name: [Event]}} of the lines `wanted` accepts."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        plane_name = next((_text(v) for f, v in parts if f == 2), "")
        lines = [v for f, v in parts if f == 3]
        if not any(wanted(plane_name, _string_field(ln, 2)) for ln in lines):
            continue
        stat_names = {k: _string_field(m, 2)
                      for k, m in _map_entries(parts, 5)}
        metadata = {}
        for key, meta in _map_entries(parts, 4):
            name = display = ""
            stats = {}
            for f, v in _fields(meta):
                if f == 2:
                    name = _text(v)
                elif f == 4:
                    display = _text(v)
                elif f == 5:
                    k, value = _stat(v, stat_names)
                    stats[k] = value
            if display:
                stats.setdefault("long_name", name)
            metadata[key] = (display or name, stats)
        for ln in lines:
            line_name = _string_field(ln, 2)
            if not wanted(plane_name, line_name):
                continue
            t0, events = 0, []
            for f, v in _fields(ln):
                if f == 3:
                    t0 = v
                elif f == 4:
                    ev = dict(_fields(v))
                    name, stats = metadata.get(ev.get(1), ("?", {}))
                    start = t0 + ev.get(2, 0) / 1000.0
                    events.append(Event(name, start,
                                        start + ev.get(3, 0) / 1000.0, stats))
            out.setdefault(plane_name, {})[line_name] = events
    return out


# ------------------------------------------------------------- reduction
@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    end: float
    signature: str
    self_ns: float = 0.0
    cls: str = "other"


@dataclasses.dataclass
class Trace:
    chips: dict           # plane name -> [Op] of its XLA Ops line
    in_flight: dict       # plane name -> [Op] of its Async XLA Ops line
    host: list            # [(annotation, start ns, end ns)], by start


def signature(event: Event) -> str:
    s = event.stats
    return (f"name={event.name} cat={s.get('hlo_category', '')} "
            f"op={s.get('tf_op', '')} src={s.get('source', '')}").lower()


def load(path) -> Trace:
    def wanted(plane, line):
        return ((plane.startswith("/device:") and line in (OPS_LINE, ASYNC_LINE))
                or plane.startswith("/host:"))

    chips, in_flight, host = {}, {}, []
    for plane, lines in read_planes(path, wanted).items():
        if plane.startswith("/host:"):
            host += [(e.name, e.start, e.end) for events in lines.values()
                     for e in events if e.name in ANNOTATIONS]
            continue
        for line, into in ((OPS_LINE, chips), (ASYNC_LINE, in_flight)):
            ops = [Op(e.name, e.start, e.end, signature(e))
                   for e in lines.get(line, [])]
            if ops:
                into[plane] = ops
    host.sort(key=lambda s: s[1])
    return Trace(chips, in_flight, host)


def load_classes(directory=_CLASSES_DIR):
    """[(class, [compiled patterns])] in matching order: every class's
    `patterns` by the files' `order` (what an operation is: its category,
    its primitive), then every class's `fallback_patterns` in the same order
    (where it comes from), which so see only what no `patterns` claimed."""
    files = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            files.append(json.load(f))
    files.sort(key=lambda r: (r["order"], r["class"]))
    return [(rule["class"], [re.compile(p) for p in rule[key]])
            for key in ("patterns", "fallback_patterns")
            for rule in files if rule.get(key)]


def classify(sig, rules):
    for cls, patterns in rules:
        if any(p.search(sig) for p in patterns):
            return cls
    return "other"


def charge_self_times(ops):
    """Set each op's `self_ns`: its duration less what the events nested in
    it cover. Returns the ops sorted by start."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    stack = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            # clipped to the parent: a child takes no more than they share
            parent = stack[-1]
            parent.self_ns -= max(0.0, min(op.end, parent.end) - op.start)
        stack.append(op)
    return ops


def union_intervals(intervals):
    """Merged, sorted [(start, end)]."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _covered(intervals, start, end):
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in intervals)


@dataclasses.dataclass
class Chip:
    plane: str
    busy_ns: float
    window_ns: float
    class_ns: dict        # class -> ns on the core, sums to busy_ns
    exchange_ns: float    # an exchange was running or in flight
    exposed_ns: float     # ... and the core ran nothing else
    ops: list
    gaps: list            # [(label, ns)] longest first


def reduce(trace: Trace, rules, exchange_class="exchange"):
    """Per chip: busy time, time per class, the exchange's time and how much
    of it no other work hides, and the idle gaps labelled by the host's
    annotations. The window is shared by the chips: from the first dispatch
    (or the first device event, whichever is earlier) to the end of the last
    fetch (or event)."""
    if not trace.chips:
        return []
    starts = [min(o.start for o in ops) for ops in trace.chips.values()]
    ends = [max(o.end for o in ops) for ops in trace.chips.values()]
    if trace.host:
        starts.append(trace.host[0][1])
        ends.append(max(e for _, _, e in trace.host))
    w0, w1 = min(starts), max(ends)
    out = []
    for plane, ops in sorted(trace.chips.items()):
        ops = charge_self_times(ops)
        class_ns = {}
        for op in ops:
            op.cls = classify(op.signature, rules)
            class_ns[op.cls] = class_ns.get(op.cls, 0.0) + op.self_ns
        busy = union_intervals((o.start, o.end) for o in ops)
        busy_ns = sum(e - s for s, e in busy)
        # an exchange's time: on the core (a blocking collective, the start
        # and done of an asynchronous one) or in flight beside it
        exchange = union_intervals(
            [(o.start, o.end) for o in ops if o.cls == exchange_class]
            + [(o.start, o.end) for o in trace.in_flight.get(plane, [])
               if classify(o.signature, rules) == exchange_class])
        # leaves only: a while loop is no work of its own
        others = union_intervals(
            (o.start, o.end) for o in ops
            if o.cls != exchange_class and o.self_ns == o.end - o.start)
        exchange_ns = sum(e - s for s, e in exchange)
        exposed = exchange_ns - sum(_covered(others, s, e)
                                    for s, e in exchange)
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        gaps = [(_gap_label(trace.host, edges[i], edges[i + 1]),
                 edges[i + 1] - edges[i])
                for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: -g[1])
        out.append(Chip(plane, busy_ns, w1 - w0, class_ns, exchange_ns,
                        exposed, ops, gaps))
    return out


def _gap_label(host, start, end):
    """The annotation that covers most of [start, end), or `host` when the
    loop's annotations cover none of it."""
    best, best_ns = "host", 0.0
    for name, s, e in host:
        if s >= end:
            break
        ns = min(e, end) - max(s, start)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def idle_patterns(chips, rules):
    """The patterns that no operation of the trace matches: a kind of
    operation this cell does not run, or a file of the program that moved."""
    signatures = {op.signature for chip in chips for op in chip.ops}
    return [p.pattern for _, patterns in rules for p in patterns
            if not any(p.search(s) for s in signatures)]


def top_ops(chip: Chip, n=10):
    """[(name [class], seconds)] by self time, summed over equal names."""
    total = {}
    for op in chip.ops:
        key = f"{op.name} [{op.cls}]"
        total[key] = total.get(key, 0.0) + op.self_ns
    return [[k, v * 1e-9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(chip: Chip, n=5):
    return [[label, ns * 1e-9] for label, ns in chip.gaps[:n]]


def describe(path, out=sys.stdout):
    """What a trace holds: planes, lines, and per line the events that take
    most time, with their class and what the metadata says of them."""
    rules = load_classes()
    for plane, lines in read_planes(path).items():
        print(f"PLANE {plane!r}", file=out)
        for line, events in lines.items():
            print(f"  LINE {line!r}: {len(events)} events", file=out)
            by_name = {}
            for ev in events:
                rec = by_name.setdefault(ev.name, [0, 0.0, ev])
                rec[0] += 1
                rec[1] += ev.end - ev.start
            show = 60 if line in (OPS_LINE, ASYNC_LINE) else 10
            for name, (n, ns, ev) in sorted(
                    by_name.items(), key=lambda kv: -kv[1][1])[:show]:
                s = ev.stats
                print(f"    {ns * 1e-6:10.3f} ms x{n:<6d} {name[:40]:40s} "
                      f"[{classify(signature(ev), rules)}] "
                      f"cat={s.get('hlo_category')} op={s.get('tf_op')} "
                      f"src={s.get('source')} bytes={s.get('bytes_accessed')} "
                      f"flops={s.get('model_flops')}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
