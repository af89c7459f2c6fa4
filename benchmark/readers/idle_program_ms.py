"""The milliseconds a step in which the idlest traced chip ran nothing
while one of the program's own host spans was open, inside the traced
window (PR 40): what the program's host code costs the device.

The program opens its host spans as ``jax.profiler.TraceAnnotation``s named
``det:<path>`` (``det:train/dispatch``, ``det:host/gc``:
``distributed_embeddings_tpu/obs/spans.py``), so under a profiler session
they lie on the trace's ``/host:`` plane. This reader opens the run's
``.xplane.pb`` again (``harness/xplane.read_planes``), rebuilds the idlest
chip's gaps from its operations and the window from the loop's own
annotations, as ``xplane.reduce`` does, and intersects the gaps with the
union of the ``det:`` events. The rest of the idle time is the loop's
(``sync``, ``fetch``) or nobody's.

**The two planes' clocks are anchored first.** In every trace looked at the
host plane runs behind the device plane, by another amount each session
(0.18-1.49 ms in five traces, `PERF.md` section 6, PR 40): the device
begins a program before the runtime's own host event that enqueues it, and
a 0.5 ms dispatch behind a loss fetch then reads as outside the gap it
ends. The k-th program of the chip's ``XLA Modules`` line cannot begin
before the k-th ``DoEnqueueProgram`` of the host plane began (the runtime's
own event; the ``det:train/dispatch`` that holds it begins 0.6-1.3 ms
earlier and, as an anchor, read DLRM 0.003 and 0.012 ms in two sessions
where this one reads 0.0235 and 0.0233). The host plane's events are
shifted back by the largest violation of that; a plane that violates
nothing is left where it is. What is left is the enqueue's own latency at
the step that binds the shift, taken as none: the reading is that much too
high at each gap a dispatch ends (the runtime's completion events bound it
by 0.3-0.9 ms from the other side; the enqueue event itself lasts 35-80
us). The note gives the shift.

A note lists the five longest gaps, each with the ``det:`` span that covers
most of it and that span's step ordinal (a span's ordinal is that of the
newest ``det:train/dispatch`` that began no later; the window's first
dispatch takes its ordinal from the program's recorder), or says that none
covers it.

None where no chip was traced by this process (`program_span.ran_here`),
where the trace holds no ``det:`` event (a program from before them) or no
``XLA Modules`` line or not one enqueue a program to anchor the planes on
(a note says so), and where the run kept its
trace elsewhere (``--keep-trace``): the file is looked for under
``.benchmark_trace/<cell>/``, which ``run.py`` removes after the readers.
"""

import glob
import operator
import os

from benchmark.harness import spec, xplane
from benchmark.readers import program_span

PREFIX = "det:"
DISPATCH = PREFIX + program_span.DISPATCH
ENQUEUE = "DoEnqueueProgram"
MODULES_LINE = "XLA Modules"


def read_trace(path):
    """(the program's ``det:`` events, the loop's annotations, the
    runtime's enqueues, {device plane: its programs' start ns}) of a trace;
    the first three [(name, start ns, end ns)] by start, off the host
    planes."""
    det, loop, enqueues, programs = [], [], [], {}
    planes = xplane.read_planes(
        path, lambda plane, line: (plane.startswith("/host:")
                                   or line == MODULES_LINE))
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            programs[plane] = sorted(e.start for e in lines[MODULES_LINE])
            continue
        for events in lines.values():
            for e in events:
                if e.name.startswith(PREFIX):
                    det.append((e.name, e.start, e.end))
                elif e.name in xplane.ANNOTATIONS:
                    loop.append((e.name, e.start, e.end))
                elif e.name == ENQUEUE:
                    enqueues.append((e.name, e.start, e.end))
    by_start = operator.itemgetter(1)
    return (sorted(det, key=by_start), sorted(loop, key=by_start),
            sorted(enqueues, key=by_start), programs)


def plane_shift(programs, enqueues):
    """The ns to add to the host plane's events: the k-th program began no
    earlier than its enqueue did. None where the trace has not one enqueue
    a program."""
    if not programs or len(enqueues) != len(programs):
        return None
    return min(0.0, min(p - e[1] for p, e in zip(programs, enqueues)))


def shifted(events, ns):
    return [(name, s + ns, e + ns) for name, s, e in events]


def idle_gaps(chip, chips, loop):
    """[(start, end)] in which `chip` ran nothing, inside the window that
    `xplane.reduce` gives the chips: from the first dispatch or device
    event to the end of the last fetch or event."""
    starts = [min(o.start for o in c.ops) for c in chips]
    ends = [max(o.end for o in c.ops) for c in chips]
    if loop:
        starts.append(loop[0][1])
        ends.append(max(e for _, _, e in loop))
    busy = xplane.union_intervals((o.start, o.end) for o in chip.ops)
    edges = [min(starts)] + [x for s, e in busy for x in (s, e)] + [max(ends)]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def covered_ns(gaps, det):
    """The gaps' ns under a ``det:`` event."""
    covered = xplane.union_intervals((s, e) for _, s, e in det)
    return sum(xplane._covered(covered, s, e) for s, e in gaps)


def label_gaps(gaps, det, first_ordinal, n=5):
    """The `n` longest gaps as (ns, span, its ordinal, the ns of the gap
    that it covers): of the ``det:`` events over a gap the one that covers
    most of it, the shortest of those that cover as much; (ns, None, None,
    0.0) under a gap that none covers."""
    dispatches = [s for name, s, _ in det if name == DISPATCH]

    def ordinal(began):
        before = sum(1 for d in dispatches if d <= began)
        return (first_ordinal + before - 1
                if first_ordinal is not None and before else None)

    out = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        over = max(((min(e, end) - max(s, start), s - e, name, s)
                    for name, s, e in det if s < end and e > start),
                   default=None)
        out.append((end - start, None, None, 0.0) if over is None else
                   (end - start, over[2], ordinal(over[3]), over[0]))
    return out


def _said(ns, name, ordinal, under):
    if name is None:
        return f"{ns * 1e-6:.3f} ms under no det: span"
    return (f"{ns * 1e-6:.3f} ms, {under * 1e-6:.3f} of it under {name} "
            f"(step {ordinal})")


def read(ctx, params):
    if not program_span.ran_here(ctx):
        return None
    found = sorted(glob.glob(os.path.join(
        spec.ROOT, ".benchmark_trace", ctx.cell.name, "plugins", "profile",
        "*", "*.xplane.pb")))
    if not found:
        ctx.notes.append("device.idle_program_ms: no .xplane.pb under "
                         ".benchmark_trace/ (--keep-trace): not read")
        return None
    det, loop, enqueues, programs = read_trace(found[-1])
    if not det or not ctx.steps:
        return None
    idlest = max(ctx.chips, key=lambda c: c.window_ns - c.busy_ns)
    shift = plane_shift(programs.get(idlest.plane), enqueues)
    if shift is None:
        ctx.notes.append(
            f"device.idle_program_ms: the trace has not one {ENQUEUE} for "
            f"each program of {idlest.plane}'s {MODULES_LINE} line to "
            "anchor the two planes' clocks on: not read")
        return None
    det, loop = shifted(det, shift), shifted(loop, shift)
    gaps = idle_gaps(idlest, ctx.chips, loop)
    inside = covered_ns(gaps, det)
    from distributed_embeddings_tpu import obs

    steps = program_span.window_steps(ctx, obs.default_recorder())
    labelled = label_gaps(gaps, det, steps[0].step if steps else None)
    ctx.notes.append(
        f"device.idle_program_ms: {sum(e - s for s, e in gaps) * 1e-6:.3f} "
        f"ms idle on the idlest chip in {ctx.steps} steps, "
        f"{inside * 1e-6:.3f} of it under a det: span of the program, the "
        f"host plane shifted by {shift * 1e-6:.3f} ms onto {ENQUEUE}; "
        "the longest gaps: " + "; ".join(_said(*gap) for gap in labelled))
    return inside * 1e-6 / ctx.steps
