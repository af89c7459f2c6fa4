"""A host span or a counter of the program itself (PR 40), read from the
program's own flight recorder and registry (``obs.default_recorder()``,
``obs.default_registry()``) in the run's process, after the window.

The window is known from the program's side alone: its step wrapper opens
a ``train/dispatch`` span per step with the process's step ordinal, so the
traced window's steps are the ``ctx.steps`` newest of those spans, and the
window starts where the first of them does.

``params``:

* ``span``: the span's path, as the program records it where no caller
  has a span open (``model/init``, ``embedding/get_weights``: the metric's
  file maps the program's names onto the benchmark's ``setup.*``);
* ``window``: ``before`` (spans that ended before the window began: set-up,
  read in SECONDS) or ``steps`` (spans that carry one of the window's step
  ordinals, read in MILLISECONDS); `UNITS` is what `BENCHMARK.json`'s
  entry has to say;
* ``stat``: ``sum``, ``median`` or ``first`` (the earliest such span);
* ``counter`` with ``within`` instead of ``span`` and ``stat``: the seconds
  that the durations of the instants called ``counter`` cover (the program
  records one per ``compile/seconds`` duration, when it ends) inside the
  spans of the paths ``within`` lists, before the window: what the PROGRAM
  spent tracing, lowering, compiling and loading during set-up, and not
  the benchmark's plain reference. A note gives the process's total.

None where no chip was traced or this process's devices are not of the
traced kind (a CPU's seconds are nobody's metric, and a recorded trace read
in another process says nothing of this one's spans), where the program has
no such recorder view, span or instant (a program from before them), and
where the ring has dropped entries that a ``before`` reading needs.
"""

import statistics

from benchmark.harness import xplane

DISPATCH = "train/dispatch"
UNITS = {"before": "s", "steps": "ms"}
_PER_NS = {"s": 1e-9, "ms": 1e-6}


def ran_here(ctx):
    """A chip was traced, and by this process: its devices are of the
    traced kind."""
    if not ctx.chips:
        return False
    import jax

    return jax.devices()[0].device_kind == ctx.device_kind


def window_steps(ctx, recorder):
    """The window's dispatch spans, oldest first, or None where the ring
    does not hold ``ctx.steps`` of them."""
    if not hasattr(recorder, "spans"):
        return None
    dispatches = sorted(recorder.spans(DISPATCH), key=lambda s: s.step)
    if not ctx.steps or len(dispatches) < ctx.steps:
        return None
    return dispatches[-ctx.steps:]


def _seconds_within(ctx, params, recorder, registry, t_window):
    name = params["counter"]
    spans = xplane.union_intervals(
        (s.start_ns, s.end_ns) for path in params["within"]
        for s in recorder.spans(path) if s.end_ns <= t_window)
    # an instant is written when its duration ends. The durations nest (a
    # function traced under another's trace is timed twice), so what is
    # read is the time their union covers, not their sum
    ran = xplane.union_intervals(
        (at_ns - args["seconds"] * 1e9, at_ns)
        for at_ns, args in recorder.instants(name))
    inside = sum(xplane._covered(ran, start, end) for start, end in spans)
    if not inside:
        return None
    counters = registry.snapshot()["counters"]
    ctx.notes.append(
        f"{name}: {inside * 1e-9:.3f} s inside the program's "
        f"{', '.join(params['within'])} before the window, of "
        f"{sum(e - s for s, e in ran) * 1e-9:.3f} s in the process (the "
        "rest: the plain reference's programs, the inputs', the readers'); "
        f"compile/programs {counters.get('compile/programs', 0)}, cache "
        f"hits {counters.get('compile/cache_hits', 0)}, entries written "
        f"{counters.get('compile/cache_misses', 0)}")
    return inside * 1e-9


def read(ctx, params):
    if not ran_here(ctx):
        return None
    from distributed_embeddings_tpu import obs

    recorder, registry = obs.default_recorder(), obs.default_registry()
    steps = window_steps(ctx, recorder)
    if steps is None:
        return None
    t_window = steps[0].start_ns
    before = "counter" in params or params["window"] == "before"
    if before and recorder.dropped:
        ctx.notes.append(
            f"{params.get('span') or params['counter']}: the recorder's "
            f"ring dropped {recorder.dropped} entries; a reading of set-up "
            "would be short, none is given")
        return None
    if "counter" in params:
        return _seconds_within(ctx, params, recorder, registry, t_window)
    if before:
        found = [s for s in recorder.spans(params["span"])
                 if s.end_ns <= t_window]
    else:
        ordinals = {s.step for s in steps}
        found = [s for s in recorder.spans(params["span"])
                 if s.step in ordinals]
    if not found:
        return None
    durations = [s.end_ns - s.start_ns for s in found]
    if params["stat"] == "first":
        ns = durations[min(range(len(found)),
                           key=lambda i: found[i].start_ns)]
    else:
        ns = {"sum": sum, "median": statistics.median}[params["stat"]](
            durations)
    return ns * _PER_NS[UNITS[params["window"]]]
