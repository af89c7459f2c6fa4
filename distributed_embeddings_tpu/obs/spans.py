"""Host-side spans (ISSUE 11; made lean in ISSUE 40).

``span("train/step")`` times a host-side region. A span has three
outputs, and since ISSUE 40 they cost about 3 us together on a
tier-1 CPU (29 us before: two ring entries, a generator-based context
manager, a labelled registry lookup and a numpy search per span), so a
step wrapper can open one on every call:

  * ONE entry in the process-wide flight recorder
    (`obs.trace.default_recorder`), written when the span ends: its
    path, start and end (`time.perf_counter_ns`), the span that was
    open on the thread when it began (its parent) and the thread's
    STEP ORDINAL as the span ends, so the spans of one step share an
    identifier (`FlightRecorder.spans()` gives them as `SpanRecord`s);
  * a ``jax.profiler.TraceAnnotation`` named ``det:<path>``: while a
    profiler session runs, the span lies on the ``/host:`` plane of the
    XPlane trace beside the device's planes. The two planes' clocks
    do NOT agree under a millisecond: the host plane ran 0.18-1.49 ms
    behind the device plane in five sessions on a v5e, by another
    amount each (PERF.md section 6, PR 40), so whoever sets a span
    against a gap between device operations first anchors the planes
    on an event both hold, as `benchmark/readers/idle_program_ms.py`
    does;
  * its duration in the registry histogram
    ``span_seconds{span=<path>}`` (the handle is resolved once per
    path: `MetricRegistry.span_histogram`).

Nesting composes paths: a ``span("publish")`` opened inside
``span("train")`` records as ``train/publish`` — the per-thread span
stack supplies the prefix, so instrumented helpers don't need to know
where they are called from. The stack is thread-local: pipeline worker
threads and the consumer each get their own nesting. A step function's
``train/dispatch`` is ROOTED: its path is its name wherever it is
opened (under `fit`'s ``train/step`` too), so that a reader finds every
step under one name; what was open on the thread is still its parent.

The step ordinal is thread state too: ``span(..., step=n)`` makes `n`
the thread's current step (the step wrappers of
`training.make_sparse_train_step` do, with `next_step()`), and every
span that ENDS on that thread afterwards carries it: a span around a
dispatch (`fit`'s ``train/step``) carries that dispatch's ordinal.

This module is HOST-side by design: spans read the wall clock, which is
exactly what `tools/lint_invariants.py`'s ``wallclock-in-jit`` rule
bans from jitted-code modules (ops/, layers/, parallel/, schedule/).
``obs/`` is deliberately NOT in that module set — it is the sanctioned
home for wall-clock accounting — and instrumented call sites in jitted
modules must stay in their host-side driver methods (e.g.
`LookaheadEngine.step`'s Python body, never inside a traced function:
a traced span would freeze one timestamp into the compiled program and
time nothing).

The `annotation()` helper is the shared tolerant wrapper around
`utils.profiling.annotate`: the works/doesn't-work probe is cached
module-wide, so backends with no profiler configured pay one failed
construction per process instead of one exception per region
(`utils.pipeline` delegates here — its per-stage-invocation re-probe
was measurable ingest overhead).
"""

import atexit
import contextlib
import functools
import gc
import itertools
import threading
import time
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

from distributed_embeddings_tpu.obs import registry as _registry
from distributed_embeddings_tpu.obs import trace as _trace
from distributed_embeddings_tpu.obs.registry import (MetricRegistry,
                                                     default_registry)
from distributed_embeddings_tpu.obs.trace import default_recorder

__all__ = ["span", "annotation", "current_span", "current_step",
           "next_step", "spanned", "install_gc_hook", "TRACE_PREFIX"]

# a span's name on the profiler's /host: plane is TRACE_PREFIX + its path
TRACE_PREFIX = "det:"

_state = threading.local()
_step_ordinals = itertools.count()
_now = time.perf_counter_ns

# cached annotate probe: None = untried, False = profiler unavailable
# (never retried), True = construction known to work
_ANNOTATE_OK = None


def annotation(name: str):
    """`utils.profiling.annotate(name)`, tolerating backends with no
    profiler — the probe result is cached process-wide so the failure
    path costs one exception total, not one per region."""
    global _ANNOTATE_OK
    if _ANNOTATE_OK is False:
        return contextlib.nullcontext()
    from distributed_embeddings_tpu.utils import profiling
    try:
        cm = profiling.annotate(name)
        _ANNOTATE_OK = True
        return cm
    except Exception:  # noqa: BLE001 - accounting must never break the run
        _ANNOTATE_OK = False
        return contextlib.nullcontext()


def current_span() -> Optional[str]:
    """The innermost open span path on this thread (None outside any)."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def current_step() -> Optional[int]:
    """This thread's step ordinal: the `step=` of the last span that set
    one here (None before the first)."""
    return getattr(_state, "step", None)


def next_step() -> int:
    """The next step ordinal of the process (0, 1, 2 ...): what a step
    wrapper gives its dispatch span."""
    return next(_step_ordinals)


class span:
    """Time a host-side region: ``with span("train/step", reg) as path``.

    Args:
      name: span name; joined onto the enclosing span's path with ``/``
        (top-level spans may themselves be pre-pathed: "train/step").
      registry: target registry of the ``span_seconds`` histogram
        (default: the process-local one).
      rooted: the path is `name` whatever is open on the thread (the
        program's own sites; the open span is still the parent).
      step: make this the thread's step ordinal from here on.

    The record is written even when the body raises — a failing step is
    still a step that took time — and the annotation scope closes with
    the region, so XPlane nesting matches the paths.
    """

    __slots__ = ("_name", "_registry", "_rooted", "_step", "_stack", "_path",
                 "_parent", "_annotation", "_t0")

    def __init__(self, name: str, registry: Optional[MetricRegistry] = None,
                 *, rooted: bool = False, step: Optional[int] = None):
        self._name = name
        self._registry = registry
        self._rooted = rooted
        self._step = step

    def __enter__(self) -> str:
        try:
            stack = _state.stack
        except AttributeError:
            stack = _state.stack = []
            _state.step = None
        parent = stack[-1] if stack else None
        path = (self._name if self._rooted or parent is None
                else parent + "/" + self._name)
        if self._step is not None:
            _state.step = self._step
        stack.append(path)
        self._stack, self._path, self._parent = stack, path, parent
        annotation = self._annotation = TraceAnnotation(TRACE_PREFIX + path)
        annotation.__enter__()
        self._t0 = _now()
        return path

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = _now()
        self._annotation.__exit__(exc_type, exc, tb)
        self._stack.pop()
        path, start = self._path, self._t0
        # the process-wide instances, read without their locks where
        # they exist already (the locks guard their creation)
        (_trace._default or default_recorder()).span(
            path, start, end, self._parent, _state.step)
        reg = (self._registry or _registry._default or default_registry())
        reg.span_histogram(path).record((end - start) * 1e-9)
        return False


def spanned(name: str):
    """Decorator: the call runs under ``span(name)``, named for what the
    function does (``model/init``, ``embedding/get_weights``) and joined
    onto whatever span its caller has open, like any other span. The span
    times the host: JAX returns before the device is done, so a function
    that makes device arrays is charged its tracing, lowering, compiling
    and dispatch, and what the device still has to fill belongs to
    whoever waits for it. A call under a trace
    (``jax.eval_shape(model.init, key)``) makes no array and opens no
    span."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            if any(isinstance(a, jax.core.Tracer) for a in args):
                return fn(*args, **kwargs)
            with span(name):
                return fn(*args, **kwargs)
        return timed
    return wrap


# ---- the collector's pauses -------------------------------------------
# the collection in flight, (start ns, its annotation): collections do not
# nest and a collection's two callbacks run on one thread
_gc_open = None


def _on_gc(phase, info):
    """`gc.callbacks` hook: every collection is a ``det:host/gc``
    annotation (one is 0.1-250 ms; most take microseconds), and a
    collection of generation 2, or one that took over 1 ms, is a
    ``host/gc`` span in the recorder. Nothing else is recorded."""
    global _gc_open
    if phase == "start":
        ann = TraceAnnotation(TRACE_PREFIX + "host/gc")
        ann.__enter__()
        _gc_open = (_now(), ann)
    elif _gc_open is not None:
        (start, ann), _gc_open = _gc_open, None
        end = _now()
        ann.__exit__(None, None, None)
        if info["generation"] == 2 or end - start > 1_000_000:
            default_recorder().span("host/gc", start, end, current_span(),
                                    current_step())


def _remove_gc_hook():
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def install_gc_hook() -> None:
    """Put `_on_gc` among `gc.callbacks`, once per process (the step
    builders of `training` call this; importing the module installs
    nothing). Taken out again at exit, before the interpreter's last
    collections run over half-cleared modules."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        atexit.register(_remove_gc_hook)
