"""Profiling and timing utilities.

The reference has no tracing subsystem — performance work is wall timing in
example scripts with a device-sync-by-print idiom (reference:
examples/benchmarks/synthetic_models/main.py:140-158). On TPU, first-class
tools exist; this module packages the two workflows:

  * ``benchmark(fn, *args)`` — compile-excluded, device-synced step timing
    (block_until_ready, not print) with mean/p50/min.
  * ``trace(logdir)`` — context manager around jax.profiler producing an
    XPlane trace viewable in TensorBoard/Perfetto (op-level HLO timing,
    HBM traffic, ICI collectives).
"""

import contextlib
import statistics
import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax

__all__ = ["BenchResult", "benchmark", "benchmark_train_steps", "trace",
           "annotate", "fetch_sync", "hlo_op_counts",
           "hlo_collective_bytes", "hlo_collective_overlap"]


def hlo_op_counts(lowered, ops: Sequence[str] = ("sort", "scatter", "gather",
                                                 "all_to_all")) -> dict:
    """Count StableHLO op mentions in a lowered (not yet compiled) jax
    program — the static twin of a profiler trace: op COUNTS are decided
    at trace time, so regressions like "the train step re-sorts the same
    ids three times" (docs/perf_model.md 'Sort folding') are catchable on
    any backend, hardware or not.

    Ported onto the typed IR (`analysis.ir.op_counts`, ISSUE 10) —
    behavior-identical to the regex era, asserted on recorded fixtures:
    counts are per TEXTUAL mention as whole words (``sort`` counts
    ``stablehlo.sort`` but not ``sort_key``; attribute-embedded
    references like ``#stablehlo.gather<...>`` count too), stable for
    equality/upper-bound assertions, not a dynamic execution count.

    Args:
      lowered: a ``jax.jit(f).lower(...)`` result, its ``.as_text()``
        string (StableHLO MLIR), or a pre-parsed ``analysis.ir.Module``.
      ops: StableHLO op mnemonics.

    Returns {op: count}.
    """
    from distributed_embeddings_tpu.analysis import ir
    return ir.op_counts(_hlo_text(lowered), ops)


def _hlo_text(lowered):
    from distributed_embeddings_tpu.analysis import ir
    if isinstance(lowered, (str, ir.Module)):
        return lowered
    return lowered.as_text()


_COLLECTIVES = ("ragged_all_to_all", "all_to_all", "all_gather",
                "reduce_scatter", "collective_permute")


def hlo_collective_bytes(lowered, collectives=_COLLECTIVES) -> dict:
    """Sum the payload (first-operand) bytes of each collective op in a
    lowered program, split by element dtype — the byte-level twin of
    `hlo_op_counts` and the static audit behind the wire-compression
    claim (ISSUE 5, docs/perf_model.md "Wire compression"). Ported onto
    the typed IR (`analysis.ir.collective_bytes`, ISSUE 10).

    Shapes inside shard_map bodies are per-device — ratios between two
    lowerings of the same program are what the audit asserts;
    `analysis.programs.expected_collective_bytes` is the exact
    model-side twin when fleet accounting is needed.

    Returns {op: {dtype: bytes}, "total": {dtype: bytes},
    "float_bytes": int, "int_bytes": int}.
    """
    from distributed_embeddings_tpu.analysis import ir
    return ir.collective_bytes(_hlo_text(lowered), collectives)


def hlo_collective_overlap(lowered, collectives=_COLLECTIVES,
                           compute_ops=("dot_general",
                                        "convolution")) -> dict:
    """Classify every collective in a lowered program by its dependency
    relation to the module's dense compute — the static overlap audit
    behind the lookahead pipeline (ISSUE 9, docs/perf_model.md
    "Lookahead prefetch"). Ported onto the typed IR
    (`analysis.ir.collective_overlap`, ISSUE 10), which owns the long
    method docs: call-site granularity over the interprocedural
    shmap_body call graph, conservative region folding, two-direction
    taint.

    Returns {"collectives_total", "overlap_candidates",
    "serialized_collectives", "candidates_by_op", "compute_sites"}.
    """
    from distributed_embeddings_tpu.analysis import ir
    return ir.collective_overlap(_hlo_text(lowered), collectives,
                                 compute_ops)


def fetch_sync(out) -> float:
    """Drain the device queue by FETCHING a value derived from ``out``.

    The sync of record for all timing in this repo: a host fetch of a
    value derived from the outputs cannot complete before the data exists,
    whatever the backend's ``block_until_ready`` does, and it is also what
    a training loop that logs its loss does.

    Cost per leaf is one element's slice + host fetch — NOT a full-leaf
    reduction (an astype/sum would materialize an f32 copy of every leaf:
    for a 4 GiB bf16 table that is an 8 GiB temp inside the timed region).
    A one-element slice carries the same guarantee: it cannot be produced
    before the leaf's buffer exists. Returns the summed scalar so callers
    can sanity-check it (note: only element [0...] of each leaf is
    observed — use a full device-side reduction if you need finiteness of
    the whole output).
    """
    import jax.numpy as jnp
    total = 0.0
    fetched = False
    for leaf in jax.tree.leaves(out):
        if not hasattr(leaf, "dtype") or leaf.size == 0:
            continue
        first = leaf.reshape(-1)[0] if leaf.ndim else leaf
        total += float(first.astype(jnp.float32))
        fetched = True
    if not fetched:
        # no fetchable array leaf (empty/none): block_until_ready rather
        # than silently timing only the dispatch (ADVICE r3)
        jax.block_until_ready(out)
    return total


class BenchResult(NamedTuple):
    mean_s: float
    p50_s: float
    min_s: float
    iters: int
    compile_s: float

    @property
    def mean_ms(self) -> float:
        return self.mean_s * 1e3

    def __str__(self):
        return (f"mean={self.mean_s * 1e3:.3f}ms p50={self.p50_s * 1e3:.3f}ms "
                f"min={self.min_s * 1e3:.3f}ms (compile {self.compile_s:.1f}s, "
                f"{self.iters} iters)")


def benchmark(fn: Callable, *args, iters: int = 20, warmup: int = 2,
              **kwargs) -> BenchResult:
    """Time `fn(*args)` with device sync per iteration.

    The first call (compile) is timed separately; `warmup` additional calls
    run before measurement to settle caches/autotuning.
    """
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    fetch_sync(out)
    compile_s = time.perf_counter() - t0
    for _ in range(warmup):
        out = fn(*args, **kwargs)
        # sync EVERY call: XLA:CPU's in-process collectives deadlock when
        # several collective-bearing executions are queued concurrently
        # (rendezvous termination after 40s); on TPU this just serializes
        # warmup, which is fine
        fetch_sync(out)

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        fetch_sync(out)
        times.append(time.perf_counter() - t0)
    return BenchResult(mean_s=statistics.mean(times),
                       p50_s=statistics.median(times),
                       min_s=min(times), iters=iters, compile_s=compile_s)


def benchmark_train_steps(step_fn: Callable, params, opt_state,
                          batches: Sequence, iters: int = 20,
                          warmup: int = 2):
    """Time ``step_fn(params, opt_state, *batch) -> (params, opt_state,
    loss)`` with the state THREADED from call to call, so the step may
    donate it (an out-of-place step holds two copies of every table and
    accumulator). Rotates through pre-built `batches` (tuples of the
    batch args) so input-dependent effects (e.g. power-law gather
    locality) are averaged; each call is synced by fetching its loss.
    The first call (compile) is timed separately. Returns
    (BenchResult, params, opt_state)."""
    def one(i, params, opt_state):
        t0 = time.perf_counter()
        params, opt_state, loss = step_fn(params, opt_state,
                                          *batches[i % len(batches)])
        fetch_sync(loss)
        return time.perf_counter() - t0, params, opt_state

    compile_s, params, opt_state = one(0, params, opt_state)
    for i in range(warmup):
        _, params, opt_state = one(i, params, opt_state)
    times = []
    for i in range(iters):
        dt, params, opt_state = one(i, params, opt_state)
        times.append(dt)
    return (BenchResult(mean_s=statistics.mean(times),
                        p50_s=statistics.median(times),
                        min_s=min(times), iters=iters, compile_s=compile_s),
            params, opt_state)


@contextlib.contextmanager
def trace(logdir: str, host_tracer_level: int = 2,
          python_tracer_level: Optional[int] = None):
    """Capture a jax.profiler trace for everything inside the block:

        with profiling.trace("/tmp/trace"):
            step(params, batch)
            jax.block_until_ready(...)

    View with TensorBoard's profile plugin or ui.perfetto.dev.

    Args:
      host_tracer_level: TraceMe verbosity (1 critical, 2 info — the
        default, 3 verbose).
      python_tracer_level: 0 disables the per-python-call tracer. THE
        knob for long captures (ISSUE 14): the python tracer emits one
        event per interpreted call, and a multi-second bench run
        overflows the profiler's host event buffer with them — observed
        to silently DROP the later `TraceAnnotation` events (the kernels
        bench's late arms lost their spans). None (default) keeps the
        profiler's stock behavior.

    When either knob differs from the stock (2, None) the session is
    built directly with `ProfileOptions`; if this jaxlib cannot (API
    drift), the capture falls back to the stock tracer rather than
    failing the run — the options are fidelity, not correctness.
    """
    if host_tracer_level == 2 and python_tracer_level is None:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        return
    sess = None
    try:
        from jax._src.lib import xla_client
        opts = xla_client.profiler.ProfileOptions()
        opts.host_tracer_level = int(host_tracer_level)
        if python_tracer_level is not None:
            opts.python_tracer_level = int(python_tracer_level)
        # backends must wake before the tracer (the stock start_trace
        # does the same — on Cloud TPU a later libtpu init would miss
        # the device tracer entirely)
        jax.devices()
        sess = xla_client.profiler.ProfilerSession(opts)
    except Exception:  # noqa: BLE001 - options are best-effort fidelity
        sess = None
    if sess is None:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        return
    try:
        yield
    finally:
        sess.stop_and_export(str(logdir))


def annotate(name: str):
    """Named region that shows up in profiler traces (TraceAnnotation)."""
    return jax.profiler.TraceAnnotation(name)


def benchmark_chained(step: Callable, state, iters: int = 20) -> BenchResult:
    """Steady-state timing of `state -> state` work as ONE device program:
    a jitted fori_loop executes `step` `iters` times with the carried state
    forcing inter-iteration dependencies. Immune to per-dispatch latency and
    async-dispatch ambiguity (both observed to distort per-call timing over
    remote-attached TPUs); wall-clock / iters is pure device time.

    Timing is SLOPE-BASED with fetch sync (see ``fetch_sync``): the loop
    program runs once (t1) and then twice back-to-back (t2); per-iter time is
    (t2 - t1) / iters, which cancels every constant overhead — dispatch,
    fetch round-trip, queue drain — even on backends where
    ``block_until_ready`` is unreliable.
    """
    from jax import lax

    lf = jax.jit(lambda s: lax.fori_loop(0, iters, lambda i, s: step(s), s))
    t0 = time.perf_counter()
    out = lf(state)
    fetch_sync(out)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = lf(state)
    fetch_sync(out)
    t1 = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = lf(state)
    out = lf(out)
    fetch_sync(out)
    t2 = time.perf_counter() - t0

    per_iter = max(t2 - t1, 1e-9) / iters
    return BenchResult(mean_s=per_iter, p50_s=per_iter,
                       min_s=min(per_iter, t1 / iters), iters=iters,
                       compile_s=compile_s)
