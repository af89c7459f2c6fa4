"""Bounded multi-stage background ingestion pipeline.

The reference hides host-side input cost behind device compute with a
1-thread prefetch executor (reference examples/dlrm/utils.py:231-254); the
seed's `utils/prefetch.py` kept only the staging half of that overlap — the
`stage()` call runs in the consumer thread, so pread, hash lookup and numpy
batch assembly all still serialize with the train step. This module is the
full overlap: every ingestion stage (read → preprocess → stage) runs in its
own persistent worker thread connected by bounded queues, so steady-state
end-to-end throughput is set by the SLOWEST stage, not the SUM of stages
(docs/perf_model.md "Ingestion pipeline").

Contract highlights:
  * Order-preserving: one worker per stage, FIFO queues — pipelined output
    is bit-identical to serial iteration (tests/test_pipeline.py).
  * Backpressure: every inter-stage queue is bounded by `depth`, so at most
    ``(stages + 1) * depth + stages`` batches are ever materialized.
  * Failure propagation: a worker exception rides the queue BEHIND the
    items already produced — the consumer drains those, then the original
    exception re-raises at the call site (no hang, no silent drop).
  * Clean shutdown: `close()` (or exhaustion, or the context manager) stops
    and joins every worker; no threads leak across pipeline lifetimes.
  * Accounting: per-stage wall time lands in an
    ``ingest/stage_seconds{stage=...}`` histogram family of an
    `obs.MetricRegistry` (`stage_summaries()` reads them; pass
    ``registry=`` to land them in a shared run registry — ISSUE 11), and
    each stage body runs under a `utils.profiling.annotate` region so
    profiler traces show where ingestion time goes.
"""

import queue as queue_lib
import threading
import time
from typing import Any, Callable, Iterable, Optional, Sequence, Tuple

from distributed_embeddings_tpu import faults
from distributed_embeddings_tpu.obs.registry import MetricRegistry

# transient stage-body errors (OSError — real filesystem flakes and the
# injected ``ingest.stage`` fault alike) retry in place this many times
# before propagating through the normal drain-then-raise path; stage fns
# are pure per-item transforms by contract, so a retry is safe
_STAGE_RETRIES = 3

__all__ = ["IngestPipeline", "SerialPipeline", "READ_STAGE"]

# name of the implicit first stage (pulling the source iterator); the
# source's own work — pread, batch synthesis — is accounted here
READ_STAGE = "read"

_END = object()          # end-of-stream sentinel


class _Failure:
    """A worker exception in transit to the consumer (rides the FIFO queue
    behind the items produced before it, preserving drain order)."""

    __slots__ = ("exc", "stage")

    def __init__(self, exc: BaseException, stage: str):
        self.exc = exc
        self.stage = stage


def _annotate(name: str):
    """profiling.annotate, tolerating backends with no profiler configured.

    Delegates to `obs.spans.annotation`, whose works/doesn't-work probe
    is cached process-wide — a profiler-less backend pays ONE failed
    construction total, not one raised-and-swallowed exception per stage
    invocation on every batch (measurable overhead at ingest rates)."""
    from distributed_embeddings_tpu.obs.spans import annotation
    return annotation(f"ingest/{name}")


class IngestPipeline:
    """Background ingestion: stages run ahead of the consumer in threads.

    Args:
      source: iterable of batches (each item is whatever the first stage
        consumes — raw buffers, numpy pytrees, ...). Pulled by a persistent
        reader thread; `next(source)` time is accounted as the ``read``
        stage.
      stages: sequence of ``(name, fn)`` — each fn maps one item to the
        next representation (e.g. ``("preprocess", ds.preprocess)``,
        ``("stage", lambda b: stage_dp_batch(mesh, b))``). One persistent
        worker thread per stage, applied in order.
      depth: bound of every inter-stage queue (2 = classic double buffer).
        Total in-flight batches are capped at
        ``(len(stages) + 1) * depth + len(stages)``.
      name: thread-name prefix (useful in py-spy / faulthandler dumps).
      registry: optional `obs.MetricRegistry` the per-stage histograms
        are created in, as ``ingest/stage_seconds{stage=...}`` families
        (ISSUE 11 — `training.fit` passes its run registry so ingest
        timing lands in the unified snapshot). Default: a private
        registry, preserving per-instance accounting; each stage's
        histogram has exactly one writer thread either way.

    Iterate it like any iterator; `close()` is called automatically on
    exhaustion and on `with` exit, and is idempotent. A worker exception
    surfaces at the consumer as the original exception after the items
    staged before it have been drained.
    """

    def __init__(self, source: Iterable, stages: Sequence[Tuple[str, Callable]],
                 depth: int = 2, name: str = "ingest",
                 registry: Optional[MetricRegistry] = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._stages = [(str(n), fn) for n, fn in stages]
        names = [READ_STAGE] + [n for n, _ in self._stages]
        if len(set(names)) != len(names):
            raise ValueError(f"stage names must be unique (and not "
                             f"{READ_STAGE!r}): {names}")
        self._source = iter(source)
        self._depth = int(depth)
        self._stop = threading.Event()
        self._closed = False
        reg = registry if registry is not None else MetricRegistry()
        self._registry = reg
        self._hists = {n: reg.histogram("ingest/stage_seconds", stage=n)
                       for n in names}
        # queues[0] feeds stage 0; queues[-1] feeds the consumer
        self._queues = [queue_lib.Queue(maxsize=self._depth)
                        for _ in range(len(self._stages) + 1)]
        self._threads = [threading.Thread(
            target=self._read_loop, name=f"{name}-{READ_STAGE}", daemon=True)]
        for i, (sname, fn) in enumerate(self._stages):
            self._threads.append(threading.Thread(
                target=self._stage_loop, args=(i, sname, fn),
                name=f"{name}-{sname}", daemon=True))
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------ workers
    def _put(self, q: queue_lib.Queue, item) -> bool:
        """Bounded put that stays responsive to shutdown. Returns False when
        the pipeline stopped before the item could be enqueued."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue_lib.Full:
                continue
        return False

    def _get(self, q: queue_lib.Queue):
        while not self._stop.is_set():
            try:
                return q.get(timeout=0.05)
            except queue_lib.Empty:
                continue
        return _END

    def _read_loop(self):
        hist = self._hists[READ_STAGE]
        out = self._queues[0]
        while True:
            t0 = time.perf_counter()
            try:
                with _annotate(READ_STAGE):
                    item = next(self._source)
            except StopIteration:
                self._put(out, _END)
                return
            except BaseException as e:  # noqa: BLE001 - propagate, never hang
                self._put(out, _Failure(e, READ_STAGE))
                return
            hist.record(time.perf_counter() - t0)
            if not self._put(out, item):
                return

    def _run_stage_body(self, sname: str, fn: Callable, item):
        """One stage application with bounded transient retry (ISSUE 13):
        an `OSError` from the stage body — the ``ingest.stage`` fault
        point injects exactly this class — retries in place up to
        `_STAGE_RETRIES` times (tiny capped backoff, counted in
        ``ingest/stage_retries_total{stage=}``) before propagating, so a
        filesystem flake degrades to a latency blip instead of killing
        the training run. Non-OSError exceptions propagate immediately
        (the drain-then-raise contract is unchanged)."""
        for attempt in range(_STAGE_RETRIES + 1):
            try:
                faults.check_raise("ingest.stage", stage=sname)
                with _annotate(sname):
                    return fn(item)
            except OSError:
                if attempt >= _STAGE_RETRIES:
                    raise
                self._registry.counter("ingest/stage_retries_total",
                                       stage=sname).inc()
                time.sleep(min(0.002 * (2 ** attempt), 0.02))

    def _stage_loop(self, idx: int, sname: str, fn: Callable):
        hist = self._hists[sname]
        inq, outq = self._queues[idx], self._queues[idx + 1]
        while True:
            item = self._get(inq)
            if item is _END:
                self._put(outq, _END)
                return
            if isinstance(item, _Failure):
                self._put(outq, item)
                return
            t0 = time.perf_counter()
            try:
                item = self._run_stage_body(sname, fn, item)
            except BaseException as e:  # noqa: BLE001 - propagate, never hang
                self._put(outq, _Failure(e, sname))
                return
            hist.record(time.perf_counter() - t0)
            if not self._put(outq, item):
                return

    # ----------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        outq = self._queues[-1]
        while True:
            try:
                item = outq.get(timeout=0.1)
                break
            except queue_lib.Empty:
                if self._stop.is_set():
                    raise StopIteration from None
                if not self._threads[-1].is_alive() and outq.empty():
                    # final worker died without a sentinel (should be
                    # impossible — every exit path enqueues one); fail
                    # loudly rather than spin forever
                    self.close()
                    raise RuntimeError(
                        "ingestion worker exited without result") from None
        if item is _END:
            self.close()
            raise StopIteration
        if isinstance(item, _Failure):
            self.close()
            raise item.exc
        return item

    # ---------------------------------------------------------- lifecycle
    def close(self):
        """Stop and join all workers; idempotent, never raises on re-entry.

        Safe to call with items still in flight (the bounded queues are
        drained so blocked putters wake up and exit)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # drain so workers blocked on put() observe the stop promptly
        for q in self._queues:
            try:
                while True:
                    q.get_nowait()
            except queue_lib.Empty:
                pass
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = [t for t in self._threads if t.is_alive()]
        if self._threads:  # pragma: no cover - blocking source
            # a reader stuck inside next(source) cannot observe the stop
            # event; the workers are daemons, so abandoning them is safe —
            # and close() runs in finally blocks where raising would
            # clobber the caller's result (or mask the real exception)
            import warnings
            warnings.warn(
                "ingestion workers still blocked at close "
                f"({[t.name for t in self._threads]}); abandoning daemon "
                "threads (source iterator blocked in next()?)",
                RuntimeWarning, stacklevel=2)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    # --------------------------------------------------------- accounting
    def stage_summaries(self) -> dict:
        """Per-stage wall-time summaries: {stage: {count, mean_ms, p50_ms,
        p95_ms, p99_ms, max_ms}} — `read` is the implicit source stage."""
        return {n: h.summary() for n, h in self._hists.items()}

    def stage_histograms(self) -> dict:
        """The live per-stage `LatencyHistogram` objects — callers that
        aggregate across runs (`LatencyHistogram.merge`, e.g. bench reps)
        read these rather than the summarized dicts."""
        return dict(self._hists)

    def bottleneck(self) -> Optional[str]:
        """Name of the slowest stage by mean wall time (None before any
        item completed) — the stage whose rate bounds pipelined throughput."""
        means = {n: h.summary()["mean_ms"] for n, h in self._hists.items()
                 if h.count}
        return max(means, key=means.get) if means else None


class SerialPipeline:
    """The same stages run inline in the consumer thread, with the same
    per-stage accounting — the parity reference for tests (pipelined
    output must be bit-identical to this iteration order)."""

    def __init__(self, source: Iterable, stages: Sequence[Tuple[str, Callable]],
                 registry: Optional[MetricRegistry] = None):
        self._source = iter(source)
        self._stages = [(str(n), fn) for n, fn in stages]
        reg = registry if registry is not None else MetricRegistry()
        self._hists = {n: reg.histogram("ingest/stage_seconds", stage=n)
                       for n in [READ_STAGE] + [n for n, _ in self._stages]}

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        item = next(self._source)
        self._hists[READ_STAGE].record(time.perf_counter() - t0)
        for sname, fn in self._stages:
            t0 = time.perf_counter()
            item = fn(item)
            self._hists[sname].record(time.perf_counter() - t0)
        return item

    def close(self):
        pass

    def stage_summaries(self) -> dict:
        return {n: h.summary() for n, h in self._hists.items()}

    def stage_histograms(self) -> dict:
        return dict(self._hists)


def staged_batches(data: Iterable, stage: Optional[Callable] = None,
                   preprocess: Optional[Callable] = None, depth: int = 2,
                   pipelined: bool = True,
                   registry: Optional[MetricRegistry] = None) -> Any:
    """Convenience constructor for the common train-loop shape.

    Args:
      data: iterable of batches.
      stage: device staging fn (default `jax.device_put`) — e.g.
        ``lambda b: stage_dp_batch(mesh, b)``.
      preprocess: optional host transform run in its own worker between
        read and stage (e.g. `RawBinaryDataset.preprocess`, or an
        IntegerLookup translation).
      depth: per-queue bound.
      pipelined: False returns the serial (inline) form with identical
        output — the A/B switch `training.fit(pipelined=...)` exposes.
      registry: optional `obs.MetricRegistry` for the per-stage
        histograms (see `IngestPipeline`).
    """
    import jax
    stages = []
    if preprocess is not None:
        stages.append(("preprocess", preprocess))
    stages.append(("stage", stage or jax.device_put))
    if pipelined:
        return IngestPipeline(data, stages, depth=depth, registry=registry)
    return SerialPipeline(data, stages, registry=registry)
