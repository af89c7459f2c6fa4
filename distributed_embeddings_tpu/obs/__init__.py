"""Unified runtime telemetry (ISSUE 11/14/25): one metric registry,
host-side step-span tracing, declarative SLO evaluation, a bounded
flight recorder with version-lineage tracks, and the stage scopes that
name the work inside a jitted train step (`obs.stages`) — across
train/serve/vocab/store/lookahead.

See docs/observability.md for the full API and schema; the short form:

    from distributed_embeddings_tpu import obs

    reg = obs.MetricRegistry()            # or obs.default_registry()
    reg.counter("train/steps").inc()
    with obs.span("train/step", reg):     # ~3 us; one recorder entry
        ...
    obs.default_recorder().spans("train/step")    # name, start, end, parent, step
    snap = reg.snapshot()
    findings = obs.evaluate_rules(obs.load_rules("slo.json"), snap)
    obs.default_recorder().export("trace.json")   # Perfetto-loadable
    with obs.stages.stage("lookup"):              # inside a traced step
        ...
"""

from distributed_embeddings_tpu.obs.registry import (  # noqa: F401
    Counter, Gauge, LatencyHistogram, MetricRegistry, default_registry,
    metric_key, reset_default_registry)
from distributed_embeddings_tpu.obs.slo import (  # noqa: F401
    evaluate_rules, load_rules, metric_value, summarize)
from distributed_embeddings_tpu.obs.spans import (  # noqa: F401
    annotation, current_span, current_step, install_gc_hook, next_step, span,
    spanned)
from distributed_embeddings_tpu.obs.instrument import (  # noqa: F401
    export_exchange_gauges, export_kernel_gauges)
from distributed_embeddings_tpu.obs.trace import (  # noqa: F401
    FlightRecorder, SpanRecord, default_recorder, dump_postmortem,
    reset_default_recorder)
from distributed_embeddings_tpu.obs import stages  # noqa: F401

__all__ = [
    "Counter", "Gauge", "LatencyHistogram", "MetricRegistry",
    "default_registry", "reset_default_registry", "metric_key",
    "span", "annotation", "current_span", "current_step", "next_step",
    "spanned", "install_gc_hook",
    "load_rules", "evaluate_rules", "metric_value", "summarize",
    "export_exchange_gauges", "export_kernel_gauges",
    "FlightRecorder", "SpanRecord", "default_recorder",
    "reset_default_recorder",
    "dump_postmortem", "stages",
]
