"""Attribution-driven auto-tuning of the performance-knob space.

The system exposes ~15 orthogonal performance knobs (scatter impl,
lookup path, exchange wire, id wire, storage dtypes, hot rows,
lookahead, pipeline depth, publish cadence, admission limits, ...),
per-stage device time (the `det.*` stage scopes of obs/stages.py, read
from a trace by `benchmark.run --trace 1`) and static cost
models (analysis.programs.expected_collective_bytes,
exchange_padding_report, docs/perf_model.md projections). This package
closes the measure->decide loop (ROADMAP item 5):

  registry  the declarative knob-space registry — each knob's env var,
            legal values, safety class (offline vs runtime-flippable),
            parity class and cost-model hook. THE single source of
            truth the docs table, the scenario lint and the search
            harness all read.
  resolve   the consumption seam: `knob_value(env, fallback)` resolves
            env var > tools/tuned/<workload>.json (explicit opt-in via
            DET_TUNED_WORKLOAD / DET_TUNED_PATH) > the file
            DET_MEASURED_DEFAULTS_PATH names (TPU-backend only) > fallback,
            every tuned/measured adoption leaving a flight-recorder
            event. `ops.sparse_update.measured_default` delegates here.
  search    bench-independent search machinery for `bench.py --mode
            tune`: arm enumeration over the registry, cost-model
            pruning (every pruned arm logged with its rationale — no
            silent caps), and the `tuned-config-v1` config-of-record
            schema + validator.
  runtime   the online half (stretch): `RuntimeTuner` maps SLO
            evaluator findings to bounded adjustments of
            runtime-flippable knobs only, every auto-flip leaving a
            flight-recorder event.
"""

from .registry import (Knob, all_knobs, get_knob, knob_table_markdown,
                       validate_override)
from .resolve import knob_value, reset_cache, tuned_source
from .search import (TUNED_SCHEMA, Arm, enumerate_arms, prune_by_cost,
                     validate_tuned_record)
from .runtime import RuntimeTuner

__all__ = [
    "Knob", "all_knobs", "get_knob", "knob_table_markdown",
    "validate_override", "knob_value", "reset_cache", "tuned_source",
    "TUNED_SCHEMA", "Arm", "enumerate_arms", "prune_by_cost",
    "validate_tuned_record", "RuntimeTuner",
]
