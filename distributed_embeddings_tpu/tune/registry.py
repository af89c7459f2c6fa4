"""The declarative knob-space registry — single source of truth.

Before this module every performance knob lived as a scattered
``os.environ.get``/``measured_default`` call site, and the list of what
is tunable existed only in humans (and a duplicated copy inside
tools/window_rehearsal.py). The registry makes the space declarative:

  * ``bench.py --mode tune`` enumerates its search space from here,
  * ``docs/perf_model.md``'s knob table is GENERATED from here
    (``knob_table_markdown``; drift-gated by tests/test_tune.py),
  * ``tools/lint_invariants.py``'s scenario-knob rule validates soak /
    fleet scenario ``"knobs"`` overrides against it,
  * ``tune.runtime.RuntimeTuner`` refuses to auto-flip any knob whose
    safety class is not ``runtime``,
  * ``tune.resolve`` rejects tuned-config entries naming unknown knobs
    or illegal values (loudly — warning + counter, never a crash).

Safety classes:
  offline  changes the lowered program / plan (wire dtypes, kernel
           dispatch, lookahead depth...): legal only between runs,
           decided by the offline search harness.
  runtime  host-side policy read per use (publish cadence, admission
           limits...): safe for the RuntimeTuner to flip on a live
           system.

Parity classes (what adopting a non-default value does to numerics):
  exact    bit-exact vs the fallback by construction or by a standing
           parity gate (tiled/pallas scatter, int16 id wire, lookahead
           patching, pipeline depth, cadences). The offline tuner may
           adopt these into a config-of-record's ``winner``.
  bounded  parity-gated to a documented tolerance (bf16 wire, int8/fp8
           storage, hot-row float reorder). The tuner never silently
           adopts these: they ride as ``staged_tpu_arms`` for a human +
           chip-run decision.
  numerics user-visible numerics trade (cumsum dedup's ~sqrt(N)*eps +
           weakened rep promise). Never auto-flipped.
"""

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

OFFLINE = "offline"
RUNTIME = "runtime"

PARITY_EXACT = "exact"
PARITY_BOUNDED = "bounded"
PARITY_NUMERICS = "numerics"


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable knob. ``values`` is the closed legal set for enum
    knobs; ``None`` means an integer domain bounded by
    [``int_min``, ``int_max``] (``None`` bound = open). ``fallback`` is
    the hand-picked default the resolution chain bottoms out at —
    always legal by construction (validated at import)."""
    name: str                       # short slug, e.g. "scatter_impl"
    env: str                        # e.g. "DET_SCATTER_IMPL"
    values: Optional[Tuple[str, ...]]
    fallback: str
    safety: str                     # OFFLINE | RUNTIME
    parity: str                     # exact | bounded | numerics
    cost_model: Optional[str]       # cost hook the search prunes with
    doc: str
    int_min: Optional[int] = None
    int_max: Optional[int] = None

    def is_legal(self, value: str) -> bool:
        if self.values is not None:
            return value in self.values
        try:
            v = int(value)
        except (TypeError, ValueError):
            # the empty string means "unset" for open-domain knobs whose
            # fallback is unset (fleet queue-rows cap)
            return value == "" and self.fallback == ""
        if self.int_min is not None and v < self.int_min:
            return False
        if self.int_max is not None and v > self.int_max:
            return False
        return True

    def domain_str(self) -> str:
        if self.values is not None:
            return "/".join(self.values)
        lo = "-inf" if self.int_min is None else str(self.int_min)
        hi = "inf" if self.int_max is None else str(self.int_max)
        return f"int [{lo}, {hi}]"


# Cost-model hook names (what `bench.py --mode tune` prunes/ranks with):
#   collective_bytes  analysis.programs.expected_collective_bytes over
#                     the arm's plan — exact per-device payload bytes
#   padding_report    layer.exchange_padding_report structural fields
#   sort_audit        analysis op-count gates (stablehlo.sort bounds)
#   overlap_audit     collective-overlap classification (lookahead)
#   payload_bytes     wire.delta_row_bytes at-rest/stream accounting
#   step_time         no static model — measured arm only
KNOBS: Tuple[Knob, ...] = (
    Knob("scatter_impl", "DET_SCATTER_IMPL",
         ("xla", "tiled", "pallas", "pallas-dma"), "xla",
         OFFLINE, PARITY_EXACT, "sort_audit",
         "sparse-update scatter kernel family (TPU dispatch; "
         "compile-probe gated, bit-exact vs xla)"),
    Knob("lookup_path", "DET_LOOKUP_PATH",
         ("auto", "xla", "tiled", "fused", "pallas"), "auto",
         OFFLINE, PARITY_EXACT, "sort_audit",
         "forward gather/combine path (fused = Pallas "
         "gather->combine, parity-gated)"),
    Knob("dedup_impl", "DET_DEDUP_IMPL", ("sort", "cumsum"), "sort",
         OFFLINE, PARITY_NUMERICS, "step_time",
         "id-dedup aggregation; cumsum trades ~sqrt(N)*eps precision — "
         "never auto-flipped"),
    Knob("exchange_wire", "DET_EXCHANGE_WIRE",
         ("f32", "bf16", "bf16-sr"), "f32",
         OFFLINE, PARITY_BOUNDED, "collective_bytes",
         "float payload dtype on every exchange collective (bf16 "
         "halves the dominant wire)"),
    Knob("id_wire", "DET_ID_WIRE", ("auto", "int32"), "auto",
         OFFLINE, PARITY_EXACT, "collective_bytes",
         "id-exchange dtype; auto narrows to int16 where the planner "
         "proves the key space fits (lossless)"),
    Knob("store_dtype", "DET_STORE_DTYPE", ("f32", "int8", "fp8"), "f32",
         OFFLINE, PARITY_BOUNDED, "payload_bytes",
         "at-rest row storage dtype for eligible (cold/offloaded) "
         "buckets"),
    Knob("delta_dtype", "DET_DELTA_DTYPE", ("f32", "int8", "fp8"), "f32",
         OFFLINE, PARITY_BOUNDED, "payload_bytes",
         "published delta/snapshot stream payload dtype (independent "
         "of table residency)"),
    Knob("hot_rows", "DET_HOT_ROWS", None, "0",
         OFFLINE, PARITY_BOUNDED, "padding_report",
         "replicated hot-shard rows per MP bucket (0 = off; <=1e-5 "
         "multi-hot float reorder)", int_min=0),
    Knob("lookahead", "DET_LOOKAHEAD", ("0", "1"), "0",
         OFFLINE, PARITY_EXACT, "overlap_audit",
         "prefetch pipeline depth: overlap batch N+1's exchanges with "
         "batch N's dense compute (bit-exact with patching)"),
    Knob("pipeline_depth", "DET_PIPELINE_DEPTH", None, "2",
         OFFLINE, PARITY_EXACT, "step_time",
         "ingest pipeline inter-stage queue bound (backpressure)",
         int_min=1),
    Knob("publish_every", "DET_PUBLISH_EVERY", None, "0",
         RUNTIME, PARITY_EXACT, "payload_bytes",
         "training-side delta publish cadence in steps (0 = off; "
         "serving freshness vs publish cost)", int_min=0),
    Knob("snapshot_every", "DET_STORE_SNAPSHOT_EVERY", None, "0",
         RUNTIME, PARITY_EXACT, "payload_bytes",
         "full-snapshot compaction cadence in publishes (0 = only the "
         "mandatory first; re-anchor cost vs replay length)", int_min=0),
    Knob("vocab_admit", "DET_VOCAB_ADMIT", None, "2",
         RUNTIME, PARITY_BOUNDED, "step_time",
         "vocab/hot-row admission threshold: observed hits before a "
         "key is admitted", int_min=1),
    Knob("fleet_queue_depth", "DET_FLEET_MAX_QUEUE_DEPTH", None, "64",
         RUNTIME, PARITY_EXACT, "step_time",
         "admission control: shed when a replica's batcher holds this "
         "many queued requests", int_min=1),
    Knob("fleet_queue_rows", "DET_FLEET_MAX_QUEUE_ROWS", None, "",
         RUNTIME, PARITY_EXACT, "step_time",
         "admission control: shed when queued ROWS exceed this bound "
         "(empty = unlimited)", int_min=1),
)

_BY_NAME: Dict[str, Knob] = {k.name: k for k in KNOBS}
_BY_ENV: Dict[str, Knob] = {k.env: k for k in KNOBS}

# registry invariants, enforced at import: a duplicated env var or an
# illegal fallback would silently corrupt every consumer above
assert len(_BY_NAME) == len(KNOBS), "duplicate knob name in registry"
assert len(_BY_ENV) == len(KNOBS), "duplicate knob env var in registry"
for _k in KNOBS:
    assert _k.safety in (OFFLINE, RUNTIME), _k
    assert _k.parity in (PARITY_EXACT, PARITY_BOUNDED,
                         PARITY_NUMERICS), _k
    assert _k.is_legal(_k.fallback), \
        f"knob {_k.name}: fallback {_k.fallback!r} outside its own domain"


def all_knobs() -> Tuple[Knob, ...]:
    return KNOBS


def get_knob(name_or_env: str) -> Knob:
    """Look a knob up by slug or env var; KeyError on unknown."""
    k = _BY_NAME.get(name_or_env) or _BY_ENV.get(name_or_env)
    if k is None:
        raise KeyError(f"unknown knob {name_or_env!r}; registry has "
                       f"{sorted(_BY_NAME)}")
    return k


def maybe_get(name_or_env: str) -> Optional[Knob]:
    return _BY_NAME.get(name_or_env) or _BY_ENV.get(name_or_env)


def validate_override(env: str, value) -> Optional[str]:
    """One scenario/tuned-config override checked against the registry.
    Returns an error string (for the scenario lint / tuned-file
    validator) or None when (env, value) is a known knob with a legal
    value."""
    k = _BY_ENV.get(env)
    if k is None:
        return (f"unknown knob {env!r}: not in the tune registry "
                f"(known: {sorted(_BY_ENV)})")
    if not isinstance(value, str):
        return (f"{env}: override values are env-var STRINGS, got "
                f"{type(value).__name__} {value!r}")
    if not k.is_legal(value):
        return (f"{env}={value!r}: illegal value, domain is "
                f"{k.domain_str()}")
    return None


def runtime_knobs() -> Tuple[Knob, ...]:
    return tuple(k for k in KNOBS if k.safety == RUNTIME)


def offline_knobs() -> Tuple[Knob, ...]:
    return tuple(k for k in KNOBS if k.safety == OFFLINE)


def knob_table_markdown() -> str:
    """The generated knob table docs/perf_model.md embeds between its
    knob-table markers — regenerate with
    ``python -m distributed_embeddings_tpu.tune.registry`` (drift-gated
    by tests/test_tune.py)."""
    lines = [
        "| knob | env var | legal values | default | safety | parity "
        "| cost model |",
        "|---|---|---|---|---|---|---|",
    ]
    for k in KNOBS:
        lines.append(
            f"| {k.name} | `{k.env}` | {k.domain_str()} "
            f"| `{k.fallback or '(unset)'}` | {k.safety} | {k.parity} "
            f"| {k.cost_model or '—'} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(knob_table_markdown())
