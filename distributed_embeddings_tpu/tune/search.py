"""Offline knob-space search machinery for ``bench.py --mode tune``.

Bench-independent so it is unit-testable without a mesh: arm
enumeration over the registry, cost-model pruning with a full audit
trail (EVERY pruned arm is logged with its predicted costs and a
rationale — a tuner that silently capped its search space would read as
"covered everything" when it didn't), and the ``tuned-config-v1``
config-of-record schema + validator shared by the writer (bench) and
the reader (``tune.resolve``).

The config-of-record is evidence-first: the winning values ride next to
the per-arm metric snapshots, the prune log, the device-attribution
block and the audit-findings stamp that justify them, so a future
chip run (or reviewer) can re-litigate the decision from the file
alone.
"""

import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import registry as _registry

TUNED_SCHEMA = "tuned-config-v1"


@dataclasses.dataclass
class Arm:
    """One point in the knob space: env-var overrides + a stable key."""
    overrides: Dict[str, str]
    key: str = ""

    def __post_init__(self):
        if not self.key:
            self.key = arm_key(self.overrides)


def arm_key(overrides: Dict[str, str]) -> str:
    """Stable, human-greppable arm label: short knob slugs, sorted."""
    if not overrides:
        return "defaults"
    parts = []
    for env in sorted(overrides):
        k = _registry.maybe_get(env)
        parts.append(f"{k.name if k else env}={overrides[env]}")
    return ",".join(parts)


def enumerate_arms(space: Dict[str, Sequence[str]],
                   include_defaults: bool = True) -> List[Arm]:
    """Cross-product over ``{env: [values...]}``. Every env must name a
    registry knob and every value must be legal — an illegal search
    space refuses at enumeration, not mid-measurement. The all-fallback
    baseline arm rides first (the hand-picked config the winner must
    match or beat)."""
    for env, values in space.items():
        k = _registry.get_knob(env)          # KeyError on unknown knob
        for v in values:
            err = _registry.validate_override(k.env, v)
            if err is not None:
                raise ValueError(f"search space: {err}")
    envs = sorted(space)
    arms: List[Arm] = []
    seen = set()
    if include_defaults:
        base = {e: _registry.get_knob(e).fallback for e in envs}
        arms.append(Arm(base, key="defaults"))
        seen.add(tuple(sorted(base.items())))
    for combo in itertools.product(*(space[e] for e in envs)):
        ov = dict(zip(envs, combo))
        sig = tuple(sorted(ov.items()))
        if sig in seen:
            continue
        seen.add(sig)
        arms.append(Arm(ov))
    return arms


def prune_by_cost(arms: Sequence[Arm],
                  cost_fn: Callable[[Arm], Dict[str, float]],
                  keep: int,
                  order: Sequence[str],
                  always_keep: Sequence[str] = ("defaults",),
                  ) -> Tuple[List[Arm], List[dict], bool]:
    """Rank arms by the cost models and keep the ``keep`` cheapest.

    ``cost_fn(arm)`` returns the arm's predicted structural costs;
    ``order`` names the cost keys in ranking priority (lexicographic —
    e.g. ``("collective_bytes", "padding_ratio")``). Arms named in
    ``always_keep`` survive unconditionally (the baseline must always
    be measured — a tuner that never re-measures the incumbent cannot
    claim "or better").

    Returns ``(survivors, pruned_log, audit_ok)``: every pruned arm is
    logged with its predicted costs, its rank and the rationale; and
    ``audit_ok`` asserts the cost-model ORDERING was respected — no
    pruned arm predicted cheaper than a kept arm (the CI tune smoke
    gates on this; a False here means the pruning logic itself is
    buggy, which must fail loudly, not ship a record)."""
    costed = []
    for arm in arms:
        costs = dict(cost_fn(arm))
        rank = tuple(float(costs.get(k, 0.0)) for k in order)
        costed.append((rank, arm, costs))
    costed.sort(key=lambda t: (t[0], t[1].key))
    keep = max(int(keep), 1)
    survivors: List[Arm] = []
    pruned_log: List[dict] = []
    kept_ranks, pruned_ranks = [], []
    for i, (rank, arm, costs) in enumerate(costed):
        forced = arm.key in always_keep
        if len(survivors) < keep or forced:
            survivors.append(arm)
            kept_ranks.append(rank)
        else:
            best = costed[0]
            pruned_log.append({
                "arm": arm.key, "overrides": arm.overrides,
                "predicted": costs, "rank": i,
                "rationale": (
                    f"predicted {order[0]}={costs.get(order[0])} ranks "
                    f"#{i + 1}/{len(costed)} (best arm "
                    f"{best[1].key!r}: {order[0]}="
                    f"{best[2].get(order[0])}); outside keep={keep}"),
            })
            pruned_ranks.append(rank)
    # ordering audit: every non-forced survivor must predict <= every
    # pruned arm on the ranking tuple
    free_kept = [r for r, a in zip(kept_ranks, survivors)
                 if a.key not in always_keep]
    audit_ok = (not pruned_ranks or not free_kept
                or max(free_kept) <= min(pruned_ranks))
    return survivors, pruned_log, audit_ok


def split_adoptable(overrides: Dict[str, str]) -> Tuple[Dict[str, str],
                                                        Dict[str, str]]:
    """(adoptable, staged): non-default override values whose knob
    parity class is ``exact`` may enter a config-of-record ``winner``;
    ``bounded``/``numerics`` overrides must ride as staged TPU-decision
    arms instead (the f32/default-path bit-exactness acceptance:
    the tuner only ADOPTS among bit-exact-gated strategies)."""
    adoptable, staged = {}, {}
    for env, value in overrides.items():
        k = _registry.get_knob(env)
        if value == k.fallback:
            adoptable[env] = value
        elif k.parity == _registry.PARITY_EXACT:
            adoptable[env] = value
        else:
            staged[env] = value
    return adoptable, staged


def build_record(workload: str, winner: Dict[str, str],
                 arms: Sequence[dict], pruned: Sequence[dict],
                 prune_order: Sequence[str], prune_audit_ok: bool,
                 beats_default: Dict[str, bool],
                 staged_tpu_arms: Sequence[dict],
                 git_sha: str, backend: str, created_at: str,
                 attribution: Optional[dict] = None,
                 extra: Optional[dict] = None) -> dict:
    """Assemble a schema-valid tuned-config-v1 doc (validated before
    return — the writer can never emit a record the reader rejects)."""
    doc = {
        "schema": TUNED_SCHEMA,
        "workload": workload,
        "created_at": created_at,
        "git_sha": git_sha,
        "backend": backend,
        "winner": dict(winner),
        "arms": list(arms),
        "pruned": list(pruned),
        "prune_order": list(prune_order),
        "prune_audit_ok": bool(prune_audit_ok),
        "beats_default": dict(beats_default),
        "staged_tpu_arms": list(staged_tpu_arms),
    }
    if attribution is not None:
        doc["device_attribution"] = attribution
    if extra:
        doc.update(extra)
    errors = validate_tuned_record(doc)
    if errors:
        raise ValueError(f"refusing to emit an invalid tuned record: "
                         f"{errors}")
    return doc


def validate_tuned_record(doc) -> List[str]:
    """Schema check for a tuned-config-v1 doc; [] = valid. Shared by
    the bench writer (refuse to emit garbage) and ``tune.resolve`` (a
    stale/malformed file must fall through loudly, never crash)."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a JSON object ({type(doc).__name__})"]
    if doc.get("schema") != TUNED_SCHEMA:
        errors.append(f"schema {doc.get('schema')!r} != {TUNED_SCHEMA!r} "
                      "(stale or foreign file)")
    if not isinstance(doc.get("workload"), str) or not doc.get("workload"):
        errors.append("missing/invalid 'workload'")
    if not isinstance(doc.get("winner"), dict):
        errors.append("missing/invalid 'winner' (env -> value dict)")
    for field in ("created_at", "git_sha"):
        if not isinstance(doc.get(field), str) or not doc.get(field):
            errors.append(f"missing/invalid {field!r} (provenance is "
                          "not optional)")
    arms = doc.get("arms")
    if not isinstance(arms, list) or not arms:
        errors.append("missing/empty 'arms' (a record with no measured "
                      "evidence is not a config-of-record)")
    else:
        for i, arm in enumerate(arms):
            if not isinstance(arm, dict) or "overrides" not in arm \
                    or "key" not in arm:
                errors.append(f"arms[{i}]: needs 'key' + 'overrides'")
    pruned = doc.get("pruned")
    if not isinstance(pruned, list):
        errors.append("missing 'pruned' (the prune log is part of the "
                      "evidence trail; use [] when nothing was pruned)")
    else:
        for i, p in enumerate(pruned):
            if not isinstance(p, dict) or "rationale" not in p:
                errors.append(f"pruned[{i}]: every pruned arm carries "
                              "a 'rationale'")
    if "prune_audit_ok" in doc and doc["prune_audit_ok"] is not True:
        errors.append("prune_audit_ok is not True: the cost-model "
                      "ordering audit failed at write time")
    if not isinstance(doc.get("staged_tpu_arms", []), list):
        errors.append("'staged_tpu_arms' must be a list")
    return errors
