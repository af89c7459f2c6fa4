"""Dynamic vocabulary manager: streaming admission, cold-row eviction,
and recompile-free table growth (ISSUE 7).

The reference's third pillar is on-the-fly vocabulary building — an
`IntegerLookup` over a device-side cuCollections hash map. We reproduce
the hash-lookup half host-side (`native/hashmap.cpp`); this module turns
it into a full runtime-capacity system for production key spaces that
are unbounded and DRIFT:

  * **Frequency-gated admission.** Raw (untranslated, arbitrary int64)
    keys flow through a per-managed-table `ManagedVocab`. Unknown keys
    translate to the table's FALLBACK row (row 0 — the classic shared
    OOV bucket, exactly `IntegerLookup`'s index-0 contract) or, in
    ``on_miss='drop'`` mode, to zero-weight lanes. A decayed
    `HotnessTracker` counts the raw stream; a key whose recent
    frequency crosses `admit_threshold` is bound to a free physical row
    at the next `maintain()` — from then on it owns private capacity.
  * **Eviction.** When a table's occupancy crosses `high_watermark`,
    the coldest resident keys (by the same decayed counters) are
    demoted back to fallback: their embedding rows are stashed
    host-side, their bindings erased (`IntegerLookup.erase` — the slot
    returns to the free list). A re-admitted key restores its stashed
    row, so a key that oscillates around the threshold does not lose
    its training each cycle.
  * **Recompile-free growth.** The planner pre-reserves
    ``vocab_slack`` rows per managed table
    (`DistributedEmbedding(vocab_slack=)` / ``DET_VOCAB_SLACK``), so
    every admission fills pre-allocated ``[world, rows_max, width]``
    capacity: no array shape ever changes, the jitted train step and
    the serving forward compile exactly once per (plan, batch shape).
    Device writes (admitted-row init/restore, optimizer-row reset) go
    through the same pow2-padded cached row scatter the table store
    uses. At `replan_watermark` occupancy the manager LOGS a re-plan
    recommendation (more slack / bigger plan) — the one thing that
    genuinely needs a recompile is deliberately left to the operator.

Division of labor (one owner per piece of state):

  * binding (key -> physical row) + free slots: the erasable
    `IntegerLookup` — `state_dict` round-trips its key table and free
    list through checkpoints and the publish stream;
  * recent-frequency counters + admission candidates: the shared
    `HotnessTracker` (decay= mode), the same class training hot rows
    and the serving cache admit through;
  * the rows themselves: the layer's stacked params — the manager only
    ever touches them through gather/scatter at maintain time, so
    train/serve steps see ordinary arrays.

Translation is pure host-side numpy on the raw id stream (the same
place `IntegerLookup` already runs) and never enters jit.
"""

import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax

from distributed_embeddings_tpu.layers.embedding import IntegerLookup
from distributed_embeddings_tpu.ops import wire as wire_ops
from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds, SparseIds
# one implementation of the pow2-padded cached row scatter/gather
# (out-of-range world index drops) — shared with the table store so the
# per-shape retrace count AND the padded-index convention stay in one
# place across both subsystems
from distributed_embeddings_tpu.store.table_store import (
    padded_gather_rows, padded_scatter_rows)
from distributed_embeddings_tpu.utils.checkpoint import (load_row_delta,
                                                         save_row_delta)
from distributed_embeddings_tpu.utils.hotness import HotnessTracker

__all__ = ["ManagedVocab", "VocabManager", "default_admit_threshold",
           "latest_vocab_state", "vocab_state_path"]

_HOLE = np.iinfo(np.int64).min
# index-rebuild placeholder keys (load_state): astronomically outside any
# plausible raw-key space; erased immediately after replay
_DUMMY_BASE = -(2 ** 62)

_VOCAB_FILE_RE = re.compile(r"^vocab_v(\d{8})\.npz$")


def default_admit_threshold() -> int:
    """`DET_VOCAB_ADMIT` environment default for the admission threshold
    (recent decayed count at which an unknown key earns a private row).
    Default 2: one sighting is noise, a repeat is a signal — the same
    default the serving cache promotes at."""
    try:
        return max(1, int(os.environ.get("DET_VOCAB_ADMIT", "2")))
    except ValueError:
        return 2


def vocab_state_path(directory: str, version: int) -> str:
    """Binding-state sidecar path for one published store version."""
    return os.path.join(directory, f"vocab_v{version:08d}.npz")


def latest_vocab_state(directory: str,
                       upto: Optional[int] = None) -> Optional[str]:
    """Newest ``vocab_v{V}.npz`` sidecar in a publish directory with
    V <= `upto` (None = any) — the binding a consumer loads to match the
    row payloads it just applied."""
    if not os.path.isdir(directory):
        return None
    best = None
    for name in os.listdir(directory):
        m = _VOCAB_FILE_RE.match(name)
        if not m:
            continue
        v = int(m.group(1))
        if upto is not None and v > upto:
            continue
        if best is None or v > best[0]:
            best = (v, os.path.join(directory, name))
    return best[1] if best else None


class ManagedVocab:
    """Binding + admission state of ONE managed table.

    Rows: ``capacity`` physical rows (configured input_dim, which the
    planner already inflated by vocab_slack). Row 0 is the shared
    fallback/OOV row and is never bound; rows 1..capacity-1 are the
    bindable pool. The binding is an erasable `IntegerLookup` whose
    index space IS the row space.
    """

    def __init__(self, table_id: int, capacity: int, base_rows: int,
                 slack: int, admit_threshold: int, decay: float,
                 use_native: Optional[bool] = None,
                 stash_max: Optional[int] = None,
                 stash_dtype: Optional[str] = None,
                 stash_max_bytes: Optional[int] = None):
        if capacity < 2:
            raise ValueError(
                f"managed table {table_id}: capacity {capacity} leaves no "
                "bindable row beyond the fallback")
        self.table_id = int(table_id)
        self.capacity = int(capacity)
        self.base_rows = int(base_rows)
        self.slack = int(slack)
        self.binding = IntegerLookup(max_tokens=capacity - 1,
                                     use_native=use_native)
        self.tracker = HotnessTracker(
            capacity=capacity - 1, promote_threshold=admit_threshold,
            decay=decay)
        # host-side demotion storage: evicted keys' embedding rows
        # ([table_width] f32), restored verbatim on re-admission.
        # BOUNDED: under a genuinely drifting key universe most evicted
        # keys never return, so an uncapped stash (and therefore every
        # published sidecar, which carries it) would grow for the life
        # of the run. Insertion-ordered dict, oldest demotion dropped
        # first past `stash_max` (default: one table's worth of rows —
        # a key evicted longer ago than capacity-many later evictions
        # restarts from zero, the pre-stash semantics).
        self.stash: Dict[int, np.ndarray] = {}
        self.stash_max = (capacity - 1 if stash_max is None
                          else max(0, int(stash_max)))
        # quantized stash storage (ISSUE 15): evicted rows park at
        # `stash_dtype` (int8/fp8 payload + one f32 scale per row —
        # ~4x more evicted tenants resident per stash byte; re-admission
        # decodes, so the restore differs from the demoted row by at
        # most one quantization step). None defers to DET_STORE_DTYPE;
        # 'f32' keeps the exact pre-seam stash. `stash_max_bytes`
        # optionally bounds the stash in BYTES (oldest demotion drops
        # first, like the row cap) — the budget under which a quantized
        # stash holds ~4x more tenants.
        self.stash_dtype = wire_ops.resolve_store_dtype(
            wire_ops.default_store_dtype() if stash_dtype is None
            else stash_dtype)
        self.stash_max_bytes = (None if stash_max_bytes is None
                                else max(0, int(stash_max_bytes)))
        self._stash_bytes = 0
        # lifetime stats
        self.admissions = 0
        self.evictions = 0
        self.fallback_hits = 0
        self.translated = 0

    # ------------------------------------------------------------ queries
    @property
    def bound(self) -> int:
        """Live bound keys (excludes the fallback row)."""
        return self.binding.size - 1

    @property
    def occupancy(self) -> float:
        """bound / bindable — the watermark the eviction policy runs on."""
        return self.bound / max(self.capacity - 1, 1)

    def resident_keys(self) -> np.ndarray:
        """Bound raw keys ([n] int64, binding-index order)."""
        vocab = self.binding.get_vocabulary()[1:]
        return np.asarray([k for k in vocab if k is not None], np.int64)

    # ---------------------------------------------------------- translate
    def translate(self, keys: np.ndarray) -> np.ndarray:
        """Raw keys -> physical rows; unbound keys -> 0 (fallback row).
        Query-only: never binds, never counts."""
        rows = self.binding.lookup(keys)
        self.translated += int(np.asarray(keys).size)
        self.fallback_hits += int((np.asarray(rows) == 0).sum())
        return rows

    def observe(self, keys: np.ndarray,
                valid: Optional[np.ndarray] = None) -> None:
        """Feed the admission tracker (decayed recent-frequency counts)."""
        self.tracker.observe(keys, valid=valid)

    # ---------------------------------------------------- admission policy
    def pending_fresh(self) -> np.ndarray:
        """Unbound keys whose recent count crossed the admission
        threshold, hottest first ([n] int64) — the admission DEMAND the
        manager sizes eviction against. Stale pendings (keys that got
        bound since crossing) are dropped as a side effect."""
        cands = self.tracker.pending_candidates()
        if not cands:
            return np.empty((0,), np.int64)
        keys = np.asarray([k for _, k in cands], np.int64)
        bound_rows = np.asarray(self.binding.lookup(keys))
        self.tracker.drop_pending(keys[bound_rows != 0])
        return keys[bound_rows == 0]

    def bind(self, keys: Sequence[int]) -> np.ndarray:
        """Bind keys to rows (free-list reuse first). Returns the rows."""
        if not len(keys):
            return np.empty((0,), np.int64)
        arr = np.asarray(keys, np.int64)
        rows = np.asarray(self.binding(arr))
        ok = rows != 0
        self.tracker.drop_pending(arr[ok])
        self.admissions += int(ok.sum())
        return rows

    def plan_evictions(self, low_watermark: float) -> np.ndarray:
        """Coldest resident keys to demote so occupancy lands at
        `low_watermark` ([n] int64; empty when nothing to do)."""
        bindable = self.capacity - 1
        target = int(low_watermark * bindable)
        n_evict = self.bound - target
        if n_evict <= 0:
            return np.empty((0,), np.int64)
        keys = self.resident_keys()
        scores = self.tracker.counts_for(keys)
        order = np.argsort(scores, kind="stable")      # coldest first
        return keys[order[:n_evict]]

    # --------------------------------------------------- stash internals
    @staticmethod
    def _entry_bytes(entry) -> int:
        """Resident bytes of one stash entry: the 8-byte key + payload
        (+ the per-row scale for quantized entries)."""
        if isinstance(entry, tuple):
            return 8 + entry[0].nbytes + 4
        return 8 + entry.nbytes

    def _stash_put(self, key: int, row_f32: np.ndarray) -> None:
        """Insert one demoted row (f32 in, stored at `stash_dtype`) and
        keep both stash bounds: the row cap and the optional byte
        budget, oldest demotion first."""
        old = self.stash.pop(key, None)        # re-stash refreshes age
        if old is not None:
            self._stash_bytes -= self._entry_bytes(old)
        if self.stash_dtype == "f32":
            entry = np.asarray(row_f32, np.float32)
        else:
            p, s = wire_ops.encode_rows_np(
                np.asarray(row_f32, np.float32)[None], self.stash_dtype)
            entry = (p[0], np.float32(s[0, 0]))
        self.stash[key] = entry
        self._stash_bytes += self._entry_bytes(entry)
        while self.stash and (
                len(self.stash) > self.stash_max
                or (self.stash_max_bytes is not None
                    and self._stash_bytes > self.stash_max_bytes)):
            dropped = self.stash.pop(next(iter(self.stash)))
            self._stash_bytes -= self._entry_bytes(dropped)

    def stash_take(self, key: int) -> Optional[np.ndarray]:
        """Pop + decode one stashed row (f32), or None."""
        entry = self.stash.pop(int(key), None)
        if entry is None:
            return None
        self._stash_bytes -= self._entry_bytes(entry)
        if isinstance(entry, tuple):
            return wire_ops.decode_rows_np(
                entry[0], np.asarray(entry[1]).reshape(1),
                self.stash_dtype)
        return entry

    def stash_bytes(self) -> int:
        """Resident stash bytes (keys + payloads + scales) — the
        ``vocab/stash_bytes`` gauge's per-table term."""
        return self._stash_bytes

    def unbind(self, keys: np.ndarray,
               rows_payload: Optional[np.ndarray] = None) -> np.ndarray:
        """Erase bindings (eviction). `rows_payload` ([n, width]) is the
        keys' current embedding rows — stashed (at `stash_dtype`) for
        re-admission. Returns the freed row indices."""
        keys = np.asarray(keys, np.int64).reshape(-1)
        if rows_payload is not None:
            for i, k in enumerate(keys.tolist()):
                self._stash_put(k, rows_payload[i])
        freed = self.binding.erase(keys)
        self.evictions += int((np.asarray(freed) != 0).sum())
        return freed

    # -------------------------------------------------------------- state
    def state_dict(self, full: bool = True) -> Dict[str, np.ndarray]:
        """`full=False` keeps only the serving-critical binding (key
        table + free list): the tracker counters and the demotion stash
        are trainer-resume state and can be a table-sized payload — a
        consumer that only translates must not re-download them on
        every publish."""
        vocab = self.binding.get_vocabulary()[1:]   # index order, None holes
        keys = np.asarray([_HOLE if k is None else k for k in vocab],
                          np.int64)
        out = {"keys": keys,
               "free": np.asarray(self.binding.free_slots(), np.int64)}
        if full:
            ck, cv = self._tracker_items()
            stash_keys = np.asarray(sorted(self.stash), np.int64)
            if self.stash_dtype == "f32":
                stash_rows = (np.stack([self.stash[int(k)]
                                        for k in stash_keys])
                              if len(stash_keys)
                              else np.zeros((0, 0), np.float32))
                out.update({"count_keys": ck, "count_vals": cv,
                            "stash_keys": stash_keys,
                            "stash_rows": stash_rows})
            else:
                # quantized stash (ISSUE 15): checkpoint the payloads at
                # rest — a table-sized stash must not inflate 4x through
                # every save — with the per-row scales as a sibling
                entries = [self.stash[int(k)] for k in stash_keys]
                stash_rows = (np.stack([e[0] for e in entries])
                              if entries else np.zeros((0, 0), np.int8))
                stash_scale = np.asarray([e[1] for e in entries],
                                         np.float32)
                out.update({"count_keys": ck, "count_vals": cv,
                            "stash_keys": stash_keys,
                            "stash_rows": stash_rows,
                            "stash_scale": stash_scale})
        return out

    def _tracker_items(self) -> Tuple[np.ndarray, np.ndarray]:
        # stored counts are in lazily-decayed INFLATED units; persist
        # true units so a restore (fresh tracker, scale 1) is exact
        inv = 1.0 / self.tracker._scale
        items = sorted(self.tracker._counts.items())
        ck = np.asarray([k for k, _ in items], np.int64)
        cv = np.asarray([float(v) * inv for _, v in items], np.float64)
        return ck, cv

    def load_state(self, state: Dict[str, np.ndarray],
                   stash_dtype: str = "f32") -> None:
        """Rebuild binding/free-list/counters exactly from `state_dict`
        output. The index table is replayed in index order with
        placeholder keys in the holes; erasing the placeholders in the
        SAVED free-list order reproduces both the hole pattern and the
        LIFO reuse order bit-exactly."""
        keys = np.asarray(state["keys"], np.int64)
        free = np.asarray(state["free"], np.int64)
        fresh = IntegerLookup(max_tokens=self.capacity - 1,
                              use_native=self.binding.native)
        replay = keys.copy()
        holes = replay == _HOLE
        if holes.any():
            replay[holes] = _DUMMY_BASE - np.arange(len(replay))[holes]
        if len(replay):
            got = np.asarray(fresh(replay))
            expect = np.arange(1, len(replay) + 1)
            if not np.array_equal(got, expect):
                raise ValueError(
                    "vocab state replay produced non-sequential indices "
                    "(corrupt state file or raw keys colliding with the "
                    "reserved placeholder range)")
        if len(free):
            # each erase APPENDS its index to the free list, so erasing
            # the hole placeholders in saved order rebuilds the exact
            # list (and therefore the exact LIFO reuse order)
            dummies = _DUMMY_BASE - (free - 1)
            fresh.erase(dummies)
            rebuilt = np.asarray(fresh.free_slots())
            if not np.array_equal(rebuilt, free):
                raise ValueError("vocab free-list replay mismatch")
        self.binding = fresh
        self.tracker = HotnessTracker(
            capacity=self.capacity - 1,
            promote_threshold=self.tracker.promote_threshold,
            decay=self.tracker.decay)
        ck = np.asarray(state.get("count_keys", []), np.int64)
        cv = np.asarray(state.get("count_vals", []), np.float64)
        self.tracker._counts = {int(k): float(v) for k, v in zip(ck, cv)}
        if len(ck):
            # one vectorized probe for the whole counter set — a per-key
            # loop here would stall every consumer poll that loads a
            # sidecar at production counter counts
            unbound = np.asarray(fresh.lookup(ck)) == 0
            hot = cv >= self.tracker.promote_threshold
            self.tracker._pending = {int(k) for k in ck[unbound & hot]}
        self.stash = {}
        self._stash_bytes = 0
        sk = np.asarray(state.get("stash_keys", []), np.int64)
        sr = np.asarray(state.get("stash_rows", np.zeros((0, 0))))
        # saved entries decode at the SAVED stash dtype, then re-park at
        # this manager's configured dtype (legacy f32 files carry none)
        if wire_ops.resolve_store_dtype(stash_dtype) != "f32":
            sr = wire_ops.decode_rows_np(
                sr, np.asarray(state["stash_scale"],
                               np.float32)[:, None], stash_dtype)
        sr = np.asarray(sr, np.float32)
        for i, k in enumerate(sk.tolist()):
            self._stash_put(k, sr[i])

    def stats(self) -> dict:
        return {"capacity": self.capacity, "base_rows": self.base_rows,
                "slack_rows": self.slack, "bound": self.bound,
                "occupancy": round(self.occupancy, 4),
                "admissions": self.admissions, "evictions": self.evictions,
                "fallback_hits": self.fallback_hits,
                "translated": self.translated,
                "fallback_hit_rate": round(
                    self.fallback_hits / self.translated, 4)
                if self.translated else 0.0,
                "stashed": len(self.stash),
                "stash_bytes": self.stash_bytes(),
                "stash_dtype": self.stash_dtype}


class VocabManager:
    """Runtime vocabulary control for a `DistributedEmbedding`.

    Args:
      emb: the layer (dp-input mode). Managed tables are its
        table-parallel (group 1) tables whose placements are all
        device-resident; dp/row-sliced/offloaded tables pass through
        untranslated (their key spaces stay caller-managed).
      tables: optional explicit global-table-id subset to manage.
      admit_threshold: recent decayed count at which an unknown key is
        bound (None -> `DET_VOCAB_ADMIT`, default 2).
      decay: tracker aging factor per observed batch (default 0.99 —
        a key unseen for ~500 batches ages to noise); 1.0 = all-time
        counts (no drift tracking).
      high_watermark / low_watermark: occupancy that triggers eviction /
        the occupancy eviction drains down to.
      replan_watermark: occupancy at which `maintain` logs the re-plan
        recommendation (the capacity, not the policy, is the problem).
      on_miss: 'fallback' (default) routes unknown keys to row 0;
        'drop' zero-weights their lanes instead (translated inputs
        become (ids, weights) tuples — reducing-combiner inputs only).
      max_admit_per_cycle: bound on bindings per maintain() call
        (None = fill all free slots).
      use_native: force the native/numpy binding backend (tests).
      stash_max: per-table bound on the host-side demotion stash
        (None = one table's worth of rows); the oldest stashed demotion
        drops first, and a dropped key re-admits from zeros.
      stash_dtype: at-rest storage of stashed rows (ISSUE 15): 'f32'
        (exact, default via ``DET_STORE_DTYPE``) or 'int8'/'fp8'
        (per-row-scaled quantized payloads — ~4x more evicted tenants
        resident per stash byte; a re-admitted row restores within one
        quantization step of its demoted value).
      stash_max_bytes: optional per-table BYTE budget on the stash
        (keys + payloads + scales; oldest drops first) — the budget a
        quantized stash holds ~4x more tenants under.
      registry: optional `obs.MetricRegistry` (ISSUE 11) the manager's
        vocabulary metrics land in — ``vocab/admissions`` /
        ``vocab/evictions`` counters and the ``vocab/occupancy`` /
        ``vocab/high_watermark`` / ``vocab/low_watermark`` /
        ``vocab/fallback_hit_rate`` / ``vocab/bound_rows`` gauges
        (updated after every observing translate and every maintain
        cycle). Default: a private registry; `training.fit` rebinds via
        `use_registry`.

    Workflow::

        mgr = VocabManager(emb)
        cats = mgr.translate(raw_cats, observe=True)   # every step
        params, opt = mgr.maintain(params, opt)        # every N steps

    or hand both jobs to ``training.fit(vocab=mgr, vocab_every=N)``.
    """

    def __init__(self, emb, tables: Optional[Sequence[int]] = None,
                 admit_threshold: Optional[int] = None, decay: float = 0.99,
                 high_watermark: float = 0.9, low_watermark: float = 0.75,
                 replan_watermark: float = 0.98, on_miss: str = "fallback",
                 max_admit_per_cycle: Optional[int] = None,
                 use_native: Optional[bool] = None,
                 stash_max: Optional[int] = None,
                 stash_dtype: Optional[str] = None,
                 stash_max_bytes: Optional[int] = None, log_fn=None,
                 registry=None):
        if not emb.dp_input:
            raise ValueError(
                "VocabManager translates data-parallel input batches; this "
                "layer was built with dp_input=False")
        if jax.process_count() > 1:
            # per-process trackers/bindings would silently diverge the
            # SPMD programs' id streams (the TableStore producer's
            # failure mode, and worse: different ROWS per process) —
            # refuse loudly; translate on one controller (or broadcast
            # the binding) is the supported multi-process shape for now
            raise NotImplementedError(
                "VocabManager is single-controller: per-process bindings "
                "would diverge the SPMD id streams. Run admission on one "
                "controller and distribute translated rows (or the saved "
                "binding state) instead.")
        if on_miss not in ("fallback", "drop"):
            raise ValueError(f"on_miss must be 'fallback'|'drop', "
                             f"got {on_miss!r}")
        if not 0.0 < low_watermark <= high_watermark <= 1.0:
            raise ValueError(
                f"need 0 < low_watermark <= high_watermark <= 1, got "
                f"{low_watermark}/{high_watermark}")
        self.emb = emb
        self.on_miss = on_miss
        self.high_watermark = float(high_watermark)
        self.low_watermark = float(low_watermark)
        self.replan_watermark = float(replan_watermark)
        self.max_admit_per_cycle = max_admit_per_cycle
        self.admit_threshold = (default_admit_threshold()
                                if admit_threshold is None
                                else max(1, int(admit_threshold)))
        self._log = log_fn or (lambda msg: None)
        strat = emb.strategy
        eligible = self._eligible_tables()
        if tables is None:
            managed = eligible
        else:
            managed = [int(t) for t in tables]
            bad = [t for t in managed if t not in eligible]
            if bad:
                raise ValueError(
                    f"tables {bad} are not manageable (must be "
                    "table-parallel, non-offloaded, and not in a "
                    "hot-row-replicated bucket — hot write-back and "
                    "vocab rebind would fight over physical rows)")
        if not managed:
            raise ValueError(
                "no manageable tables in this plan (table-parallel, "
                "non-offloaded, hot-rows-free) — a VocabManager here "
                "would silently pass every input through untranslated")
        self.vocabs: Dict[int, ManagedVocab] = {}
        for gtid in managed:
            cfg = strat.global_configs[gtid]
            cap = int(cfg["input_dim"])
            self.vocabs[gtid] = ManagedVocab(
                gtid, capacity=cap,
                base_rows=int(cfg.get("vocab_base_rows", cap)),
                slack=int(cfg.get("vocab_slack", 0)),
                admit_threshold=self.admit_threshold,
                decay=decay, use_native=use_native, stash_max=stash_max,
                stash_dtype=stash_dtype, stash_max_bytes=stash_max_bytes)
        if on_miss == "drop":
            for gtid in self.vocabs:
                if strat.global_configs[gtid].get("combiner") is None:
                    raise ValueError(
                        f"on_miss='drop' zero-weights missed lanes, which "
                        f"needs a reducing combiner; managed table {gtid} "
                        "has combiner=None")
        # per-table placement geometry, precomputed for maintain()
        self._placements = {gtid: self._table_placements(gtid)
                            for gtid in self.vocabs}
        # admitted-slot flat keys per bucket since the last drain — the
        # rows maintain() rewrote, i.e. exactly what a weight-streaming
        # delta must republish (evictions rewrite nothing). Kept as
        # dedup'd sorted arrays merged at write time, so a
        # never-drained manager (no publisher attached) is bounded by
        # bucket capacity, not by run length.
        self._touched: Dict[Tuple[str, int], np.ndarray] = {}
        self.maintain_cycles = 0
        # observing translate() calls — one per training step in the fit
        # wiring, the honest "per step" denominator for eviction rates
        self.observe_steps = 0
        self._replan_warned: set = set()
        from distributed_embeddings_tpu.obs.registry import MetricRegistry
        self._metrics = registry if registry is not None \
            else MetricRegistry()
        # last cumulative totals already exported as counter increments
        self._exported = {"admissions": 0, "evictions": 0}

    def use_registry(self, registry) -> None:
        """Rebind metrics onto `registry` (ISSUE 11; the
        `TableStore.use_registry` idiom — `training.fit` unifies the
        run's namespace through this). Counter baselines carry over, so
        only admissions/evictions that happen AFTER the rebind land in
        the new registry."""
        self._metrics = registry

    def _export_metrics(self) -> None:
        """Refresh the registry view of the manager (cheap: O(tables)
        attribute sums — called per observing translate and per
        maintain cycle). Admissions/evictions export as counter DELTAS
        against the cumulative per-table totals; occupancy/fallback
        rate as gauges."""
        adm = sum(mv.admissions for mv in self.vocabs.values())
        ev = sum(mv.evictions for mv in self.vocabs.values())
        m = self._metrics
        m.counter("vocab/admissions").inc(adm - self._exported["admissions"])
        m.counter("vocab/evictions").inc(ev - self._exported["evictions"])
        self._exported = {"admissions": adm, "evictions": ev}
        cap = sum(mv.capacity - 1 for mv in self.vocabs.values())
        bound = sum(mv.bound for mv in self.vocabs.values())
        tr = sum(mv.translated for mv in self.vocabs.values())
        fb = sum(mv.fallback_hits for mv in self.vocabs.values())
        m.gauge("vocab/occupancy").set(bound / cap if cap else 0.0)
        m.gauge("vocab/bound_rows").set(bound)
        m.gauge("vocab/high_watermark").set(self.high_watermark)
        m.gauge("vocab/low_watermark").set(self.low_watermark)
        m.gauge("vocab/fallback_hit_rate").set(fb / tr if tr else 0.0)
        m.gauge("vocab/maintain_cycles").set(self.maintain_cycles)
        m.gauge("vocab/stash_bytes").set(
            sum(mv.stash_bytes() for mv in self.vocabs.values()))
        for gtid, mv in self.vocabs.items():
            m.gauge("vocab/occupancy", table=gtid).set(mv.occupancy)

    # ---------------------------------------------------------- geometry
    def _eligible_tables(self) -> List[int]:
        """Manageable = table-parallel, non-offloaded, and NOT in a
        hot-row-replicated bucket. The hot-bucket exclusion is a
        correctness gate, not a convenience: while a row is
        hot-resident the replicated hot shard is authoritative and the
        canonical row is stale — eviction would stash the stale copy,
        and a rebind of the freed physical row would be overwritten by
        the OLD tenant's hot row at the next `sync_hot_rows` write-back
        (hot membership is keyed by flat physical row). Until the two
        policies coordinate, a table is managed by at most one of
        them."""
        strat = self.emb.strategy
        out = []
        for t_local, gtid in enumerate(strat.table_groups[1]):
            pls = [pl for pl in self.emb.plan.tp_placements
                   if pl.table_id == t_local]
            if pls and not any(
                    self.emb.plan.tp_buckets[pl.bucket].offload
                    or self.emb.plan.tp_buckets[pl.bucket].hot_rows > 0
                    for pl in pls):
                out.append(gtid)
        return out

    def _table_placements(self, gtid: int):
        t_local = self.emb.strategy.table_groups[1].index(gtid)
        return sorted((pl for pl in self.emb.plan.tp_placements
                       if pl.table_id == t_local),
                      key=lambda pl: pl.col_start)

    # --------------------------------------------------------- translate
    def _managed_for_input(self, i: int) -> Optional[ManagedVocab]:
        return self.vocabs.get(self.emb.strategy.input_table_map[i])

    @staticmethod
    def _host_ids(x) -> np.ndarray:
        return np.asarray(jax.device_get(x)).astype(np.int64)

    def _translate_one(self, mv: ManagedVocab, x, raws_out=None):
        """One input through its table's binding, preserving form.
        `raws_out`: optional list collecting the raw flat keys (the
        caller observes them per TABLE, not per input — see translate)."""
        if isinstance(x, RaggedIds):
            vals = self._host_ids(x.values)
            if raws_out is not None:
                raws_out.append(vals.reshape(-1))
            rows = mv.translate(vals)
            if self.on_miss == "drop":
                raise ValueError(
                    "on_miss='drop' cannot synthesize weights for "
                    "RaggedIds inputs; use dense [B, k] (+weights) forms")
            return RaggedIds(rows.astype(np.int32), x.row_splits)
        if isinstance(x, SparseIds):
            vals = self._host_ids(x.values)
            if raws_out is not None:
                raws_out.append(vals.reshape(-1))
            rows = mv.translate(vals)
            if self.on_miss == "drop":
                raise ValueError(
                    "on_miss='drop' cannot zero-weight SparseIds values; "
                    "use dense [B, k] (+weights) forms")
            return SparseIds(x.indices, rows.astype(np.int32),
                             x.dense_shape)
        weights = None
        if isinstance(x, tuple) and len(x) == 2:
            x, weights = x
        ids = self._host_ids(x)
        orig_dtype = np.asarray(x).dtype
        if not np.issubdtype(orig_dtype, np.integer):
            orig_dtype = np.int32
        if raws_out is not None:
            raws_out.append(ids.reshape(-1))
        rows = mv.translate(ids).astype(orig_dtype)
        if self.on_miss == "drop":
            miss = rows == 0
            w = (np.ones(ids.shape, np.float32) if weights is None
                 else np.asarray(jax.device_get(weights),
                                 np.float32).copy())
            w[miss] = 0.0
            return (rows, w)
        return (rows, weights) if weights is not None else rows

    def translate(self, inputs: Sequence, observe: bool = False) -> List:
        """Translate one batch's raw keys to physical rows (host-side).
        Unmanaged inputs pass through untouched. `observe=True`
        additionally feeds the admission tracker — the training side's
        form; serving translates query-only. Observation is aggregated
        PER TABLE: a table shared by k inputs (input_table_map) gets one
        decay tick per batch over the union stream, not k ticks — the
        aging window is a property of the table, not of how many inputs
        feed it."""
        if len(inputs) != self.emb._n_inputs:
            raise ValueError(
                f"expected {self.emb._n_inputs} inputs, got {len(inputs)}")
        if observe:
            self.observe_steps += 1
        per_table_raws: Dict[int, List[np.ndarray]] = {}
        out = []
        for i, x in enumerate(inputs):
            mv = self._managed_for_input(i)
            if mv is None:
                out.append(x)
                continue
            raws = (per_table_raws.setdefault(mv.table_id, [])
                    if observe else None)
            out.append(self._translate_one(mv, x, raws_out=raws))
        for gtid, chunks in per_table_raws.items():
            self.vocabs[gtid].observe(np.concatenate(chunks))
        if observe:
            # training-side translate = one step: refresh the registry
            # view (fallback-hit rate moves per batch, not per cycle)
            self._export_metrics()
        return out

    # ---------------------------------------------------------- maintain
    def _flat_keys(self, gtid: int, rows: np.ndarray):
        """Physical rows of table `gtid` -> per-bucket (flat keys, col
        ranges): one entry per placement (column slices live on
        different ranks; every slice stores the row)."""
        out = []
        for pl in self._placements[gtid]:
            rows_max = max(self.emb.plan.tp_buckets[pl.bucket].rows_max, 1)
            flat = pl.rank * rows_max + pl.row_offset + rows
            out.append((pl.bucket, flat, pl.col_start, pl.col_end))
        return out

    def _gather_table_rows(self, params: dict, gtid: int,
                           rows: np.ndarray) -> np.ndarray:
        """Current [n, table_width] rows assembled across placements."""
        width = sum(pl.col_end - pl.col_start
                    for pl in self._placements[gtid])
        out = np.zeros((len(rows), width), np.float32)
        for bucket, flat, c0, c1 in self._flat_keys(gtid, rows):
            arr = params["tp"][bucket]
            rows_max = max(self.emb.plan.tp_buckets[bucket].rows_max, 1)
            out[:, c0:c1] = padded_gather_rows(arr, flat // rows_max,
                                               flat % rows_max)
        return out

    def _scatter_bucket(self, arr, flat: np.ndarray, rows_max: int,
                        payload: np.ndarray):
        """Row scatter into one stacked leaf via the store's shared
        pow2-padded kernel (pad lanes drop)."""
        return padded_scatter_rows(arr, flat // rows_max,
                                   flat % rows_max, payload)

    def _write_admitted(self, params: dict, opt_states: Optional[dict],
                        gtid: int, keys: np.ndarray, rows: np.ndarray):
        """Write admitted keys' rows: stashed payload (re-admission) or
        zeros (fresh key), and ZERO the optimizer-state rows of the slot
        — a reused slot must not leak its previous tenant's momentum or
        accumulator."""
        mv = self.vocabs[gtid]
        width = sum(pl.col_end - pl.col_start
                    for pl in self._placements[gtid])
        payload = np.zeros((len(keys), width), np.float32)
        for i, k in enumerate(keys.tolist()):
            stashed = mv.stash_take(k)     # decoded f32 (ISSUE 15)
            if stashed is not None:
                payload[i] = stashed
        new_tp = list(params["tp"])
        new_opt = (None if opt_states is None
                   else {**opt_states, "tp": list(opt_states["tp"])})
        for bucket, flat, c0, c1 in self._flat_keys(gtid, rows):
            rows_max = max(self.emb.plan.tp_buckets[bucket].rows_max, 1)
            new_tp[bucket] = self._scatter_bucket(
                new_tp[bucket], flat, rows_max, payload[:, c0:c1])
            cur = self._touched.get(("tp", bucket))
            self._touched[("tp", bucket)] = (
                np.union1d(cur, flat) if cur is not None
                else np.unique(flat))
            if new_opt is not None:
                shape = tuple(new_tp[bucket].shape[:2])

                def reset_rows(leaf, flat=flat, rows_max=rows_max,
                               shape=shape):
                    if (getattr(leaf, "ndim", 0) >= 2
                            and tuple(leaf.shape[:2]) == shape):
                        zeros = np.zeros(
                            (len(flat),) + tuple(leaf.shape[2:]), np.float32)
                        return self._scatter_bucket(leaf, flat, rows_max,
                                                    zeros)
                    return leaf

                new_opt["tp"][bucket] = jax.tree.map(
                    reset_rows, new_opt["tp"][bucket])
        params = {**params, "tp": new_tp}
        return params, (opt_states if new_opt is None else new_opt)

    def maintain(self, params: dict, opt_states: Optional[dict] = None):
        """Run one admission/eviction cycle against the owned tables.

        Policy (per table): admissions stop at the HIGH watermark, so
        steady-state occupancy never exceeds it; when admission DEMAND
        (pending threshold-crossers) does not fit under that line, the
        coldest residents drain to the LOW watermark first — pressure,
        not occupancy alone, drives eviction, so a stable key universe
        never churns and a drifting one turns over exactly the cold
        tail. When even a full drain cannot absorb the demand, the
        manager logs the re-plan recommendation (more `vocab_slack`):
        capacity, not policy, is the bottleneck.

        Order is load-bearing within a table: evicted rows are gathered
        into the stash BEFORE new keys bind (a freed slot may be
        rebound in the same cycle — the old tenant's row must be
        captured before the new tenant's write). Returns
        (params, opt_states) with touched leaves replaced — same
        shapes/shardings, nothing recompiles.
        """
        self.maintain_cycles += 1
        for gtid, mv in self.vocabs.items():
            bindable = mv.capacity - 1
            cap_rows = int(self.high_watermark * bindable)
            fresh = mv.pending_fresh()
            if len(fresh) > cap_rows - mv.bound:
                # admission pressure beyond the watermark: drain the
                # cold tail first
                evict_keys = mv.plan_evictions(self.low_watermark)
                if len(evict_keys):
                    rows = np.asarray(mv.binding.lookup(evict_keys))
                    payload = self._gather_table_rows(params, gtid, rows)
                    mv.unbind(evict_keys, payload)
            free = cap_rows - mv.bound
            if len(fresh) > max(free, 0) and gtid not in \
                    self._replan_warned:
                self._replan_warned.add(gtid)
                msg = (f"vocab: table {gtid} admission demand "
                       f"({len(fresh)} keys) exceeds post-eviction "
                       f"capacity ({max(free, 0)} free rows under the "
                       f"{self.high_watermark} watermark): re-plan with "
                       "a larger vocab_slack (DET_VOCAB_SLACK) at the "
                       "next restart")
                self._log(msg)
                import warnings
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
            if self.max_admit_per_cycle is not None:
                free = min(free, self.max_admit_per_cycle)
            if free <= 0 or not len(fresh):
                continue
            keys = fresh[:free]
            rows = mv.bind(keys)
            ok = rows != 0
            if ok.any():
                params, opt_states = self._write_admitted(
                    params, opt_states, gtid, keys[ok], rows[ok])
        self._export_metrics()
        return params, opt_states

    @property
    def pending_publication(self) -> bool:
        """True when maintain() rewrote rows that no publication has
        carried yet (fit uses this to force a tail publish — a consumer
        must never miss a rebind's row init)."""
        return any(len(v) for v in self._touched.values())

    def drain_touched(self) -> Dict[Tuple[str, int], np.ndarray]:
        """Flat row keys maintain() rewrote since the last drain, per tp
        bucket — merge into `TableStore.commit(touched=...)` so the next
        published delta republishes rebound rows."""
        out = {k: v for k, v in self._touched.items() if len(v)}
        self._touched = {}
        return out

    # -------------------------------------------------------------- state
    def state_dict(self, full: bool = True
                   ) -> Tuple[dict, Dict[str, np.ndarray]]:
        meta = {"kind": "vocab_state",
                "tables": sorted(self.vocabs),
                "admit_threshold": self.admit_threshold,
                "decay": (self.vocabs[min(self.vocabs)].tracker.decay
                          if self.vocabs else None),
                # stash payload encoding of THIS save (ISSUE 15) — a
                # loader decodes with it, then re-parks at its own
                # configured dtype; legacy files carry none (= f32)
                "stash_dtype": (self.vocabs[min(self.vocabs)].stash_dtype
                                if self.vocabs else "f32"),
                "capacity": {str(t): mv.capacity
                             for t, mv in self.vocabs.items()}}
        arrays = {}
        for gtid, mv in self.vocabs.items():
            for name, arr in mv.state_dict(full=full).items():
                arrays[f"t{gtid}_{name}"] = arr
        return meta, arrays

    def save_state(self, path: str, full: bool = True) -> str:
        """Write the binding state as one npz. `full=True` (checkpoint
        form) carries everything a trainer resume needs: key table,
        free list, decayed counters, demotion stash. `full=False`
        (the publish sidecar form `fit` writes) carries only what a
        translating consumer needs — key table + free list + policy
        header — so per-publish sidecar bytes scale with the BINDING,
        not with a table-sized stash.

        The write is crash-durable like `TableStore.publish` (ISSUE 13):
        fsync file + directory around the atomic rename, and the
        ``vocab.save_state`` fault point can corrupt the payload or
        crash before the rename (consumers verify the container
        checksums on load and keep serving the previous binding)."""
        from distributed_embeddings_tpu import faults
        from distributed_embeddings_tpu.utils.checkpoint import (
            publish_atomic)
        meta, arrays = self.state_dict(full=full)
        final = path if path.endswith(".npz") else path + ".npz"
        spec = faults.check("vocab.save_state", path=final)
        tmp = save_row_delta(path + ".tmp", meta, arrays)
        if spec is not None and spec.kind in faults.CORRUPTING_KINDS:
            faults.corrupt_file(tmp, spec)
        if spec is not None and spec.kind == "crash_before_rename":
            raise faults.InjectedCrash(
                f"save_state {final}: injected crash before rename "
                f"(orphaned {os.path.basename(tmp)})")
        return publish_atomic(tmp, final)

    def load_state(self, path: str) -> None:
        """Restore the full saved state — including the ADMISSION POLICY
        (threshold + decay): a restored manager must resume the saved
        run's behavior, not whatever this instance was constructed with
        (a policy mismatch would silently change which keys admit and
        how fast counters age after every checkpoint restore)."""
        meta, arrays = load_row_delta(path)
        if meta.get("kind") != "vocab_state":
            raise ValueError(f"{path}: not a vocab state file")
        if "admit_threshold" in meta:
            self.admit_threshold = int(meta["admit_threshold"])
        saved_decay = meta.get("decay")
        for gtid, mv in self.vocabs.items():
            # mv.load_state rebuilds the tracker from these fields
            mv.tracker.promote_threshold = self.admit_threshold
            if "decay" in meta:
                mv.tracker.decay = (None if saved_decay is None
                                    else float(saved_decay))
            cap = int(meta.get("capacity", {}).get(str(gtid), mv.capacity))
            if cap != mv.capacity:
                raise ValueError(
                    f"{path}: table {gtid} capacity {cap} != plan "
                    f"capacity {mv.capacity} (different vocab_slack?)")
            prefix = f"t{gtid}_"
            state = {name[len(prefix):]: arr
                     for name, arr in arrays.items()
                     if name.startswith(prefix)}
            if state:
                mv.load_state(state,
                              stash_dtype=meta.get("stash_dtype", "f32"))

    # -------------------------------------------------------------- stats
    def occupancy(self) -> Dict[int, float]:
        return {t: mv.occupancy for t, mv in self.vocabs.items()}

    def stats(self) -> dict:
        per = {t: mv.stats() for t, mv in self.vocabs.items()}
        tot_cap = sum(mv.capacity - 1 for mv in self.vocabs.values())
        tot_bound = sum(mv.bound for mv in self.vocabs.values())
        tot_tr = sum(mv.translated for mv in self.vocabs.values())
        tot_fb = sum(mv.fallback_hits for mv in self.vocabs.values())
        return {
            "tables": per,
            "occupancy": round(tot_bound / tot_cap, 4) if tot_cap else 0.0,
            "bound": tot_bound,
            "admissions": sum(mv.admissions for mv in self.vocabs.values()),
            "evictions": sum(mv.evictions for mv in self.vocabs.values()),
            "fallback_hit_rate": round(tot_fb / tot_tr, 4) if tot_tr
            else 0.0,
            "maintain_cycles": self.maintain_cycles,
        }
