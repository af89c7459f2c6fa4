"""TableStore: one versioned owner for a DistributedEmbedding's tables.

Before this subsystem, training (`layers/dist_model_parallel.py` + the
hot-row shard) and serving (`serving/engine.py` / `serving/cache.py`)
each held their own copy of table, optimizer and hot-row state,
reconciled only by whole-table `refresh()` / `sync_hot_rows()` steps —
there was no way to push an updated table into a running
`InferenceEngine` short of a restart or a full-table copy. `TableStore`
is the parameter-server-style answer:

  * **One source of truth.** The store owns the layer's params pytree
    (per-bucket fused tables, row-sliced tables, dp tables, hot
    membership) and optimizer state behind one interface. Its
    `read_rows` is THE versioned read — canonical table rows with the
    AUTHORITATIVE hot-resident rows overlaid, via the same
    `DistributedEmbedding.hot_resident_rows` helper `get_weights` uses,
    so a stale overlay (the old two-path failure, where serving and
    checkpointing re-derived resident rows independently) is
    structurally impossible.
  * **Monotonic versions.** Every `commit`/`replace`/`sync_hot_rows`
    bumps the store version; per-original-table versions record the
    last commit that touched each table (`table_versions`).
  * **Row-delta publication.** The training side accumulates the
    sparse update's touched-row sets host-side (`observe`, mirroring
    `DistributedEmbedding.touched_row_keys` — the same dedup'd
    post-sentinel-mask id stream PR 2's `canonical_id_sort`/`dedup_sum`
    consume on device) and `publish`es them as row-delta files: dedup'd
    touched keys + MERGED row payloads + a version header
    (`utils/checkpoint.save_row_delta`). The first publish — and every
    `snapshot_every`-th after — is a full-snapshot compaction so a
    fresh replica (or one that fell off the delta chain) can resync.
  * **In-place consumption.** A consumer-side store applies deltas
    without recompiling or copying full tables: HBM buckets via a
    cached jitted row scatter, host-offloaded buckets via the existing
    `host_apply_rows_inplace` seam (`kind="set"`), dp tables by
    replicated replacement (they train dense — every row may move, and
    they are small by construction, so each delta carries them whole).
    `DeltaConsumer` drives a directory poll loop with
    staleness-vs-publish accounting (version lag + seconds).

Payload semantics (load-bearing): delta rows are the MERGED view
(`read_rows`), so a consumer's canonical tables reproduce the
publisher's `get_weights` output bit-exactly at every consumed version
— whether or not the publisher had hot-resident rows at the time. A
consumer with a NON-EMPTY hot set of its own would shadow those writes,
so delta application refuses it (serving replicas are hot-less by
construction; call `sync_hot_rows` + re-admit after a snapshot if you
must consume into a training layer).

Multi-process note: the producer side (`observe`/`publish`/`read_rows`)
is SINGLE-CONTROLLER for now and raises under multi-process meshes —
touched-row observation and row reads see only this process's
addressable shards, so a multi-process publish would silently drop rows
touched or stored on other processes (the one failure mode the delta
contract cannot tolerate). Gather to one controller first (e.g. publish
from a `get_weights` snapshot), or run the publisher single-process;
consumer-side `apply_published` must be called collectively (every
process, same file) like any other SPMD param update.
"""

import os
import re
import time
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu import compat, faults
from distributed_embeddings_tpu.obs.trace import default_recorder
from distributed_embeddings_tpu.ops import sparse_update as sparse_update_ops
from distributed_embeddings_tpu.ops import wire as wire_ops
from distributed_embeddings_tpu.utils import checkpoint as ckpt_lib

__all__ = ["DeltaChainError", "DeltaConsumer", "TableStore",
           "padded_gather_rows", "padded_scatter_rows",
           "restore_from_published", "scan_published"]


# ------------------------------------------------- failure classification
# (ISSUE 13) Two failure classes a consumer must tell apart:
#   * TRANSIENT — the read may succeed if retried (filesystem flake,
#     injected `InjectedIOError`): capped-exponential-backoff retry, give
#     the file up for THIS poll if retries exhaust (the next poll tries
#     again — serving latency must not absorb unbounded sleeps).
#   * CORRUPT — the file's bytes are wrong and, streams being immutable
#     once renamed into place, will stay wrong forever: quarantine (skip
#     permanently + `store/corrupt_files_total` + one loud warning) and
#     let the chain re-anchor on the next snapshot. The load layer
#     (`checkpoint.load_row_delta*`) funnels every parse-level failure
#     — bad zip, member CRC, torn payload, unparseable header — into
#     `StreamIntegrityError`, so corruption is exactly ONE type here.
# Anything else is a programming/config error and propagates (a
# shape-signature mismatch or a hot-resident guard must fail loudly,
# not quarantine a healthy stream; the serving engine's `poll_updates`
# still converts it to degraded mode).
def _is_transient_error(e: BaseException) -> bool:
    return isinstance(e, OSError)


def _is_corrupt_error(e: BaseException) -> bool:
    return isinstance(e, ckpt_lib.StreamIntegrityError)


class DeltaChainError(RuntimeError):
    """A delta's base_version does not match the consumer's version —
    the consumer fell off the publish chain (missed/compacted file) and
    must resync from a snapshot."""


# cached jitted row scatter/gather over stacked [world, rows, w] params:
# out-of-range w_idx (the pad sentinel == world) drops, so delta batches
# pad to power-of-2 sizes and the per-shape retrace count stays bounded.
@jax.jit
def _scatter_rows(stack, w_idx, r_idx, rows):
    return stack.at[w_idx, r_idx].set(rows.astype(stack.dtype), mode="drop")


@jax.jit
def _gather_rows(stack, w_idx, r_idx):
    return stack[w_idx, r_idx]


def _next_pow2(n: int) -> int:
    return 1 << max(int(max(n, 1) - 1).bit_length(), 0)


def padded_gather_rows(arr, w_idx: np.ndarray,
                       r_idx: np.ndarray) -> np.ndarray:
    """Rows of a stacked [world, rows, w] param at (w_idx, r_idx), via
    the cached jitted gather over pow2-padded (clipped) indices — the
    ONE padded-index preparation both the store and the vocab manager
    batch row reads through (the per-shape retrace count stays bounded
    across both subsystems)."""
    n = len(w_idx)
    m = _next_pow2(n)
    wp = np.zeros((m,), np.int64)
    rp = np.zeros((m,), np.int64)
    wp[:n] = np.clip(w_idx, 0, arr.shape[0] - 1)
    rp[:n] = np.clip(r_idx, 0, arr.shape[1] - 1)
    return np.asarray(_gather_rows(arr, jnp.asarray(wp),
                                   jnp.asarray(rp)))[:n]


def padded_scatter_rows(arr, w_idx: np.ndarray, r_idx: np.ndarray,
                        rows: np.ndarray):
    """Set rows of a stacked param at (w_idx, r_idx) via the cached
    jitted scatter; pow2-pad lanes carry an out-of-range world index
    and drop. Shared by delta apply and vocab admission writes."""
    n = len(w_idx)
    m = _next_pow2(n)
    wp = np.full((m,), arr.shape[0], np.int64)     # OOB pad -> dropped
    rp = np.zeros((m,), np.int64)
    vp = np.zeros((m,) + tuple(rows.shape[1:]), np.float32)
    wp[:n], rp[:n], vp[:n] = w_idx, r_idx, rows
    return _scatter_rows(arr, jnp.asarray(wp), jnp.asarray(rp),
                         jnp.asarray(vp))


def _np_rows_from_shards(arr, w_idx: np.ndarray,
                         r_idx: np.ndarray) -> np.ndarray:
    """Row gather from a (host-resident) stacked array via its
    addressable shards — no XLA program touches the host placement.
    Output is f32 VALUES regardless of the stored dtype (int8/fp8
    payloads cast losslessly; the caller multiplies in the per-row
    scale for quantized buckets)."""
    out = np.zeros((len(w_idx), arr.shape[-1]), np.float32)
    for sh in arr.addressable_shards:
        start = sh.index[0].start or 0
        data = np.asarray(sh.data)
        for j in range(data.shape[0]):
            m = w_idx == start + j
            if m.any():
                out[m] = data[j][r_idx[m]]
    return out


def _host_set_rows(table_h, w_idx: np.ndarray, r_idx: np.ndarray,
                   rows: np.ndarray):
    """Set rows of a pinned-host stacked bucket in place, shard by shard,
    through the `host_apply_rows_inplace` seam (kind='set') — the same
    XLA-free path the offloaded sparse apply uses, so only the delta rows
    ever cross a memory boundary."""
    if not len(w_idx):
        return table_h
    new_shards = []
    for sh in table_h.addressable_shards:
        start = sh.index[0].start or 0
        stop = start + sh.data.shape[0]
        hit = (w_idx >= start) & (w_idx < stop)
        if not hit.any():
            # untouched shard: pass the existing buffer through — the
            # rows-only-traffic contract (no full-shard copy/restage for
            # world slices the delta never reaches)
            new_shards.append(sh.data)
            continue
        t_np = np.array(sh.data)               # host->host copy, mutable
        for j in range(t_np.shape[0]):
            m = w_idx == start + j
            if m.any():
                n = int(m.sum())
                if t_np.dtype == np.float32:
                    sparse_update_ops.host_apply_rows_inplace(
                        "set", t_np[j], (),
                        np.ascontiguousarray(r_idx[m], np.int32),
                        np.ascontiguousarray(rows[m], np.float32),
                        np.ones((n,), np.float32), 0.0)
                else:
                    # quantized payload/scale leaves (ISSUE 15): the
                    # C++ row kernels are f32-only; a plain fancy-index
                    # set is the same rows-only write at these dtypes
                    t_np[j][r_idx[m]] = np.asarray(rows[m], t_np.dtype)
        new_shards.append(jax.device_put(t_np, sh.data.sharding))
    return compat.assemble_like(table_h, new_shards)


_FILE_RE = re.compile(r"^stream_v(\d{8})_(delta|snapshot)\.npz$")


def _publish_path(directory: str, version: int, kind: str) -> str:
    return os.path.join(directory, f"stream_v{version:08d}_{kind}.npz")


def scan_published(directory: str) -> List[Tuple[int, str, str]]:
    """Sorted [(version, kind, path)] of the publish stream in a
    directory (the delta log a consumer polls). The ``store.scan``
    fault point filters the result (delayed-visibility injection: a
    lagging directory view hides fresh files for N scans)."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = _FILE_RE.match(name)
        if m:
            out.append((int(m.group(1)), m.group(2),
                        os.path.join(directory, name)))
    return faults.filter_scan("store.scan", sorted(out))


class TableStore:
    """Versioned owner of one `DistributedEmbedding`'s parameter state.

    Args:
      emb: the `DistributedEmbedding` whose plan keys everything.
      params: the layer params pytree ({'dp', 'tp', 'row'[, 'hot']}).
      opt_states: optional sparse-optimizer state pytree (training side).
      snapshot_every: every N-th publish is a full-snapshot compaction
        (0/None = only the mandatory first publish; env default
        `DET_STORE_SNAPSHOT_EVERY`).
      delta_dtype: payload dtype of published stream files (ISSUE 15):
        'f32' (default — byte-identical files to the pre-seam
        container), 'int8' or 'fp8' (per-row-scaled quantized row
        payloads, ~4x smaller; the container header carries the dtype
        and consumers decode on apply). None defers to
        ``DET_DELTA_DTYPE``. Keys stay int64 and dp tables stay f32
        (dense-trained and small by construction). Applies to what THIS
        store publishes; consuming is driven by each file's header.
      registry: optional `obs.MetricRegistry` (ISSUE 11) the store's
        streaming metrics land in — producer counters
        (``store/publishes``, ``store/publish_bytes``,
        ``store/publish_rows``), consumer counters (``store/applies``,
        ``store/apply_bytes``, ``store/apply_rows``) and the
        ``store/version{role=publisher|consumer}`` gauges;
        `DeltaConsumer` adds the staleness
        family (``store/version_lag``,
        ``store/publish_to_apply_seconds``). Default: a private
        registry; `training.fit` rebinds its publisher store onto the
        run registry via `use_registry`.
    """

    def __init__(self, emb, params: dict, opt_states: Optional[dict] = None,
                 snapshot_every: Optional[int] = None, registry=None,
                 delta_dtype: Optional[str] = None):
        from distributed_embeddings_tpu.obs.registry import MetricRegistry
        self._metrics = registry if registry is not None \
            else MetricRegistry()
        self.emb = emb
        self._params = params
        self._opt = opt_states
        if snapshot_every is None:
            snapshot_every = int(os.environ.get(
                "DET_STORE_SNAPSHOT_EVERY", "0"))
        self.snapshot_every = int(snapshot_every)
        self.delta_dtype = (wire_ops.default_delta_dtype()
                            if delta_dtype is None
                            else wire_ops.resolve_store_dtype(delta_dtype))
        # cumulative published bytes per payload dtype -> the
        # ``store/bytes{dtype=}`` gauge (docs/observability.md)
        self._published_bytes_by_dtype: Dict[str, int] = {}
        self.version = 0
        strat = emb.strategy
        self._n_tables = len(strat.global_configs)
        self.table_versions = [0] * self._n_tables
        # plan signature: consumers refuse a stream published for a
        # different model (shape mismatch would otherwise scatter-drop
        # or corrupt silently)
        self._sig = [(int(c["input_dim"]), int(c["output_dim"]))
                     for c in strat.global_configs]
        # kind/index -> original table ids (version bookkeeping)
        self._bucket_tables: Dict[int, List[int]] = {}
        for pl in emb.plan.tp_placements:
            gtid = strat.table_groups[1][pl.table_id]
            self._bucket_tables.setdefault(pl.bucket, [])
            if gtid not in self._bucket_tables[pl.bucket]:
                self._bucket_tables[pl.bucket].append(gtid)
        self._row_tables = list(strat.table_groups[2])
        self._dp_tables = list(strat.table_groups[0])
        # producer-side accumulation: touched flat keys since last
        # publish, and the kinds touched since the last commit (drives
        # per-table version bumps)
        self._pending: Dict[Tuple[str, int], np.ndarray] = {}
        self._since_commit: set = set()
        self._publishes = 0
        # version of the last publish (None = never published: the next
        # publish is forced to a snapshot so consumers have an anchor)
        self._published_version: Optional[int] = None
        # consumer-side chain marker: True after an out-of-band swap
        # (`replace`/`set_weights`) until the next SNAPSHOT apply. The
        # version counter alone cannot carry this — a local bump lands
        # in the same integer namespace as the publisher's versions, so
        # one publish later a delta's base_version could alias the
        # replaced state and chain onto unrelated tables silently.
        self._chain_broken = False
        # directories whose orphaned tmp files this publisher already
        # swept (once per directory per store — publisher startup)
        self._swept_dirs: set = set()

    # ------------------------------------------------------------- state
    def use_registry(self, registry) -> None:
        """Rebind the store's metrics onto `registry` (ISSUE 11) —
        `training.fit` calls this so a run's publisher reports into the
        ONE run registry. Counts accumulated in the previous registry
        stay there (instruments are resolved per event, not cached)."""
        self._metrics = registry

    @property
    def params(self) -> dict:
        return self._params

    @property
    def opt_states(self) -> Optional[dict]:
        return self._opt

    def full_table_bytes(self) -> int:
        """Bytes of one full portable copy of every table (f32) — the
        denominator of the delta-vs-full-copy accounting."""
        return sum(v * w * 4 for v, w in self._sig)

    @staticmethod
    def _require_single_controller(what: str) -> None:
        """The producer-side reads are process-local (addressable shards
        only): under multi-process they would silently DROP rows touched
        or stored on other processes — the one failure a SET-payload
        delta cannot tolerate — so they refuse loudly instead."""
        if jax.process_count() > 1:
            raise NotImplementedError(
                f"TableStore.{what} is single-controller: it reads only "
                "this process's batch/table shards, so a multi-process "
                "publish would silently drop other processes' rows. "
                "Publish from one controller over gathered state, or run "
                "the training publisher single-process.")

    # ------------------------------------------------- producer: touched
    def observe(self, inputs) -> None:
        """Accumulate the touched-row sets of one training batch
        (host-side numpy; the same per-bucket flat keys the sparse
        update writes — see `DistributedEmbedding.touched_row_keys`).
        Call once per step on the SAME inputs `apply` sees; the union
        since the last publish becomes the next delta's key set."""
        self._require_single_controller("observe")
        touched = self.emb.touched_row_keys(inputs)
        self._merge_touched(touched)

    def _merge_touched(self, touched: Dict[Tuple[str, int], np.ndarray]):
        for key, keys in touched.items():
            keys = np.asarray(keys, np.int64).reshape(-1)
            if not len(keys):
                continue
            cur = self._pending.get(key)
            self._pending[key] = (np.union1d(cur, keys)
                                  if cur is not None else np.unique(keys))
            self._since_commit.add(key)

    def commit(self, params: dict, opt_states: Optional[dict] = None,
               touched: Optional[Dict[Tuple[str, int], np.ndarray]] = None
               ) -> int:
        """Swap in the post-step pytrees and bump the store version.
        `touched` optionally merges extra touched keys (same shape as
        `touched_row_keys` output) for callers that track them
        elsewhere. Returns the new version."""
        if touched:
            self._merge_touched(touched)
        self._params = params
        if opt_states is not None:
            self._opt = opt_states
        self.version += 1
        # dp tables train dense: every commit may move every dp row
        for gtid in self._dp_tables:
            self.table_versions[gtid] = self.version
        for kind, idx in self._since_commit:
            gtids = (self._bucket_tables.get(idx, []) if kind == "tp"
                     else [self._row_tables[idx]])
            for gtid in gtids:
                self.table_versions[gtid] = self.version
        self._since_commit = set()
        # lineage (ISSUE 14): a commit OPENS version V's async track in
        # the flight recorder — publish/scan/apply/serve land on it
        default_recorder().lineage(self.version, "commit")
        return self.version

    def replace(self, params: dict, opt_states: Optional[dict] = None) -> int:
        """Full out-of-band swap (e.g. `InferenceEngine.set_params`):
        bumps the version and BREAKS the delta chain — the next publish
        is forced to a snapshot, and a consumer store that replaced its
        params mid-stream resyncs at the next snapshot."""
        self._params = params
        if opt_states is not None:
            self._opt = opt_states
        self.version += 1
        for gtid in range(self._n_tables):
            self.table_versions[gtid] = self.version
        self._pending = {}
        self._since_commit = set()
        self._published_version = None
        self._chain_broken = True
        return self.version

    # -------------------------------------------------- versioned reads
    def table(self, kind: str, idx: int):
        """The current param leaf for ('tp'|'row'|'dp', index) — use this
        (never a cached array reference) wherever code needs the table a
        serving path reads, so the read is at the store's version by
        construction."""
        return self._params[kind][idx]

    def read_rows(self, b: int, keys) -> np.ndarray:
        """THE versioned read of tp bucket `b`: rows for flat keys
        (`rank * rows_max + row`, the layout `HotRowCache` and the hot
        shard share), canonical table values with the authoritative
        hot-resident rows overlaid — byte-identical to what
        `get_weights` would report for those rows at this version."""
        keys = np.asarray(keys, np.int64).reshape(-1)
        bucket = self.emb.plan.tp_buckets[b]
        rows_max = max(bucket.rows_max, 1)
        arr = self._params["tp"][b]
        w_idx = keys // rows_max
        r_idx = keys % rows_max
        sd = self.emb._bucket_store_dtype(b)
        if self.emb._bucket_memory_kind(b):
            out = _np_rows_from_shards(arr, w_idx, r_idx)
            if sd != "f32":
                # quantized at-rest storage (ISSUE 15): the versioned
                # read is ALWAYS decoded f32 — payload values (cast
                # losslessly above) x the per-row scale leaf
                out = out * _np_rows_from_shards(
                    self._params["tp_scale"][b], w_idx, r_idx)
        else:
            out = padded_gather_rows(arr, w_idx, r_idx)
            if sd != "f32":
                # HBM-resident quantized buckets (ISSUE 17): payload
                # codes gather losslessly through the f32 transit, so
                # decode is the same multiply by the scale rows
                out = out * padded_gather_rows(
                    self._params["tp_scale"][b], w_idx, r_idx)
        overlay = self.emb.hot_resident_rows(self._params).get(b)
        if overlay is not None:
            okeys, orows = overlay                 # sorted by construction
            pos = np.searchsorted(okeys, keys)
            pos_c = np.minimum(pos, len(okeys) - 1)
            hit = (pos < len(okeys)) & (okeys[pos_c] == keys)
            if hit.any():
                out = np.array(out)
                out[hit] = orows[pos_c[hit]]
        return out.astype(np.float32)

    def read_row_table_rows(self, t: int, keys) -> np.ndarray:
        """Versioned read of row-sliced table `t` by GLOBAL row ids."""
        keys = np.asarray(keys, np.int64).reshape(-1)
        rt = self.emb.plan.row_tables[t]
        base = np.asarray(rt.row_base, np.int64)
        w_idx = np.searchsorted(base, keys, side="right") - 1
        r_idx = keys - base[w_idx]
        arr = self._params["row"][t]
        return padded_gather_rows(arr, w_idx, r_idx)

    def get_weights(self) -> List[np.ndarray]:
        """Portable merged per-table weights at the current version
        (delegates to `DistributedEmbedding.get_weights`, whose hot
        overlay reads the same `hot_resident_rows` source as
        `read_rows`)."""
        return self.emb.get_weights(self._params)

    def set_weights(self, weights) -> int:
        """Rebuild params from portable per-table weights (empty hot
        set, like `DistributedEmbedding.set_weights`) and bump the
        version. Chain-breaking like `replace`."""
        params = self.emb.set_weights(weights)
        return self.replace(params, self._opt)

    def sync_hot_rows(self, new_keys=None, admit: bool = False) -> int:
        """Route the hot shard's consistency step through the store:
        write-back + optional re-admission on the OWNED pytrees, then a
        version bump. The merged view (`read_rows`/`get_weights`) is
        invariant under this step — only the canonical/hot split moves."""
        p, s = self.emb.sync_hot_rows(self._params, self._opt,
                                      new_keys=new_keys, admit=admit)
        self._params = p
        if s is not None:
            self._opt = s
        self.version += 1
        for b in self.emb._hot_buckets:
            for gtid in self._bucket_tables.get(b, []):
                self.table_versions[gtid] = self.version
        return self.version

    # --------------------------------------------------------- publishing
    def publish(self, directory: str, force_snapshot: bool = False) -> dict:
        """Write the next stream file into `directory`.

        The first publish (and every `snapshot_every`-th, and any forced
        one) is a full snapshot: one merged per-table array each, the
        compaction consumers resync from. Otherwise a row-delta: per
        touched tp bucket / row table the dedup'd keys + merged row
        payloads accumulated by `observe`/`commit` since the last
        publish, plus the dp tables whole. Requires a commit since the
        last publish (versions must be distinct per file).

        Robustness (ISSUE 13): the first publish into a directory sweeps
        orphaned ``*.tmp*`` files a crashed predecessor left; the stream
        file is fsync'd before — and its directory after — the atomic
        rename (rename is atomic against concurrent readers but not
        against power loss); and all publisher state (`_publishes`,
        `_published_version`, the pending touched keys) moves ONLY after
        the rename lands, so an injected `InjectedCrash` (or a real
        exception) between write and rename leaves the publisher able to
        retry the same content under a later version. The
        ``store.publish`` fault point wraps the write: ``pause`` skips
        the publish (returns ``{"kind": "paused", ...}``, state kept),
        ``truncate``/``bit_flip`` corrupt the renamed-in file (the
        consumer's quarantine path owns those), ``crash_before_rename``
        raises after writing the tmp file.

        Returns {"kind", "version", "base_version", "path", "bytes",
        "rows"}."""
        self._require_single_controller("publish")
        if self.version == self._published_version:
            raise ValueError(
                "publish: nothing committed since the last publish "
                "(stream files are keyed by version)")
        os.makedirs(directory, exist_ok=True)
        m = self._metrics
        if directory not in self._swept_dirs:
            self._swept_dirs.add(directory)
            removed = ckpt_lib.sweep_orphan_tmp(directory)
            if removed:
                m.counter("store/orphan_tmp_swept_total").inc(len(removed))
                warnings.warn(
                    f"publish: swept {len(removed)} orphaned tmp file(s) "
                    f"from {directory} (crashed publisher leftovers): "
                    f"{[os.path.basename(p) for p in removed]}",
                    RuntimeWarning, stacklevel=2)
        publishes = self._publishes + 1
        snap = (force_snapshot or self._published_version is None
                or (self.snapshot_every
                    and publishes % self.snapshot_every == 0))
        dd = self.delta_dtype
        meta = {"version": self.version,
                "base_version": self._published_version,
                "published_at": time.time(),
                "dtype": dd,
                "sig": self._sig}

        def enc(arrays, name, rows):
            # quantized stream payload (ISSUE 15): rows encode at the
            # store's delta_dtype with the per-row scale as a sibling
            # array; f32 writes the rows verbatim (byte-identical file)
            p, s = wire_ops.encode_rows_np(rows, dd)
            arrays[name] = p
            if s is not None:
                arrays[f"{name}_scale"] = s

        # model payload bytes through the ONE shared formula
        # (ops/wire.delta_row_bytes / snapshot_row_bytes) — the bench's
        # measured-vs-model reconciliation and `exchange_padding_report`
        # charge the same arithmetic
        model_bytes = 0
        if snap:
            meta["kind"] = "snapshot"
            weights = self.get_weights()
            arrays = {}
            for i, w in enumerate(weights):
                enc(arrays, f"table{i}", np.asarray(w, np.float32))
                model_bytes += w.shape[0] * wire_ops.snapshot_row_bytes(
                    w.shape[1], dd)
            n_rows = sum(w.shape[0] for w in weights)
        else:
            meta["kind"] = "delta"
            arrays = {}
            n_rows = 0
            for (kind, idx), keys in sorted(self._pending.items()):
                rows = (self.read_rows(idx, keys) if kind == "tp"
                        else self.read_row_table_rows(idx, keys))
                arrays[f"{kind}{idx}_keys"] = keys
                enc(arrays, f"{kind}{idx}_rows", rows)
                model_bytes += len(keys) * wire_ops.delta_row_bytes(
                    rows.shape[1], dd)
                n_rows += len(keys)
            for j in range(len(self._params["dp"])):
                # dp tables stay f32: dense-trained (every row moves
                # every delta) and small by construction
                dp = np.asarray(self._params["dp"][j], np.float32)
                arrays[f"dp{j}_full"] = dp
                model_bytes += dp.nbytes
                n_rows += dp.shape[0]
        path = _publish_path(directory, self.version, meta["kind"])
        spec = faults.check("store.publish", path=path,
                            stream_kind=meta["kind"])
        if spec is not None:
            m.counter("store/publish_faults_total", kind=spec.kind).inc()
        if spec is not None and spec.kind == "pause":
            # publisher pause: nothing written, nothing advanced — the
            # pending touched keys ride into the next (resumed) publish
            return {"kind": "paused", "version": self.version,
                    "base_version": meta["base_version"], "path": None,
                    "bytes": 0, "rows": 0}
        # atomic publication: a concurrent consumer's directory scan must
        # never see a half-written file (the tmp name does not match the
        # stream pattern, and os.replace is atomic on one filesystem);
        # fsync file-then-rename-then-directory makes it crash-durable
        tmp = ckpt_lib.save_row_delta(path + ".tmp", meta, arrays)
        if spec is not None and spec.kind in faults.CORRUPTING_KINDS:
            faults.corrupt_file(tmp, spec)
        if spec is not None and spec.kind == "crash_before_rename":
            raise faults.InjectedCrash(
                f"publish {path}: injected crash before rename "
                f"(orphaned {os.path.basename(tmp)})")
        ckpt_lib.publish_atomic(tmp, path)
        self._publishes = publishes
        self._published_version = self.version
        self._pending = {}
        info = {"kind": meta["kind"], "version": self.version,
                "base_version": meta["base_version"], "path": path,
                "bytes": os.path.getsize(path), "rows": n_rows,
                "dtype": dd,
                # measured sum of in-file array bytes vs the shared byte
                # model (wire.delta_row_bytes/snapshot_row_bytes) — equal
                # by construction; the bench and tier-1 assert it stays so
                "payload_bytes": int(sum(a.nbytes
                                         for a in arrays.values())),
                "model_payload_bytes": int(model_bytes)}
        m.counter("store/publishes").inc()
        m.counter("store/publish_bytes").inc(info["bytes"])
        m.counter("store/publish_rows").inc(n_rows)
        self._published_bytes_by_dtype[dd] = (
            self._published_bytes_by_dtype.get(dd, 0) + info["bytes"])
        m.gauge("store/bytes", dtype=dd).set(
            self._published_bytes_by_dtype[dd])
        # role-labeled: a publisher and a consumer store on ONE shared
        # run registry (the bench serve mode shape) must not flap a
        # single version gauge between the two meanings
        m.gauge("store/version", role="publisher").set(self.version)
        default_recorder().lineage(self.version, "publish",
                                   kind=meta["kind"], bytes=info["bytes"],
                                   rows=n_rows)
        return info

    # --------------------------------------------------------- consuming
    def _check_sig(self, meta: dict, path: str) -> None:
        sig = [tuple(int(x) for x in pair) for pair in meta.get("sig", [])]
        if sig != self._sig:
            raise ValueError(
                f"{path}: published for a different model "
                f"(table shapes {sig} != {self._sig})")

    def _hot_resident_guard(self) -> None:
        if self.emb.hot_resident_rows(self._params):
            raise ValueError(
                "delta consumption requires an EMPTY hot set on the "
                "consumer: resident hot rows would shadow the canonical "
                "writes (serving replicas are hot-less; training "
                "consumers must sync_hot_rows + drop residency first)")

    def _apply_tp_rows(self, b: int, keys: np.ndarray, rows: np.ndarray):
        """Set decoded f32 `rows` into bucket b. Returns (table, scale):
        scale is None for f32-stored buckets; quantized buckets (ISSUE
        15) re-encode the incoming rows at the bucket's storage dtype
        (deterministic RNE — stream application must be reproducible)
        and write payload + per-row scale leaves in one pass."""
        bucket = self.emb.plan.tp_buckets[b]
        rows_max = max(bucket.rows_max, 1)
        arr = self._params["tp"][b]
        w_idx = keys // rows_max
        r_idx = keys % rows_max
        sd = self.emb._bucket_store_dtype(b)
        if sd != "f32":
            payload, scale = wire_ops.encode_rows_np(rows, sd)
            if self.emb._bucket_memory_kind(b):
                return (_host_set_rows(arr, w_idx, r_idx, payload),
                        _host_set_rows(self._params["tp_scale"][b],
                                       w_idx, r_idx, scale))
            # HBM-resident quantized bucket (ISSUE 17): payload codes
            # transit the f32 scatter lanes exactly (ints on the int8
            # grid / exact e4m3 values), `_scatter_rows` casts back to
            # the stored dtype on write
            return (padded_scatter_rows(arr, w_idx, r_idx, payload),
                    padded_scatter_rows(self._params["tp_scale"][b],
                                        w_idx, r_idx, scale))
        if self.emb._bucket_memory_kind(b):
            return _host_set_rows(arr, w_idx, r_idx,
                                  np.asarray(rows, np.float32)), None
        return padded_scatter_rows(arr, w_idx, r_idx, rows), None

    def _apply_row_rows(self, t: int, keys: np.ndarray, rows: np.ndarray):
        rt = self.emb.plan.row_tables[t]
        base = np.asarray(rt.row_base, np.int64)
        arr = self._params["row"][t]
        w_idx = np.searchsorted(base, keys, side="right") - 1
        r_idx = keys - base[w_idx]
        return padded_scatter_rows(arr, w_idx, r_idx, rows)

    def apply_published(self, path: str) -> dict:
        """Apply one stream file (delta or snapshot) in place.

        Deltas require `meta['base_version'] == self.version`
        (DeltaChainError otherwise — resync from a snapshot); snapshots
        apply from any version. Returns {"kind", "version", "rows",
        "bytes", "published_at", "payload"} — payload maps
        ("tp", b) -> (keys, rows) for delta files so callers (the
        serving engine) can update HBM caches straight off the wire."""
        meta, arrays = ckpt_lib.load_row_delta(path)
        if "crc" not in meta:
            # checksum-less legacy (container v1) file: applied, but
            # counted — the rolling-upgrade signal (ISSUE 13)
            self._metrics.counter("store/legacy_files_total").inc()
        self._check_sig(meta, path)
        # payload dtype (ISSUE 15): legacy headers carry none and load
        # as the f32 they are; quantized payloads decode against their
        # `_scale` siblings here, so every downstream consumer (row
        # scatter, HBM caches, the returned payload map) sees f32 rows.
        # load_row_delta already refused dtypes this build cannot decode.
        stream_dtype = meta.get("dtype", "f32")

        def dec(name):
            if stream_dtype == "f32":
                return np.asarray(arrays[name], np.float32)
            scale = arrays.get(f"{name}_scale")
            if scale is None:
                raise ValueError(
                    f"{path}: array {name} is {stream_dtype}-encoded but "
                    "carries no _scale sibling — publisher bug, not "
                    "stream damage")
            return wire_ops.decode_rows_np(arrays[name], scale,
                                           stream_dtype)

        payload: Dict[Tuple[str, int], Tuple[np.ndarray, np.ndarray]] = {}
        if meta["kind"] == "snapshot":
            tables = [dec(f"table{i}") for i in range(self._n_tables)]
            self._params = self.emb.set_weights(tables)
            n_rows = sum(t.shape[0] for t in tables)
            self._chain_broken = False       # snapshots re-anchor the chain
        else:
            if self._chain_broken:
                raise DeltaChainError(
                    f"{path}: this store's params were replaced out of "
                    "band (set_params/replace) after its last snapshot — "
                    "a version match alone cannot prove the delta chains "
                    "from the current tables; resync from a snapshot")
            if meta["base_version"] != self.version:
                raise DeltaChainError(
                    f"{path}: delta base_version {meta['base_version']} "
                    f"!= consumer version {self.version}; resync from a "
                    "snapshot")
            self._hot_resident_guard()
            new_params = dict(self._params)
            new_params["tp"] = list(self._params["tp"])
            new_params["row"] = list(self._params["row"])
            new_params["dp"] = list(self._params["dp"])
            if "tp_scale" in self._params:
                new_params["tp_scale"] = list(self._params["tp_scale"])
            n_rows = 0
            for name in sorted(arrays):
                m = re.match(r"^(tp|row)(\d+)_keys$", name)
                if not m:
                    continue
                kind, idx = m.group(1), int(m.group(2))
                keys = np.asarray(arrays[name], np.int64)
                rows = dec(f"{kind}{idx}_rows")
                n_rows += len(keys)
                if kind == "tp":
                    new_params["tp"][idx], scale_leaf = self._apply_tp_rows(
                        idx, keys, rows)
                    if scale_leaf is not None:
                        new_params["tp_scale"][idx] = scale_leaf
                    payload[("tp", idx)] = (keys, rows)
                else:
                    new_params["row"][idx] = self._apply_row_rows(
                        idx, keys, rows)
            for j in range(len(new_params["dp"])):
                name = f"dp{j}_full"
                if name in arrays:
                    dp = jnp.asarray(arrays[name])
                    if self.emb.mesh is not None:
                        from jax.sharding import (NamedSharding,
                                                  PartitionSpec as P)
                        dp = jax.device_put(
                            dp, NamedSharding(self.emb.mesh, P()))
                    new_params["dp"][j] = dp
                    n_rows += arrays[name].shape[0]
            self._params = new_params
        self.version = int(meta["version"])
        self._published_version = None     # consumers never publish onward
        info = {"kind": meta["kind"], "version": self.version,
                "rows": n_rows, "bytes": os.path.getsize(path),
                "published_at": meta.get("published_at"),
                "payload": payload}
        m = self._metrics
        m.counter("store/applies").inc()
        m.counter("store/apply_bytes").inc(info["bytes"])
        m.counter("store/apply_rows").inc(n_rows)
        m.gauge("store/version", role="consumer").set(self.version)
        default_recorder().lineage(self.version, "apply",
                                   kind=meta["kind"], rows=n_rows)
        return info


class DeltaConsumer:
    """Poll loop + staleness accounting over one store and one publish
    directory: apply every new stream file in chain order, falling back
    to the newest snapshot when the chain breaks (missed or compacted
    deltas).

    Hardened (ISSUE 13): a corrupt file (failed checksum, bad zip, torn
    payload) is QUARANTINED — skipped permanently, counted in
    ``store/corrupt_files_total``, one loud warning — and the chain
    re-anchors on the publisher's next snapshot through the existing
    snapshot-fallback path; a transient read error (`OSError`) retries
    with capped exponential backoff (``store/poll_retries_total``) and,
    if it persists, gives the file up for THIS poll only. `poll` leaves
    the store in a consistent last-good state on every path — the
    serving engine's `poll_updates` wraps it so nothing escapes to the
    request loop. The metadata cache is bounded by the LIVE stream:
    entries whose files left the directory (compaction, operator
    cleanup) evict at the end of each poll.

    Args:
      store: consumer-side `TableStore`.
      directory: publish directory to poll.
      max_transient_retries: in-poll retry budget per file for transient
        read errors (backoff 2^k * `retry_backoff_s`, capped at
        `retry_backoff_cap_s` — bounded so a poll can never stall the
        serving loop for more than ~0.1 s).
    """

    def __init__(self, store: TableStore, directory: str,
                 max_transient_retries: int = 3,
                 retry_backoff_s: float = 0.005,
                 retry_backoff_cap_s: float = 0.05):
        self.store = store
        self.directory = directory
        self.max_transient_retries = int(max_transient_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_cap_s = float(retry_backoff_cap_s)
        self._meta_cache: Dict[str, dict] = {}
        self.applied: List[dict] = []
        self._lag_versions: List[int] = []
        self._lag_seconds: List[float] = []
        self._apply_seconds = 0.0
        self._rows_applied = 0
        # path -> reason string; quarantined files are invisible to the
        # chooser forever (stream files are immutable once renamed, so
        # corruption is permanent)
        self.quarantined: Dict[str, str] = {}
        self._retries_total = 0
        self._degraded: set = set()
        self._last_scan: List[Tuple[int, str, str]] = []
        # versions whose first directory sighting was already recorded
        # on the lineage track (one "scan" per version per consumer)
        self._lineage_scanned: set = set()

    # ------------------------------------------------------------ internals
    def _visible(self, upto: Optional[int] = None
                 ) -> List[Tuple[int, str, str]]:
        self._last_scan = scan_published(self.directory)
        files = [f for f in self._last_scan if f[2] not in self.quarantined]
        if upto is not None:
            # version ceiling (fleet canary pinning): files beyond the
            # ceiling stay out of the view — NOT out of `_last_scan`,
            # whose bookkeeping (meta-cache eviction, quarantine GC)
            # must keep tracking the whole live stream
            files = [f for f in files if f[0] <= upto]
        return files

    def _quarantine(self, path: str, err: BaseException) -> None:
        reason = f"{type(err).__name__}: {err}"
        self.quarantined[path] = reason[:300]
        self._degraded.add("corrupt_stream")
        self.store._metrics.counter("store/corrupt_files_total").inc()
        warnings.warn(
            f"stream file quarantined (corrupt, will re-anchor on the "
            f"next snapshot): {path}: {reason[:200]}",
            RuntimeWarning, stacklevel=3)

    def _backoff(self, attempt: int) -> None:
        self._retries_total += 1
        self.store._metrics.counter("store/poll_retries_total").inc()
        time.sleep(min(self.retry_backoff_s * (2 ** attempt),
                       self.retry_backoff_cap_s))

    def _meta(self, path: str) -> Optional[dict]:
        """Cached metadata-header read (stream files are immutable once
        renamed into place, so a path's header never changes). Returns
        None when the header cannot be read this poll — corrupt headers
        quarantine the file, transient errors leave it for the next
        poll."""
        meta = self._meta_cache.get(path)
        if meta is not None:
            return meta
        for attempt in range(self.max_transient_retries + 1):
            try:
                meta = ckpt_lib.load_row_delta_meta(path)
                self._meta_cache[path] = meta
                return meta
            except Exception as e:  # noqa: BLE001 - classified below
                if _is_transient_error(e):
                    if attempt >= self.max_transient_retries:
                        self._degraded.add("io_transient")
                        return None
                    self._backoff(attempt)
                    continue
                if _is_corrupt_error(e):
                    self._quarantine(path, e)
                    return None
                raise

    def _choose(self, files: List[Tuple[int, str, str]]) -> Optional[str]:
        """The next applicable stream file, or None (caught up / waiting
        on the publisher's next compaction)."""
        if self.store._chain_broken:
            # out-of-band replace: the local version bump is
            # meaningless against the publisher's namespace, so no
            # version filter and no delta qualifies — re-anchor on
            # the NEWEST snapshot (even one consumed before the
            # replace: re-applying re-syncs, then deltas replay)
            snaps = [f for f in files if f[1] == "snapshot"]
            return snaps[-1][2] if snaps else None
        cand = [f for f in files if f[0] > self.store.version]
        # prefer the delta that chains from the current version (the
        # cheap path); otherwise the oldest newer snapshot — the chain
        # replays from there on later iterations. Neither found = chain
        # gap with no snapshot yet: wait for the next compaction.
        nxt = None
        for version, kind, path in cand:
            if kind == "delta":
                meta = self._meta(path)
                if meta is not None \
                        and meta["base_version"] == self.store.version:
                    return path
            elif nxt is None:
                nxt = path                   # snapshot: applies from any v
        return nxt

    def _apply_one(self, path: str) -> Tuple[Optional[dict], str]:
        """Apply one file with transient retry; returns (info, status)
        with status in {"applied", "transient", "quarantined"}."""
        for attempt in range(self.max_transient_retries + 1):
            t0 = time.perf_counter()
            try:
                info = self.store.apply_published(path)
            except DeltaChainError:
                raise            # chooser contract violation: loud
            except Exception as e:  # noqa: BLE001 - classified below
                if _is_transient_error(e):
                    if attempt >= self.max_transient_retries:
                        self._degraded.add("io_transient")
                        return None, "transient"
                    self._backoff(attempt)
                    continue
                if _is_corrupt_error(e):
                    self._quarantine(path, e)
                    return None, "quarantined"
                raise
            self._apply_seconds += time.perf_counter() - t0
            return info, "applied"
        return None, "transient"             # unreachable; keeps mypy honest

    def _evict_meta_cache(self) -> None:
        """Bound the metadata cache by the LIVE stream (ISSUE 13
        satellite): a long-running consumer's cache otherwise grows with
        run length as compaction deletes superseded deltas. Uses the
        poll's own final scan — no extra directory walk."""
        live = {path for _, _, path in self._last_scan}
        if any(p not in live for p in self._meta_cache):
            self._meta_cache = {p: m for p, m in self._meta_cache.items()
                                if p in live}
        for p in [p for p in self.quarantined if p not in live]:
            del self.quarantined[p]          # counted already; file gone
        # the scan-lineage dedup set stays bounded by IN-FLIGHT versions:
        # applied versions can never re-emit (the emission requires
        # version > store.version), so their entries are dead weight
        self._lineage_scanned = {v for v in self._lineage_scanned
                                 if v > self.store.version}

    def degraded_reasons(self) -> frozenset:
        """The consumer's current degradation set (empty = healthy):
        ``corrupt_stream`` while quarantined damage keeps it behind the
        publisher, ``io_transient`` while reads flake. Cleared when a
        poll ends fully caught up."""
        return frozenset(self._degraded)

    def poll(self, upto: Optional[int] = None) -> List[dict]:
        """Apply every applicable published file. Returns the applied
        infos (possibly empty). Never raises on corrupt or transiently
        unreadable stream files (see class docstring); the
        ``consumer.poll`` fault point can inject a transient error at
        entry (exercising the engine-level degradation path).

        `upto` caps consumption at a version ceiling: files above it are
        invisible to this poll, and staleness/health accounting is
        measured against the ceiling, not the stream head — a replica
        pinned at a rollout's last-promoted version is CAUGHT UP, not
        degraded, while newer unvetted versions accumulate."""
        faults.check_raise("consumer.poll", directory=self.directory)
        files = self._visible(upto)
        # lineage (ISSUE 14): the first time this consumer's directory
        # scan SEES a not-yet-applied version, stamp it on the
        # version's async track — the scan->apply gap is the consumer
        # half of staleness
        for version, _, _ in files:
            if (version > self.store.version
                    and version not in self._lineage_scanned):
                self._lineage_scanned.add(version)
                default_recorder().lineage(version, "scan")
        newer = [f for f in files if f[0] > self.store.version]
        if not newer and not self.store._chain_broken:
            self._evict_meta_cache()
            # healthy only if nothing newer exists even among the
            # quarantined files (a quarantined NEWER file means serving
            # is genuinely behind the publisher: stay degraded until
            # the re-anchoring snapshot arrives); under a ceiling,
            # "newer" means newer WITHIN the ceiling
            if not any(f[0] > self.store.version for f in self._last_scan
                       if upto is None or f[0] <= upto):
                self._degraded.clear()
            return []
        if newer:
            # staleness just before this poll: how many published
            # versions serving had not yet consumed
            self._lag_versions.append(newer[-1][0] - self.store.version)
            self.store._metrics.gauge("store/version_lag").set(
                self._lag_versions[-1])
        out = []
        latest_seen = self.store.version
        while True:
            files = self._visible(upto)
            capped = [f for f in self._last_scan
                      if upto is None or f[0] <= upto]
            if capped:
                latest_seen = max(latest_seen, capped[-1][0])
            nxt = self._choose(files)
            if nxt is None:
                break
            info, status = self._apply_one(nxt)
            if status == "quarantined":
                continue                     # rescan: snapshot fallback
            if status != "applied":
                break                        # transient: next poll retries
            self._rows_applied += info["rows"]
            if info.get("published_at"):
                self._lag_seconds.append(
                    max(time.time() - info["published_at"], 0.0))
                self.store._metrics.histogram(
                    "store/publish_to_apply_seconds").record(
                        self._lag_seconds[-1])
            self.applied.append(info)
            out.append(info)
        # post-poll residual lag (0 when fully caught up; >0 when the
        # chain still waits on the publisher's next compaction) — from
        # the apply loop's own final scan, no extra directory walk on
        # the serving hot path
        residual = max(0, latest_seen - self.store.version)
        if out or residual:
            self.store._metrics.gauge("store/version_lag").set(residual)
        if residual == 0 and not self.store._chain_broken:
            self._degraded.clear()           # caught up: healed
        self._evict_meta_cache()
        return out

    def stats(self) -> dict:
        d_bytes = [i["bytes"] for i in self.applied if i["kind"] == "delta"]
        s_bytes = [i["bytes"] for i in self.applied
                   if i["kind"] == "snapshot"]
        versions = [i["version"] for i in self.applied]
        return {
            "applied": len(self.applied),
            "applied_deltas": len(d_bytes),
            "applied_snapshots": len(s_bytes),
            "rows_applied": self._rows_applied,
            "delta_bytes_total": int(sum(d_bytes)),
            "delta_bytes_mean": (int(np.mean(d_bytes)) if d_bytes else 0),
            "snapshot_bytes": (int(s_bytes[-1]) if s_bytes else 0),
            "apply_seconds": round(self._apply_seconds, 6),
            "apply_rows_per_sec": (
                round(self._rows_applied / self._apply_seconds)
                if self._apply_seconds > 0 else 0),
            "staleness_versions_max": (max(self._lag_versions)
                                       if self._lag_versions else 0),
            "staleness_versions_mean": (
                round(float(np.mean(self._lag_versions)), 3)
                if self._lag_versions else 0.0),
            "staleness_s_max": (round(max(self._lag_seconds), 6)
                                if self._lag_seconds else 0.0),
            "staleness_s_mean": (
                round(float(np.mean(self._lag_seconds)), 6)
                if self._lag_seconds else 0.0),
            "version_monotonic": versions == sorted(versions)
            and len(set(versions)) == len(versions),
            "version": self.store.version,
            "quarantined_files": len(self.quarantined),
            "poll_retries": self._retries_total,
            "degraded_reasons": sorted(self._degraded),
        }


def restore_from_published(emb, directory: str,
                           upto: Optional[int] = None) -> TableStore:
    """Rebuild a store's params from a publish stream: the newest
    snapshot (<= `upto` when given) plus every chained delta after it —
    the (snapshot + deltas) checkpoint-restore path. Returns a consumer
    `TableStore` positioned at the reconstructed version."""
    files = scan_published(directory)
    if upto is not None:
        files = [f for f in files if f[0] <= upto]
    snaps = [f for f in files if f[1] == "snapshot"]
    if not snaps:
        raise FileNotFoundError(
            f"no snapshot in {directory}: a delta chain needs its anchor")
    _, _, snap_path = snaps[-1]
    meta, arrays = ckpt_lib.load_row_delta(snap_path)
    n = len(meta["sig"])
    sd = meta.get("dtype", "f32")

    def table(i):
        if sd == "f32":
            return arrays[f"table{i}"]
        scale = arrays.get(f"table{i}_scale")
        if scale is None:
            # same publisher-bug guard as apply_published's dec()
            raise ValueError(
                f"{snap_path}: array table{i} is {sd}-encoded but "
                "carries no _scale sibling — publisher bug, not "
                "stream damage")
        return wire_ops.decode_rows_np(arrays[f"table{i}"], scale, sd)

    store = TableStore(emb, emb.set_weights([table(i) for i in range(n)]))
    store._check_sig(meta, snap_path)
    store.version = int(meta["version"])
    for version, kind, path in files:
        if version <= store.version or kind != "delta":
            continue
        store.apply_published(path)
    return store
