"""Hybrid data-parallel / model-parallel distributed embedding for TPU.

API mirror of the reference `DistributedEmbedding`
(reference: distributed_embeddings/python/layers/dist_model_parallel.py:712-1214),
re-designed SPMD-first:

  * One 1-D `jax.sharding.Mesh` axis plays both the dp and mp role (the
    reference likewise requires dp ranks == mp ranks, :757).
  * The forward is a single `shard_map` region: ids move dp->mp via a true
    `lax.all_to_all` — each device sends every destination only the ids of
    the features that destination owns, packed per (bucket, hotness)
    "exchange group" so per-device id traffic is
    O(owned features x true hotness), matching the reference's
    hvd.alltoall-with-splits (:169-288, :211) rather than replicating all
    ids everywhere. Embedding outputs move mp->dp the same way (:870-872).
  * Row-sliced tables: all_gather ids -> masked local lookup -> psum_scatter,
    the equivalent of hvd.grouped_allgather + grouped_reducescatter (:889-904).
    XLA gather clamps out-of-bounds instead of zero-filling like TF, so
    validity is masked explicitly.
  * There is no DistributedGradientTape/Optimizer monkey-patching layer:
    under sharded autodiff, grads of mp-sharded params stay local and grads of
    replicated (dp) params are psummed by the shard_map transpose — the
    behavioral contract of the reference's patched tape (:1242-1267) falls out
    for free.

Exchange-group design (the TPU answer to Horovod's variable `splits`):
XLA collectives need static shapes, so the variable per-destination split
sizes of hvd.alltoall are re-expressed as a *set* of fixed-shape all_to_alls.
Slots of one fused bucket are grouped by their input's hotness k; each group
exchanges a dense [world, B_local, f_max_g, k] block. Within a group there is
no hotness padding at all (every member has exactly k ids), and f_max_g
padding is bounded by per-destination feature-count imbalance, which the
planner's placement strategies already minimize.
"""

import contextlib
import functools
import logging
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_embeddings_tpu import compat
from distributed_embeddings_tpu.obs.spans import spanned
from distributed_embeddings_tpu.obs.stages import stage, staged
from distributed_embeddings_tpu.ops import embedding_ops, pallas_lookup
from distributed_embeddings_tpu.ops import sparse_update as sparse_update_ops
from distributed_embeddings_tpu.ops import wire as wire_ops
from distributed_embeddings_tpu.ops.embedding_ops import (GroupSort,
                                                          RaggedIds,
                                                          SparseIds,
                                                          canonical_id_sort)
from distributed_embeddings_tpu.ops.sparse_update import (SparseOptimizer,
                                                          SparseRowGrad,
                                                          concat_grads)
from distributed_embeddings_tpu.parallel.mesh import DEFAULT_AXIS, create_mesh
from distributed_embeddings_tpu.parallel.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.parallel.plan import ShardedPlan, lower_strategy
from distributed_embeddings_tpu.utils.hotness import HotnessTracker
from distributed_embeddings_tpu.utils.initializers import get_initializer

__all__ = [
    "DistEmbeddingStrategy",
    "DistributedEmbedding",
    "broadcast_variables",
]


def _combine(emb: jax.Array, weights: Optional[jax.Array],
             combiner: Optional[str]) -> jax.Array:
    """Reduce the hotness axis (second-to-last) of `emb` [..., K, w].

    weights [..., K] carries 0 for padded slots; mean divides by the true
    (weighted) count, matching tf.nn.embedding_lookup_sparse semantics.
    """
    if combiner is None:
        # flatten hotness into width; caller re-slices per-input
        return emb.reshape(emb.shape[:-2] + (emb.shape[-2] * emb.shape[-1],))
    if weights is None:
        if combiner == "sum":
            return jnp.sum(emb, axis=-2)
        return jnp.mean(emb, axis=-2)
    out = jnp.einsum("...k,...kw->...w", weights.astype(emb.dtype), emb)
    if combiner == "mean":
        denom = jnp.maximum(jnp.sum(weights, axis=-1), 1.0).astype(out.dtype)
        out = out / denom[..., None]
    return out


class _PreparedInput:
    """A normalized input: dense ids [B, k] (+ optional 0/1 weights [B, k])."""

    __slots__ = ("ids", "weights", "orig_1d", "k")

    def __init__(self, ids, weights, orig_1d, k):
        self.ids = ids
        self.weights = weights
        self.orig_1d = orig_1d
        self.k = k


class _ExchangeGroup:
    """The slots of one tp bucket whose inputs share hotness k — one
    fixed-shape all_to_all unit (see module docstring). Static planning data
    computed at trace time from the plan + each input's (static) hotness."""

    __slots__ = ("bucket", "k", "class_inputs", "sel", "offs", "f_max",
                 "need_w", "rank_slots", "f_per_rank", "flat_sel",
                 "in_offsets")

    def __init__(self, bucket, k, class_inputs, sel, offs, f_max, need_w,
                 rank_slots):
        self.bucket = bucket            # index into plan.tp_buckets
        self.k = k                      # hotness shared by all member inputs
        self.class_inputs = class_inputs  # tp-input indices, stack order
        self.sel = sel                  # [world, f_max] -> class input pos
        self.offs = offs                # [world, f_max] fused-table row offsets
        self.f_max = f_max
        self.need_w = need_w
        self.rank_slots = rank_slots    # per rank: ordered member TPSlots
        # true-splits (ragged) exchange metadata: per-destination feature
        # counts, the unpadded destination-major selector, and each
        # destination's start row in the flat send buffer
        self.f_per_rank = np.asarray([len(s) for s in rank_slots], np.int32)
        self.flat_sel = (np.concatenate(
            [sel[r, :n] for r, n in enumerate(self.f_per_rank)])
            if int(self.f_per_rank.sum()) else np.zeros((0,), np.int32))
        self.in_offsets = np.concatenate(
            [[0], np.cumsum(self.f_per_rank)[:-1]]).astype(np.int32)


class TapResiduals:
    """Residuals of a tapped forward pass, consumed by `sparse_update`:
    per exchange group the post-exchange absolute row ids and effective
    combine weights (None = uniform; the static scale is recomputed from the
    group metadata), and per row-sliced input the sentinel-masked local ids +
    effective weights. Registered as a pytree with the static exchange-group
    cache key as aux data so `sparse_update` can rebuild the group layout.

    `tp_sort` / `row_sort` (sort folding, ISSUE 2): optionally one
    `GroupSort` per exchange group / row input — the canonical sort of the
    SAME id stream `tp_ids`/`row_ids` carries, produced once in the forward
    (under `residual_sort_scope`) so the sparse update consumes the
    precomputed order instead of re-sorting (the reference CUDA backward's
    reuse of forward-sorted ids, embedding_lookup_kernels.cu:706-773).
    None entries (or None lists — every pre-fold producer) mean "no
    artifact"; consumers fall back to a fresh sort, so the field is
    strictly additive.

    `hot_pos` / `hot_w` (hot-row replication, ISSUE 4): per exchange group
    on a hot-sharded bucket, the pre-exchange hot-membership split —
    each lane's position in the replicated hot shard (sentinel H on miss)
    and its effective hit weight (0 on miss). The sparse update turns
    the hot-tap gradients into the replicated hot shard's dense row
    update from exactly these. None on non-hot groups / pre-hot
    residuals."""

    def __init__(self, key, tp_ids, tp_w, row_ids, row_w, tp_sort=None,
                 row_sort=None, hot_pos=None, hot_w=None):
        self.key = key          # static: ((k, has_w) per tp input)
        self.tp_ids = tp_ids    # per group [world, B, f_g, k_g] int32
        self.tp_w = tp_w        # per group [world, B, f_g, k_g] f32 or None
        self.row_ids = row_ids  # per row input [world, B, k] int32 (sentinel)
        self.row_w = row_w      # per row input [world, B, k] f32
        self.tp_sort = tp_sort    # per group GroupSort([world, N]...) | None
        self.row_sort = row_sort  # per row input GroupSort | None
        self.hot_pos = hot_pos  # per group [1, world, B_l, f_g, k_g] | None
        self.hot_w = hot_w      # per group [1, world, B_l, f_g, k_g] | None

    def tree_flatten(self):
        return ((self.tp_ids, self.tp_w, self.row_ids, self.row_w,
                 self.tp_sort, self.row_sort, self.hot_pos, self.hot_w),
                self.key)

    @classmethod
    def tree_unflatten(cls, key, children):
        return cls(key, *children)


jax.tree_util.register_pytree_node(
    TapResiduals, TapResiduals.tree_flatten, TapResiduals.tree_unflatten)


# The true-splits (ragged) exchange op lives behind the wire seam with
# every other exchange collective (ISSUE 10): `ops.wire.ragged_exchange`
# — native lax.ragged_all_to_all on TPU, the equal-shaped-collective
# emulation on CPU. Alias kept: this module's exchange paths call it by
# its historical name.
_ragged_exchange_op = wire_ops.ragged_exchange


# (backend, world_size) -> bool: did the 'native' (compute_on jit) host
# apply mode compile on this backend? Probed at most ONCE per process
# (VERDICT r5 weak #3): every further layer instance / bucket / optimizer
# reuses the verdict instead of re-compiling the known-failing program and
# re-spewing XLA's RET_CHECK stack trace to stderr.
_HOST_NATIVE_VERDICT: dict = {}


@contextlib.contextmanager
def _capture_fd2(out: dict):
    """Capture OS-level stderr (fd 2) for the duration of the block into
    ``out['data']`` — XLA's C++ status_macros LOG(ERROR) bypasses
    sys.stderr, so a Python-level redirect cannot catch it. The window is
    kept to a single probe call; callers replay the bytes when the error
    is unexpected so no diagnostics are ever lost."""
    import sys
    import tempfile
    sys.stderr.flush()
    saved = os.dup(2)
    cap = tempfile.TemporaryFile(mode="w+b")
    os.dup2(cap.fileno(), 2)
    try:
        yield
    finally:
        sys.stderr.flush()
        os.dup2(saved, 2)
        os.close(saved)
        cap.seek(0)
        out["data"] = cap.read()
        cap.close()


_INTERPRET_WARNED: set = set()


def _warn_interpret_once(path: str) -> None:
    """DET_LOOKUP_PATH=tiled/fused off-TPU runs the Pallas kernels in
    interpret mode — orders of magnitude slower than the XLA path. Fine
    for the equivalence tests that set it deliberately; say so once per
    path anywhere else (ADVICE r4)."""
    if path in _INTERPRET_WARNED:
        return
    _INTERPRET_WARNED.add(path)
    import warnings
    warnings.warn(
        f"DET_LOOKUP_PATH={path} on a non-TPU backend: this Pallas "
        "lookup runs in INTERPRET mode here (correct but very slow — "
        "intended for tests). Unset DET_LOOKUP_PATH or run on TPU.",
        RuntimeWarning, stacklevel=3)


# jit-of-named-function with static bounds: cached across chunks, calls
# and buckets (a fresh lambda per chunk would re-trace+compile every time)
def _slice_rows(a, lo: int, hi: int):
    return lax.slice_in_dim(a, lo, hi, axis=1)


_slice_rows_jit = jax.jit(_slice_rows, static_argnums=(1, 2))


def _overrides_forward(cls) -> bool:
    """True when a user embedding class carries its own forward semantics:
    it overrides Embedding.__call__ and does not declare
    `det_gather_semantics = True` (the opt-out for subclasses whose call is
    still a plain gather+combine, e.g. config-only extensions)."""
    from distributed_embeddings_tpu.layers.embedding import (
        ConcatOneHotEmbedding, Embedding)
    if cls is None or cls in (Embedding, ConcatOneHotEmbedding):
        return False
    if getattr(cls, "det_gather_semantics", False):
        return False
    # find the class that actually defines the instance __call__ — a
    # config-only layer with NO __call__ (reference CustomEmbedding test
    # contract, dist_model_parallel_test.py:48-66) has no forward of its
    # own and keeps gather semantics; plain attribute lookup would wrongly
    # return the metaclass's call here
    for base in cls.__mro__:
        if "__call__" in base.__dict__:
            return base.__dict__["__call__"] is not Embedding.__dict__.get(
                "__call__")
    return False


def _effective_weights(weights: Optional[jax.Array], k: int,
                       combiner: Optional[str]):
    """Rewrite a (weights, combiner) pair as an explicit weighted SUM:
    out[b] = scale * sum_k eff_w[b,k] * rows[b,k]  (eff_w None = all-ones).
    Returns (eff_w, scale). Matches `_combine` semantics exactly."""
    if combiner is None or combiner == "sum":
        return weights, 1.0
    if combiner != "mean":
        raise ValueError(f"Unknown combiner {combiner}")
    if weights is None:
        return None, 1.0 / max(k, 1)
    denom = jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1.0)
    return weights / denom, 1.0


def _as_stream(x: jax.Array, feature_major: bool) -> jax.Array:
    """An exchange group's per-slot array [B, f, k, ...] in the order its
    stream is flattened in: [f, k, B, ...] feature-major (see
    `sparse_update.feature_major_stream`), as it is batch-major."""
    return jnp.moveaxis(x, 0, 2) if feature_major else x


class DistributedEmbedding:
    """Distributed embedding wrapper: plans placement for a list of embedding
    tables and runs the hybrid-parallel lookup over a device mesh.

    Args (mirroring the reference :712-751):
      embeddings: list of `Embedding` layer objects (or anything exposing
        `get_config()` with input_dim/output_dim/combiner).
      strategy: 'auto' (default) | 'basic' | 'memory_balanced' |
        'memory_optimized' | 'comm_balanced' (beyond-reference: minimizes
        exchange-group padding volume using `input_max_hotness` hints;
        memory as tie-break). 'auto' = comm_balanced when any
        input_max_hotness hint > 1 (multi-hot models pay real exchange
        padding), else the reference's 'basic'. See
        `exchange_padding_report` for the volume accounting.
      column_slice_threshold: tables above this element count are split along
        output_dim into power-of-2 slices. None = auto only when there are
        fewer tables than devices.
      row_slice_threshold: tables above this element count are row-sliced
        evenly across all devices.
      dp_input: if True, `apply` takes data-parallel input — one global-batch
        array per feature. If False, takes model-parallel input (see
        `apply_mp`).
      input_table_map: input i -> table input_table_map[i] (shared tables).
      data_parallel_threshold: tables below this run replicated data-parallel.
      gpu_embedding_size: on-device element budget for table-parallel tables;
        overflow tables are flagged for host offload.
      mesh: jax Mesh with a single axis (default: all devices, axis "mp").
        world_size is taken from the mesh.
      input_max_hotness: optional per-input static max hotness, required to
        accept RaggedIds inputs (TPU needs static shapes).
      exchange_wire: float wire format for the exchange collectives
        (ISSUE 5): 'f32' (default — the exact pre-seam collectives),
        'bf16' (half the activation/weight/gradient exchange bytes, f32
        math on both sides), or 'bf16-sr' (bf16 forward, stochastically
        rounded bf16 gradients). None defers to `DET_EXCHANGE_WIRE`.
        Gated off per bucket where the planner knows rounding would be
        user-visible (combiner-None passthrough buckets keep f32); see
        `exchange_padding_report` for the resulting byte accounting.
      vocab_slack: dynamic-vocabulary growth capacity (ISSUE 7): extra
        physical rows pre-reserved per table-parallel table beyond its
        configured input_dim, so a `vocab.VocabManager` can admit new
        raw keys at runtime by binding them to free rows — no array
        shape ever changes, so the jitted step never recompiles. None
        defers to `DET_VOCAB_SLACK` (default 0 = exactly the pre-slack
        plan). The slack inflates the table's physical shape: `init`,
        `get_weights`/`set_weights` and checkpoints all see
        ``input_dim + vocab_slack`` rows for managed tables.
      storage_dtype: at-rest row storage for COLD (host-offloaded)
        buckets (ISSUE 15): 'f32' (default — params byte-identical to
        the pre-seam layer, the `exchange_wire='f32'` contract applied
        to memory), 'int8' (per-row-scaled symmetric quantization: ~4x
        more rows per host byte, rows decode to f32 at gather time,
        training write-back rounds stochastically with the wire seam's
        keyless hash), or 'fp8' (float8_e4m3fn payload where the
        backend ships it). None defers to ``DET_STORE_DTYPE``.
        Quantized buckets carry their per-row scales in a
        ``params['tp_scale']`` leaf (present only when some bucket
        quantizes, so default pytrees are unchanged); device-resident
        buckets always stay f32 (parallel/plan._storage_eligibility).
    """

    def __init__(self,
                 embeddings: Sequence,
                 strategy: str = "auto",
                 column_slice_threshold: Optional[int] = None,
                 row_slice_threshold: Optional[int] = None,
                 dp_input: bool = True,
                 input_table_map: Optional[Sequence[int]] = None,
                 data_parallel_threshold: Optional[int] = None,
                 gpu_embedding_size: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 world_size: Optional[int] = None,
                 input_max_hotness: Optional[Sequence[Optional[int]]] = None,
                 use_custom_kernel: bool = True,
                 compute_dtype: Optional[Any] = None,
                 hot_rows: Optional[int] = None,
                 exchange_wire: Optional[str] = None,
                 vocab_slack: Optional[int] = None,
                 storage_dtype: Optional[str] = None):
        if mesh is None and world_size is not None and world_size > 1:
            mesh = create_mesh(jax.devices()[:world_size])
        self.mesh = mesh
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError("DistributedEmbedding expects a 1-D mesh")
            self.axis = mesh.axis_names[0]
            self.world_size = mesh.devices.size
        else:
            self.axis = DEFAULT_AXIS
            self.world_size = 1

        self.dp_input = dp_input
        # single worker: fall back to pure table-parallel like the reference
        # (:764-774); mp-input mode also disables dp/row groups.
        if self.world_size > 1 and dp_input:
            row_thr, dp_thr = row_slice_threshold, data_parallel_threshold
        else:
            row_thr, dp_thr = None, None

        # hot-row replication (ISSUE 4) needs the dp->mp exchange to skip:
        # mp-input mode has no exchange, so the hot shard is dp-input only
        self.strategy = DistEmbeddingStrategy(
            embeddings, self.world_size, strategy,
            input_table_map=input_table_map,
            column_slice_threshold=column_slice_threshold,
            row_slice_threshold=row_thr,
            data_parallel_threshold=dp_thr,
            gpu_embedding_size=gpu_embedding_size,
            input_hotness=input_max_hotness,
            hot_rows=(hot_rows if dp_input else 0),
            exchange_wire=exchange_wire,
            vocab_slack=vocab_slack,
            storage_dtype=storage_dtype)

        if self.strategy.table_groups[1]:
            if not all(self.strategy.local_configs):
                raise ValueError(
                    "Not enough tables after slicing to run on all devices. "
                    "Try decreasing column_slice_threshold or device count.")

        self.plan: ShardedPlan = lower_strategy(self.strategy)
        # Custom user layer classes (reference instantiates layer_class via
        # from_config and calls ITS forward, :820-834). Tables whose class
        # overrides the forward are honored per-table in the data-parallel
        # group; in the fused model-parallel groups the bucket machinery
        # executes plain gather+combine, so a custom forward there would be
        # silently ignored — reject at plan time instead (VERDICT r4 item 6).
        self._dp_custom_layers = {}
        for j, gtid in enumerate(self.strategy.table_groups[0]):
            cfg = self.strategy.global_configs[gtid]
            if _overrides_forward(cfg.get("layer_class")):
                kwargs = {k: v for k, v in cfg.items() if k != "layer_class"}
                self._dp_custom_layers[j] = (
                    cfg["layer_class"].from_config(kwargs))
        for group in (1, 2):
            for gtid in self.strategy.table_groups[group]:
                cls = self.strategy.global_configs[gtid].get("layer_class")
                if _overrides_forward(cls):
                    raise ValueError(
                        f"table {gtid}: custom embedding layer class "
                        f"{cls.__name__} overrides __call__, but it was "
                        "placed in a fused model-parallel group whose "
                        "executor implements plain gather+combine — its "
                        "custom forward would be silently ignored. Either "
                        "(a) raise data_parallel_threshold so this table "
                        "is data-parallel (custom forwards run per-table "
                        "there), or (b) set `det_gather_semantics = True` "
                        "on the class to assert its forward is equivalent "
                        "to a plain (weighted) gather+combine.")
        self.input_max_hotness = (list(input_max_hotness)
                                  if input_max_hotness is not None else None)
        self._n_inputs = len(self.strategy.input_table_map)
        # like the reference Embedding's use_custom_kernel (embedding.py:72):
        # route multi-hot fused-bucket lookups through the Pallas kernels when
        # on a TPU backend; plain XLA gather+reduce otherwise.
        self.use_custom_kernel = use_custom_kernel
        # DET_RAGGED_EXCHANGE: dp->mp ids (and weights, incl. the masks
        # synthesized for ragged/sparse inputs) can move via the
        # true-splits exchange (_ragged_exchange_op) instead of padded
        # [world, f_max] blocks — the reference's exact hvd.alltoall(splits)
        # wire volume. '1' forces it, '0' forces padded, 'auto' (default)
        # decides per exchange group from the static padding accounting
        # (see _use_ragged_exchange). DET_RAGGED_NATIVE overrides the
        # native-vs-emulation op choice (default: native iff TPU backend).
        # flows that never call make_sparse_train_step (inference,
        # dense-grad optax) reach the lookup kernels too: __init__ runs
        # eagerly, so a requested kernel path is checked against the chip
        # here (compiled vs XLA, raising on a mismatch), and a request
        # this plan's tables cannot serve is refused before any step
        from distributed_embeddings_tpu.ops.sparse_update import (
            prevalidate_active_impl)
        lookup_path = os.environ.get("DET_LOOKUP_PATH", "auto")
        if lookup_path in ("tiled", "fused"):
            prevalidate_active_impl(widths=self.plan_widths())
        if (lookup_path == "pallas" and use_custom_kernel
                and pallas_lookup.is_tpu_backend()):
            for bucket in self.plan.tp_buckets:
                if bucket.offload and self._offload_enabled:
                    continue             # host-side lookup, no kernel
                pallas_lookup.check_lookup_kernel(
                    max(bucket.rows_max, 1), bucket.width, jnp.float32)
        # mixed precision (reference tests' mixed_precision_policy,
        # dist_model_parallel_test.py:30-34): params stay fp32, the lookup
        # outputs / combines / collectives run in compute_dtype (e.g. bf16).
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self._groups_cache: dict = {}
        # sort folding (ISSUE 2): (optimizer_kind, dedup_strategy) spec set
        # by residual_sort_scope — when active, tapped forwards produce
        # per-group GroupSort residuals (see TapResiduals). None = off, the
        # strictly-additive default for every non-tapped path.
        self._residual_sort_spec = None
        # serving hook (see offload_lookup_scope): replaces the host-side
        # offloaded-bucket lookup in tapless forwards — the HBM hot-row
        # cache in `serving/` plugs in here
        self._offload_lookup_override = None
        # lookahead pipeline hook (ISSUE 9, see staged_exchange_scope):
        # when set, apply() consumes these prefetched (ex_list, row_outs)
        # instead of running the exchange — the dense stage of
        # schedule.LookaheadEngine's fused step plugs in here
        self._staged_exchange = None
        # (bucket, f_max, k) -> "ragged"|"padded": the exchange path each
        # group actually took (filled at trace time, see _use_ragged_exchange)
        self._exchange_path_taken: dict = {}
        self._host_fn_cache: dict = {}
        # hot-row replication (ISSUE 4): buckets with a replicated hot
        # shard, host-side frequency trackers (admission), and the jitted
        # sync helpers. Trackers are created lazily by observe_hot_ids /
        # sync_hot_rows; membership itself is carried in params["hot"].
        self._hot_buckets = [b for b, bk in enumerate(self.plan.tp_buckets)
                             if bk.hot_rows > 0]
        self._hot_trackers: dict = {}
        self._hot_fn_cache: dict = {}
        self._hot_meta_cache: dict = {}
        # physical host offload: buckets past the gpu_embedding_size budget
        # live in pinned host memory (the reference's /CPU:0 placement,
        # :829-831); their lookups run in a compute_on("device_host") region
        # outside the shard_map, streaming only combined rows device-ward.
        self._offload_enabled = False
        self._host_kind = None
        if any(b.offload for b in self.plan.tp_buckets):
            devs = (list(self.mesh.devices.flat) if self.mesh is not None
                    else jax.devices())
            self._host_kind = compat.host_memory_kind(devs[0])
            self._offload_enabled = self._host_kind is not None
            if not self._offload_enabled:
                import warnings
                warnings.warn(
                    "gpu_embedding_size flagged table(s) for host offload, "
                    "but this backend exposes no host memory space: "
                    "offloaded buckets remain device-resident and count "
                    "against device memory.", RuntimeWarning, stacklevel=2)
        # quantized at-rest storage for OFFLOADED buckets (ISSUE 15)
        # rides the offload lookup seam: with offload runtime-disabled
        # those gathers run INSIDE the shard_map through the plain f32
        # path with no host decode hook — demote them to f32 loudly
        # rather than serve raw int8 rows as embeddings. HBM-resident
        # quantized buckets (ISSUE 17) decode inside the jitted forward
        # and are untouched by the offload runtime gate.
        if not self._offload_enabled and any(
                b.offload and b.storage_dtype != "f32"
                for b in self.plan.tp_buckets):
            import warnings
            warnings.warn(
                "storage_dtype quantization demoted to f32 for offloaded "
                "bucket(s): host offload is disabled on this backend and "
                "offloaded quantized storage decodes at the "
                "offloaded-gather seam.", RuntimeWarning, stacklevel=2)
            for b in self.plan.tp_buckets:
                if b.offload:
                    b.storage_dtype = "f32"
        # jitted per-bucket storage codec fns (decode at gather /
        # SR re-encode at write-back), cached per bucket
        self._store_codec_cache: dict = {}
        # touched-rows quantized host-apply accounting (ISSUE 17): raw
        # totals mirrored into the default registry's
        # store/quantized_rows_applied_total counter per apply
        self.quantized_rows_applied_total: int = 0
        self.quantized_apply_bytes_total: int = 0

    def _bucket_store_dtype(self, b: int) -> str:
        """The at-rest storage dtype of tp bucket b ('f32' | 'int8' |
        'fp8') — THE one predicate every storage-seam branch keys on."""
        return self.plan.tp_buckets[b].storage_dtype

    @property
    def quantized_buckets(self) -> list:
        """Buckets whose rows are stored quantized (ISSUE 15)."""
        return [b for b, bk in enumerate(self.plan.tp_buckets)
                if bk.storage_dtype != "f32"]

    def _bucket_scale(self, params: dict, b: int):
        """The per-row scale leaf of bucket b, or None at f32 storage.
        A QUANTIZED bucket with no scale leaf fails loudly here — the
        read-side twin of `host_bucket_apply`'s drift guard; falling
        through to the f32 path would serve raw int8/fp8 payload codes
        as embedding values."""
        scales = params.get("tp_scale")
        scale = None if scales is None else scales[b]
        if scale is None and self._bucket_store_dtype(b) != "f32":
            raise ValueError(
                f"bucket {b} stores {self._bucket_store_dtype(b)} rows "
                "but params carries no tp_scale leaf for it — the "
                "pytree drifted from the plan (rebuild params via "
                "init/set_weights; a hand-stripped checkpoint cannot "
                "decode)")
        return scale

    def _device_bucket_scales(self, params: dict):
        """Per-bucket stacked scale leaves for quantized DEVICE-resident
        buckets (None elsewhere), or None when no bucket needs one — the
        forward/update shard_map threading of ISSUE 17. Host-offloaded
        scales stay OUT of shard_map bodies (XLA memory-space
        propagation does not reach through them); those decode at the
        offloaded-gather seam (`_host_group_exchange`) instead."""
        if not self.quantized_buckets:
            return None
        out = [(self._bucket_scale(params, b)
                if (self._bucket_store_dtype(b) != "f32"
                    and self._bucket_memory_kind(b) is None) else None)
               for b in range(len(self.plan.tp_buckets))]
        return out if any(s is not None for s in out) else None

    def _encoded_shard_fn(self, shard_fn, encoder):
        """(rank, b, part) accessor over quantized bucket shards with
        ONE encode per (bucket, rank): the payload (part 0) and scale
        (part 1) stack builders each ask for one half of the same
        encode. THE shared assembly core of `init` (jnp encoder) and
        `set_weights` (numpy encoder) — ISSUE 15."""
        cache: dict = {}

        def part(rank: int, b: int, idx: int):
            if (b, rank) not in cache:
                cache[(b, rank)] = encoder(shard_fn(rank, b),
                                           self._bucket_store_dtype(b))
            return cache[(b, rank)][idx]
        return part

    def plan_widths(self) -> tuple:
        """The distinct table lane widths of this plan (tp buckets + row
        slices) — THE one derivation of what `sparse_update.
        prevalidate_active_impl` must compile-probe the shape-classed
        pallas gate at (a width class never probed eagerly can never
        validate under the jit trace). Shared by this constructor and the
        train-step/engine factories."""
        return tuple(sorted({b.width for b in self.plan.tp_buckets}
                            | {rt.width for rt in self.plan.row_tables}))

    # ------------------------------------------------------------------ init
    def _tp_shard(self, key, b: int, rank: int) -> jax.Array:
        """One rank's fused bucket table [rows_max, width] (traced/jittable)."""
        bucket = self.plan.tp_buckets[b]
        tbl = jnp.zeros((max(bucket.rows_max, 1), bucket.width), jnp.float32)
        for seg_i, (table_id, row_offset, rows, init_spec, dtype) in enumerate(
                bucket.init_segments[rank]):
            seg_key = jax.random.fold_in(
                jax.random.fold_in(key, table_id), rank * 131071 + seg_i)
            init_fn = get_initializer(init_spec)
            block = init_fn(seg_key, (rows, bucket.width),
                            dtype or jnp.float32)
            tbl = tbl.at[row_offset:row_offset + rows].set(block)
        return tbl

    def _row_shard(self, key, t: int, rank: int) -> jax.Array:
        rt = self.plan.row_tables[t]
        init_fn = get_initializer(rt.initializer)
        tbl = jnp.zeros((max(rt.rows_max, 1), rt.width), jnp.float32)
        rows = rt.rows_per_rank[rank]
        seg_key = jax.random.fold_in(jax.random.fold_in(key, 7919 + t), rank)
        return tbl.at[:rows].set(init_fn(seg_key, (rows, rt.width),
                                         rt.dtype or jnp.float32))

    def _rank_of_device(self):
        """Map each addressable mesh device -> its rank index (axis position).

        Multi-process safe: iterates only devices this process can address."""
        flat = list(self.mesh.devices.flat)
        return [(flat.index(d), d) for d in flat
                if d.process_index == jax.process_index()]

    def _bucket_memory_kind(self, b: int) -> Optional[str]:
        """The backend's host memory kind (pinned_host on TPU) for
        physically-offloaded buckets, else None."""
        if self._offload_enabled and self.plan.tp_buckets[b].offload:
            return self._host_kind
        return None

    def _param_sharding(self, memory_kind: Optional[str] = None):
        kw = {"memory_kind": memory_kind} if memory_kind else {}
        return NamedSharding(self.mesh, P(self.axis), **kw)

    def _stack_sharded(self, shard_fn,
                       memory_kind: Optional[str] = None) -> jax.Array:
        """Assemble a [world, rows_max, w] P(axis)-sharded array by computing
        (or staging) each rank's shard directly on that rank's device — peak
        staging is one shard, never the global stack (round-1 gap: the
        reference chunks set_weights for the same reason, :977-1017, and
        CPU-inits to dodge init OOM, embedding.py:28-47).

        shard_fn(rank) -> [rows_max, w] array-like for that rank.
        memory_kind='pinned_host' stages each shard into that rank's host
        memory (offloaded buckets — reference /CPU:0 build, :1186-1189).
        """
        shards, shape = [], None
        for rank, dev in self._rank_of_device():
            with jax.default_device(dev):
                shard = jnp.asarray(shard_fn(rank))[None]
            target = (jax.sharding.SingleDeviceSharding(
                dev, memory_kind=memory_kind) if memory_kind else dev)
            shard = jax.device_put(shard, target)
            shards.append(shard)
            shape = shard.shape
        global_shape = (self.world_size,) + tuple(shape[1:])
        sharding = self._param_sharding(memory_kind)
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards)

    # -------------------------------------------------- hot-row replication
    def _hot_sentinel(self, b: int) -> int:
        """The membership sentinel key for bucket b: one past the flat key
        space ``world * rows_max`` — no valid (rank, row) key reaches it,
        and sentinel-padded slots keep the membership array sorted."""
        return self.world_size * max(self.plan.tp_buckets[b].rows_max, 1)

    def _empty_hot_entry(self, b: int) -> dict:
        """A hot-shard param entry with an EMPTY resident set: all-sentinel
        membership (every lookup misses — byte-identical behavior to no
        hot shard until `sync_hot_rows` admits rows) and zero rows."""
        bucket = self.plan.tp_buckets[b]
        ids = jnp.full((bucket.hot_rows,), self._hot_sentinel(b), jnp.int32)
        rows = jnp.zeros((bucket.hot_rows, bucket.width), jnp.float32)
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            ids = jax.device_put(ids, rep)
            rows = jax.device_put(rows, rep)
        return {"ids": ids, "rows": rows}

    def _init_hot_params(self) -> list:
        return [self._empty_hot_entry(b) if b in self._hot_buckets else None
                for b in range(len(self.plan.tp_buckets))]

    @spanned("embedding/init")
    def init(self, key) -> dict:
        """Create the parameter pytree:
          {'dp': [replicated [V,w]...],
           'tp': [stacked [world, rows_max, w] per bucket...],
           'row': [stacked [world, slice_rows_max, w] per row table...]}

        Layers built with `hot_rows` add
          {'hot': [None | {'ids': [H] int32 sorted membership keys,
                           'rows': [H, w] replicated hot rows} per bucket]}
        — initially EMPTY (all-sentinel membership), so the forward is
        behaviorally identical to a hot-less layer until `sync_hot_rows`
        admits rows.

        With a mesh bound, every tp/row shard is materialized per-device
        (shard-sized staging); without one, plain stacked arrays.
        """
        kd, kt, kr = jax.random.split(key, 3)
        params = {"dp": [], "tp": [], "row": []}
        for j, cfg in enumerate(self.strategy.dp_configs):
            init_fn = get_initializer(cfg.get("embeddings_initializer", "uniform"))
            params["dp"].append(init_fn(
                jax.random.fold_in(kd, j),
                (cfg["input_dim"], cfg["output_dim"]),
                cfg.get("dtype") or jnp.float32))
        qbs = self.quantized_buckets
        scales: Dict[int, jax.Array] = {}
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            params["dp"] = [jax.device_put(a, rep) for a in params["dp"]]
            tp_init = jax.jit(self._tp_shard, static_argnums=(1, 2))
            row_init = jax.jit(self._row_shard, static_argnums=(1, 2))
            q_shard = self._encoded_shard_fn(
                lambda rank, b: tp_init(kt, b, rank), wire_ops.encode_rows)
            for b in range(len(self.plan.tp_buckets)):
                mk = self._bucket_memory_kind(b)
                if b in qbs:
                    params["tp"].append(self._stack_sharded(
                        lambda rank, b=b: q_shard(rank, b, 0),
                        memory_kind=mk))
                    scales[b] = self._stack_sharded(
                        lambda rank, b=b: q_shard(rank, b, 1),
                        memory_kind=mk)
                else:
                    params["tp"].append(self._stack_sharded(
                        lambda rank, b=b: tp_init(kt, b, rank),
                        memory_kind=mk))
            for t in range(len(self.plan.row_tables)):
                params["row"].append(self._stack_sharded(
                    lambda rank, t=t: row_init(kr, t, rank)))
        else:
            # jit the shard builders here too: eager .at[].set would copy
            # the whole bucket once per init segment (26 segments x 4.2 GiB
            # for the tiny model); jitted, XLA fuses them into one buffer
            tp_init = jax.jit(self._tp_shard, static_argnums=(1, 2))
            row_init = jax.jit(self._row_shard, static_argnums=(1, 2))
            for b in range(len(self.plan.tp_buckets)):
                arr = jnp.stack(
                    [tp_init(kt, b, r) for r in range(self.world_size)])
                mk = self._bucket_memory_kind(b)
                scale = None
                if b in qbs:
                    arr, scale = wire_ops.encode_rows(
                        arr, self._bucket_store_dtype(b))
                if mk:
                    hsh = jax.sharding.SingleDeviceSharding(
                        jax.devices()[0], memory_kind=mk)
                    arr = jax.device_put(arr, hsh)
                    if scale is not None:
                        scale = jax.device_put(scale, hsh)
                params["tp"].append(arr)
                if scale is not None:
                    scales[b] = scale
            for t in range(len(self.plan.row_tables)):
                params["row"].append(jnp.stack(
                    [row_init(kr, t, r) for r in range(self.world_size)]))
        if qbs:
            params["tp_scale"] = [scales.get(b)
                                  for b in range(len(self.plan.tp_buckets))]
        if self._hot_buckets:
            params["hot"] = self._init_hot_params()
        return params

    def param_shardings(self, mesh: Optional[Mesh] = None) -> dict:
        """NamedSharding pytree matching `init` output — for pjit/device_put.

        Buckets past the gpu_embedding_size budget carry
        memory_kind='pinned_host' (reference _maybe_offload :449-476 +
        /CPU:0 build :1186-1189): they live in host RAM and their lookups run
        host-side, outside the shard_map (XLA memory-space propagation does
        not reach through shard_map bodies as of jax 0.9)."""
        mesh = mesh or self.mesh
        if mesh is None:
            raise ValueError("No mesh bound")
        rep = NamedSharding(mesh, P())
        shard0 = NamedSharding(mesh, P(self.axis))
        def tp_shard(b):
            mk = self._bucket_memory_kind(b)
            return (NamedSharding(mesh, P(self.axis), memory_kind=mk)
                    if mk else shard0)
        out = {
            "dp": [rep for _ in self.strategy.dp_configs],
            "tp": [tp_shard(b) for b in range(len(self.plan.tp_buckets))],
            "row": [shard0 for _ in self.plan.row_tables],
        }
        if self.quantized_buckets:
            # per-row scales co-locate with their quantized bucket
            out["tp_scale"] = [tp_shard(b) if b in self.quantized_buckets
                               else None
                               for b in range(len(self.plan.tp_buckets))]
        if self._hot_buckets:
            out["hot"] = [({"ids": rep, "rows": rep}
                           if b in self._hot_buckets else None)
                          for b in range(len(self.plan.tp_buckets))]
        return out

    # ----------------------------------------------------------- input prep
    @staged("ids")
    def _prepare_one(self, x, max_hotness: Optional[int]) -> _PreparedInput:
        if isinstance(x, tuple) and len(x) == 2 and not isinstance(x, RaggedIds):
            ids, weights = x
            return _PreparedInput(jnp.asarray(ids), jnp.asarray(weights),
                                  False, ids.shape[1])
        if isinstance(x, RaggedIds):
            if max_hotness is None:
                raise ValueError(
                    "RaggedIds input requires input_max_hotness (static shapes "
                    "are mandatory on TPU)")
            ids, weights = embedding_ops.ragged_to_padded(x, max_hotness)
            return _PreparedInput(ids, weights, False, max_hotness)
        if isinstance(x, SparseIds):
            batch, k = int(x.dense_shape[0]), int(x.dense_shape[1])
            rows, cols = x.indices[:, 0], x.indices[:, 1]
            ids = jnp.zeros((batch, k), x.values.dtype).at[rows, cols].set(x.values)
            weights = jnp.zeros((batch, k), jnp.float32).at[rows, cols].set(1.0)
            return _PreparedInput(ids, weights, False, k)
        ids = jnp.asarray(x)
        if ids.ndim == 1:
            return _PreparedInput(ids[:, None], None, True, 1)
        if ids.ndim != 2:
            raise ValueError(f"Expected 1-D or 2-D ids, got shape {ids.shape}")
        return _PreparedInput(ids, None, False, ids.shape[1])

    def _prepare_inputs(self, inputs) -> List[_PreparedInput]:
        if len(inputs) != self._n_inputs:
            raise ValueError(
                f"Expected {self._n_inputs} inputs, got {len(inputs)}")
        prepped = []
        for i, x in enumerate(inputs):
            mh = (self.input_max_hotness[i]
                  if self.input_max_hotness is not None else None)
            prepped.append(self._prepare_one(x, mh))
        return prepped

    def _exchange_groups(self, tp_prep: Sequence[_PreparedInput]):
        """Compute the (bucket, hotness) exchange groups and the per-input
        assembly map for a given set of prepared inputs.

        Returns (groups, assembly) where assembly[i] is the ordered list of
        (rank, group_idx, slot_in_group) triples for tp input i — the same
        rank-major slot order the plan's weight layout uses (col_cursor order,
        reference :921-936), so column-slice re-concat stays correct.
        Cached per hotness/weights signature (one entry per jit trace shape).
        """
        key = tuple((p.k, p.weights is not None) for p in tp_prep)
        return self._exchange_groups_for_key(key)

    def _exchange_groups_for_key(self, key):
        """Same as `_exchange_groups` but from the static (k, has_weights)
        signature alone — lets `sparse_update` rebuild the exact group layout
        a tapped forward used, via TapResiduals.key."""
        hit = self._groups_cache.get(key)
        if hit is not None:
            return hit
        world = self.world_size
        per_bk: dict = {}   # (bucket, k) -> per-rank [(slot_idx, TPSlot)...]
        order: List[Tuple[int, int]] = []
        for b, bucket in enumerate(self.plan.tp_buckets):
            for r, slots in enumerate(bucket.slots):
                for j, s in enumerate(slots):
                    k = key[s.tp_input][0]
                    if (b, k) not in per_bk:
                        per_bk[(b, k)] = [[] for _ in range(world)]
                        order.append((b, k))
                    per_bk[(b, k)][r].append((j, s))
        groups: List[_ExchangeGroup] = []
        slot_map: dict = {}  # (bucket, rank, slot_idx_in_bucket) -> (g, j_g)
        for g, (b, k) in enumerate(order):
            ranks = per_bk[(b, k)]
            class_inputs = sorted({s.tp_input for lst in ranks
                                   for (_, s) in lst})
            pos = {i: c for c, i in enumerate(class_inputs)}
            f_max = max(len(lst) for lst in ranks)
            sel = np.zeros((world, f_max), np.int32)
            offs = np.zeros((world, f_max), np.int32)
            rank_slots = []
            for r, lst in enumerate(ranks):
                for j_g, (j, s) in enumerate(lst):
                    sel[r, j_g] = pos[s.tp_input]
                    offs[r, j_g] = s.row_offset
                    slot_map[(b, r, j)] = (g, j_g)
                rank_slots.append([s for (_, s) in lst])
            need_w = any(key[i][1] for i in class_inputs)
            groups.append(_ExchangeGroup(b, k, class_inputs, sel, offs,
                                         f_max, need_w, rank_slots))
        assembly = [
            [(rank, *slot_map[(bb, rank, jj)]) for (rank, bb, jj) in slots]
            for slots in self.plan.tp_input_slots
        ]
        self._groups_cache[key] = res = (groups, assembly)
        return res

    def exchange_padding_report(self, hotness=None,
                                hot_hit_rate=None, batch: int = 1,
                                vocab=None, lookahead: int = 0,
                                delta_dtype: Optional[str] = None) -> dict:
        """Static accounting of the dp->mp id-exchange volume.

        The exchange sends one dense [world, f_max, k] id block per
        (bucket, hotness) group and sample (see `_exchange_groups_for_key`)
        where the reference's `hvd.alltoall` with per-destination splits
        (reference dist_model_parallel.py:169-288) sends exactly the true
        nnz. This report quantifies the gap for this plan, per sample:

          true_ids       sum over groups of sum_r f_r * k  (the reference's
                         splits volume)
          exchanged_ids  sum over groups of world * f_max * k (what the
                         fixed-shape lax.all_to_all moves)
          ratio          exchanged / true  (1.0 = zero padding)

        Hot-row replication (ISSUE 4): groups on hot-sharded buckets gain

          hot_hit_ids       expected ids served by the replicated hot
                            shard per sample (true_ids x hit rate) —
                            lanes that skip the exchange's useful volume
                            (sentinel-masked, zero weight; the WIRE shape
                            is static and unchanged: `exchanged_ids`
                            still counts the padded wire slots)
          true_ids_post_hot the residual USEFUL exchange volume,
                            true_ids - hot_hit_ids

        The hit rate comes from the layer's measured admission trackers
        (`observe_hot_ids`), WINDOWED to the current residency epoch —
        `sync_hot_rows` resets the hit/miss counters at each
        (re-)admission so the all-miss warmup stream never dilutes the
        rate. Pass `hot_hit_rate` (scalar or {bucket: rate}) to project
        for an assumed rate instead.

        Wire compression (ISSUE 5): every group entry also carries the
        BYTE-level accounting of its wire — `wire_dtype` /
        `id_wire_dtype` (the plan's per-bucket formats),
        `exchanged_bytes` / `true_bytes` (id wire + the mp->dp
        activation return, forward direction, per global sample) and
        `act_bytes` vs `act_bytes_f32` (the dominant activation term at
        the actual vs the f32 wire). Top-level `act_wire_reduction` is
        the statically auditable compression claim: 2.0 when every
        bucket rides bf16, 1.0 at the f32 default. The gradient
        transpose moves the same activation volume again (same ratio);
        weighted inputs add `weight_bytes_if_weighted` per group —
        FORWARD-only (weights are inputs, not params: no gradient
        crosses the weight wire). Id fields charge the NARROWED id
        dtype (an int16 bucket's wire moves 2 B/id, exactly what the
        lowered operand carries). `analysis.programs.
        expected_collective_bytes` converts these per-sample fields
        into the exact per-device HLO payload bytes, and the
        collective-bytes audit pass + tests/test_wire.py assert the
        compiled program matches the model byte-for-byte on every wire
        config (ISSUE 10 reconciliation).

        Touched-row accounting (ISSUE 6): every group also carries
        `touched_rows_per_step` — the dedup'd post-sentinel-mask ids the
        sparse update actually writes per step at global batch size
        ``batch`` (hot-HIT lanes are sentinel-masked and skip the
        canonical scatter, so the post-hot volume is the base; the
        dedup bound is the bucket's total row count) — and
        `delta_bytes_per_step`, the row-delta size model built on it:
        ``(touched + republished hot hits) *
        wire.delta_row_bytes(width, delta_dtype)`` — 8 id bytes plus
        the width-element payload at the STREAM's storage dtype plus
        its per-row scale (`delta_dtype=None` defers to
        ``DET_DELTA_DTYPE``; 'f32' reproduces the historical
        ``8 + 4*width`` exactly). Hot-HIT rows skip the canonical
        scatter but still move the replicated hot shard, so the
        published delta republishes their merged values (bounded by
        the hot capacity). `wire.delta_row_bytes` is THE shared byte
        model: `TableStore.publish`'s payload accounting and the bench
        reconcile against the same formula, the
        `expected_collective_bytes` discipline applied to the stream
        (docs/perf_model.md "Weight streaming"). Each group also
        reports its bucket's at-rest `storage_dtype` (ISSUE 15).

        Dynamic vocabulary (ISSUE 7): every group also carries the
        bucket's capacity accounting — `slack_rows` (growth rows the
        planner pre-reserved in this bucket, folded into rows_max),
        `occupancy` (live rows / capacity rows over the bucket's
        tables: managed tables report their binding's bound count when
        a `vocab.VocabManager` is passed, 1.0 means every row is live —
        the static-vocabulary reading), and `evictions_per_step`
        (measured demotions per maintain cycle from the manager, 0.0
        without one). Top-level totals aggregate the same three.

        Lookahead prefetch (ISSUE 9): with ``lookahead > 0`` every group
        also carries the overlap-window accounting of the pipelined step:

          prefetch_patch_rows_per_step  worst-case rows the engine's
                            correctness patch re-publishes per step — the
                            previous batch's touched rows all reappearing
                            in the prefetched batch, i.e. exactly
                            `touched_rows_per_step` (the dedup bound
                            carries over)
          prefetch_patch_bytes_per_step the patch recompute's wire cost
                            model at that bound: patched rows x (id wire
                            + one activation slot at the bucket's float
                            wire) — the EXTRA exchange traffic the
                            overlap window adds on top of the normal
                            (merely earlier) prefetched exchange

        Both are 0 at lookahead=0 (and under `stale_ok`, which skips the
        patch — the report models the bit-exact mode).

        Args:
          hotness: per-tp-input hotness override; defaults to the layer's
            input_max_hotness hints (unhinted inputs count as 1).
          hot_hit_rate: hot-shard hit-rate override (see above).
          batch: global batch size for the touched-row/delta-size model
            (default 1 = per-sample accounting, matching the id fields).
          vocab: optional `vocab.VocabManager` supplying measured
            occupancy/eviction numbers for managed tables.
          lookahead: pipeline depth for the prefetch-patch model (0 = the
            sequential step, patch fields report 0).
        Returns {"groups": [...], "true_ids", "exchanged_ids", "ratio",
        "exchanged_bytes", "true_bytes", "act_bytes", "act_bytes_f32",
        "act_wire_reduction", "wire_dtypes", "id_narrowed_groups",
        "hot_hit_ids", "true_ids_post_hot", "hot_hit_rates",
        "touched_rows_per_step", "delta_bytes_per_step", "occupancy",
        "slack_rows", "evictions_per_step", "lookahead",
        "prefetch_patch_rows_per_step", "prefetch_patch_bytes_per_step"}.
        """
        tp_inputs = self.strategy.input_groups[1]
        delta_dtype = (wire_ops.default_delta_dtype() if delta_dtype is None
                       else wire_ops.resolve_store_dtype(delta_dtype))
        if hotness is None:
            mh = self.input_max_hotness or [None] * self._n_inputs
            hotness = [mh[i] or 1 for i in tp_inputs]
        if len(hotness) != len(tp_inputs):
            raise ValueError(
                f"hotness has {len(hotness)} entries, expected "
                f"{len(tp_inputs)} (one per tp input)")

        def rate_for(b):
            if b not in self._hot_buckets:
                return None
            if isinstance(hot_hit_rate, dict):
                return float(hot_hit_rate.get(b, 0.0))
            if hot_hit_rate is not None:
                return float(hot_hit_rate)
            tr = self._hot_trackers.get(b)
            return tr.hit_rate if tr is not None else 0.0

        def bucket_vocab(b):
            """(occupancy, slack_rows, evictions_per_step) of bucket b:
            live rows / capacity rows over the bucket's tables (managed
            tables read their binding; static tables are fully live)."""
            bucket = self.plan.tp_buckets[b]
            tids = sorted({self.strategy.table_groups[1][pl.table_id]
                           for pl in self.plan.tp_placements
                           if pl.bucket == b})
            live = cap = 0
            ev = 0.0
            # per-STEP denominator: observing translate() calls (one per
            # training step in the fit wiring); maintain cycles are the
            # fallback for managers driven without translation
            steps = max(getattr(vocab, "observe_steps", 0)
                        or getattr(vocab, "maintain_cycles", 0), 1) \
                if vocab is not None else 1
            for gtid in tids:
                cfg = self.strategy.global_configs[gtid]
                rows = int(cfg["input_dim"])
                cap += rows
                mv = (vocab.vocabs.get(gtid)
                      if vocab is not None else None)
                if mv is not None:
                    live += 1 + mv.bound    # fallback row is always live
                    ev += mv.evictions / steps
                else:
                    # no manager over this table: its build rows are
                    # live, but any pre-reserved slack is DEAD capacity
                    # (nothing can ever bind it) — counting it live
                    # would report a misleading 1.0 for slack plans run
                    # without (or outside) a manager
                    live += rows - int(cfg.get("vocab_slack", 0))
            return ((live / cap) if cap else 1.0, bucket.slack_rows, ev)

        vocab_by_bucket = {b: bucket_vocab(b)
                           for b in range(len(self.plan.tp_buckets))}
        key = tuple((int(h), False) for h in hotness)
        groups, _ = self._exchange_groups_for_key(key)
        report, true_tot, ex_tot, hot_tot = [], 0, 0, 0
        touched_tot, delta_bytes_tot = 0, 0
        patch_rows_tot, patch_bytes_tot = 0, 0
        ex_bytes_tot, true_bytes_tot = 0, 0
        act_bytes_tot, act_bytes_f32_tot = 0, 0
        id_narrowed = []
        for gi, g in enumerate(groups):
            bucket = self.plan.tp_buckets[g.bucket]
            true_ids = sum(len(s) for s in g.rank_slots) * g.k
            ex_ids = self.world_size * g.f_max * g.k
            true_tot += true_ids
            ex_tot += ex_ids
            # byte-level accounting (ISSUE 5), per global sample: the id
            # wire at the bucket's (possibly int16-narrowed) id dtype
            # plus the mp->dp combined-activation return — one slot is
            # width elements combined (width*k for passthrough) — at the
            # bucket's float wire. FORWARD volume; the gradient
            # transpose doubles the activation term, and weighted inputs
            # add one more id-shaped float block at the same wire
            # (`weight_bytes_if_weighted`).
            w_out = bucket.width * (1 if bucket.combiner is not None
                                    else g.k)
            id_b = wire_ops.id_wire_itemsize(bucket.id_wire_dtype)
            wire_b = wire_ops.wire_itemsize(bucket.wire_dtype)
            act_ex = self.world_size * g.f_max * w_out
            act_true = sum(len(s) for s in g.rank_slots) * w_out
            ex_bytes = ex_ids * id_b + act_ex * wire_b
            true_bytes = true_ids * id_b + act_true * wire_b
            ex_bytes_tot += ex_bytes
            true_bytes_tot += true_bytes
            act_bytes_tot += act_ex * wire_b
            act_bytes_f32_tot += act_ex * 4
            if bucket.id_wire_dtype == "int16":
                id_narrowed.append(gi)
            entry = {
                "bucket": g.bucket, "hotness": g.k, "f_max": g.f_max,
                "features_per_rank": [len(s) for s in g.rank_slots],
                "true_ids": true_ids, "exchanged_ids": ex_ids,
                "wire_dtype": bucket.wire_dtype,
                "id_wire_dtype": bucket.id_wire_dtype,
                "storage_dtype": bucket.storage_dtype,
                "act_width": w_out,
                "act_bytes": act_ex * wire_b,
                "act_bytes_f32": act_ex * 4,
                "exchanged_bytes": ex_bytes,
                "true_bytes": true_bytes,
                "weight_bytes_if_weighted": ex_ids * wire_b,
                "occupancy": round(vocab_by_bucket[g.bucket][0], 4),
                "slack_rows": vocab_by_bucket[g.bucket][1],
                "evictions_per_step": round(vocab_by_bucket[g.bucket][2],
                                            4),
                "path_taken": self._exchange_path_taken.get(
                    (g.bucket, g.f_max, g.k)),
            }
            rate = rate_for(g.bucket)
            if rate is not None:
                hot_ids = int(round(true_ids * rate))
                hot_tot += hot_ids
                entry["hot_hit_ids"] = hot_ids
                entry["true_ids_post_hot"] = true_ids - hot_ids
            # touched-row / delta-size model (ISSUE 6): rows this group's
            # sparse update writes per step — post-hot ids scaled to the
            # batch, dedup-bounded by the bucket's total rows. The BYTE
            # model adds the hot-HIT rows back in: they skip the
            # canonical scatter but move the replicated hot shard, and
            # the published delta republishes their MERGED values
            # (touched_row_keys includes them) — bounded by the hot
            # shard's capacity, the most rows the merged view can move.
            post_hot = entry.get("true_ids_post_hot", true_ids)
            touched = min(int(batch) * post_hot,
                          self.world_size * max(bucket.rows_max, 1))
            hot_pub = min(int(batch) * entry.get("hot_hit_ids", 0),
                          bucket.hot_rows)
            entry["touched_rows_per_step"] = touched
            entry["delta_bytes_per_step"] = (
                (touched + hot_pub)
                * wire_ops.delta_row_bytes(bucket.width, delta_dtype))
            touched_tot += touched
            delta_bytes_tot += entry["delta_bytes_per_step"]
            # lookahead overlap-window model (ISSUE 9): worst case, every
            # row the previous step touched reappears in the prefetched
            # batch and is re-exchanged by the correctness patch — one id
            # + one activation slot per patched row at this bucket's wire
            patch_rows = touched if lookahead > 0 else 0
            entry["prefetch_patch_rows_per_step"] = patch_rows
            entry["prefetch_patch_bytes_per_step"] = (
                patch_rows * (id_b + w_out * wire_b))
            patch_rows_tot += patch_rows
            patch_bytes_tot += entry["prefetch_patch_bytes_per_step"]
            report.append(entry)
        return {"groups": report, "true_ids": true_tot,
                "exchanged_ids": ex_tot,
                "ratio": (ex_tot / true_tot) if true_tot else 1.0,
                "exchanged_bytes": ex_bytes_tot,
                "true_bytes": true_bytes_tot,
                "act_bytes": act_bytes_tot,
                "act_bytes_f32": act_bytes_f32_tot,
                # f32-wire bytes / actual-wire bytes of the dominant
                # (activation) exchange: 1.0 all-f32, 2.0 all-bf16 — the
                # statically auditable half-the-wire claim
                "act_wire_reduction": (act_bytes_f32_tot / act_bytes_tot
                                       if act_bytes_tot else 1.0),
                "wire_dtypes": {b: bk.wire_dtype for b, bk in
                                enumerate(self.plan.tp_buckets)},
                "id_narrowed_groups": id_narrowed,
                "hot_hit_ids": hot_tot,
                "true_ids_post_hot": true_tot - hot_tot,
                "hot_hit_rates": {b: rate_for(b) for b in self._hot_buckets},
                "touched_rows_per_step": touched_tot,
                "delta_bytes_per_step": delta_bytes_tot,
                "delta_dtype": delta_dtype,
                "storage_dtypes": {b: bk.storage_dtype for b, bk in
                                   enumerate(self.plan.tp_buckets)},
                "lookahead": int(lookahead),
                "prefetch_patch_rows_per_step": patch_rows_tot,
                "prefetch_patch_bytes_per_step": patch_bytes_tot,
                # capacity accounting (ISSUE 7), each bucket counted ONCE
                # (a bucket can serve several hotness groups): occupancy
                # capacity-weighted over buckets, slack/evictions summed
                "occupancy": round(
                    sum(vocab_by_bucket[b][0]
                        * max(self.plan.tp_buckets[b].rows_max, 1)
                        for b in vocab_by_bucket)
                    / max(sum(max(self.plan.tp_buckets[b].rows_max, 1)
                              for b in vocab_by_bucket), 1), 4)
                if vocab_by_bucket else 1.0,
                "slack_rows": sum(v[1] for v in vocab_by_bucket.values()),
                # top-level evictions come from the MANAGER, not a
                # bucket sum: a column-sliced table spanning several
                # buckets (unequal slice widths land in different
                # width-keyed buckets) would otherwise count each
                # logical eviction once per bucket. Per-group entries
                # keep the per-bucket view — each bucket genuinely
                # rewrites its slice of a rebound row.
                "evictions_per_step": round(
                    sum(mv.evictions for mv in vocab.vocabs.values())
                    / max(getattr(vocab, "observe_steps", 0)
                          or getattr(vocab, "maintain_cycles", 0), 1),
                    4) if vocab is not None else 0.0,
                "exchange_paths": dict(self._exchange_path_taken)}

    def residual_sort_scope(self, spec):
        """Scope the sort-folding spec over forwards traced inside it.

        ``spec = (optimizer_kind, dedup_strategy)`` — e.g. ("adagrad",
        "sort") — tells tapped forwards (``return_residuals=True``) to
        produce per-group/per-row-input `GroupSort` residual artifacts
        wherever `sparse_update`'s dispatch (mirrored statically by
        `sparse_update.update_consumes_sort`) or the tiled forward gather
        will consume them; ``None`` disables. `make_sparse_train_step`
        wraps its loss+grad region in this scope, so the production train
        step sorts each exchange group's ids exactly once (ISSUE 2). The
        scope is trace-time state on this layer instance — like
        `offload_lookup_scope`, re-entrant but not thread-safe."""

        @contextlib.contextmanager
        def scope():
            prev = self._residual_sort_spec
            self._residual_sort_spec = spec
            try:
                yield self
            finally:
                self._residual_sort_spec = prev
        return scope()

    def _fwd_tiled_active(self, bucket, k: int) -> bool:
        """Will `_group_lookup` take a sorted-gather Pallas path (tiled
        or the ISSUE 12 fused gather->combine) for this (bucket,
        hotness)? Mirrors its dispatch statically (trace-safe) — both
        paths consume the residual sort's inverse permutation."""
        path = os.environ.get("DET_LOOKUP_PATH", "auto")
        if path not in ("tiled", "fused") or not self.use_custom_kernel:
            return False
        # flatten path (no combiner at hotness > 1) has no sorted gather
        return bucket.combiner is not None or k == 1

    def _feature_major(self, bucket, k: int, batch: int) -> bool:
        """Is a group's id stream flattened (f, k, b)? The one answer the
        lookup, the folded sort and the update's contributions share
        (`sparse_update.feature_major_stream`: the bucket's width and the
        batch decide). Batch-major stays where the stream's consumer is
        batch-major by construction: an offloaded bucket's host lookup and
        apply, and the sorted-gather kernels, which unpermute by the
        folded sort."""
        if bucket.offload and self._offload_enabled:
            return False
        if self._fwd_tiled_active(bucket, k):
            return False
        return sparse_update_ops.feature_major_stream(bucket.width, batch)

    def _fold_sort(self, bucket, grp, ids: jax.Array,
                   want_inv: bool) -> GroupSort:
        """The folded canonical sort of one group's exchanged ids
        [B, f, k], over the stream in the order `_group_contrib` builds
        the contributions in."""
        fm = self._feature_major(bucket, grp.k, ids.shape[0])
        return canonical_id_sort(_as_stream(ids, fm),
                                 max(bucket.rows_max, 1), want_inv=want_inv)

    def _sort_plan(self, groups, spec) -> List[Optional[str]]:
        """Per exchange group: None (no artifact), "plain" (sid/perm/
        seg_start for the sparse update) or "inv" (+ inverse permutation,
        consumed by the tiled forward gather's unpermute). Buckets whose
        update concatenates several groups keep None — a per-group sort
        cannot serve the concatenated dedup, and applying the optimizer
        per group instead would change adagrad/adam numerics."""
        if spec is None:
            return [None] * len(groups)
        opt_kind, strategy = spec
        per_bucket: dict = {}
        for grp in groups:
            per_bucket[grp.bucket] = per_bucket.get(grp.bucket, 0) + 1
        plan: List[Optional[str]] = []
        for grp in groups:
            bucket = self.plan.tp_buckets[grp.bucket]
            if bucket.offload and self._offload_enabled:
                plan.append(None)    # host apply path keeps its own dedup
                continue
            fwd_inv = self._fwd_tiled_active(bucket, grp.k)
            upd = (per_bucket[grp.bucket] == 1
                   and sparse_update_ops.update_consumes_sort(
                       opt_kind, strategy, max(bucket.rows_max, 1),
                       bucket.width))
            plan.append("inv" if fwd_inv else ("plain" if upd else None))
        return plan

    def _row_sort_plan(self, spec) -> List[Optional[str]]:
        """Per row-sliced input: "plain" when its table's update will
        consume the artifact (single-input tables only — shared tables
        concatenate, see `_sort_plan`)."""
        n = len(self.strategy.input_groups[2])
        if spec is None:
            return [None] * n
        opt_kind, strategy = spec
        counts: dict = {}
        for j in range(n):
            t = self.strategy.map_groups[2][j]
            counts[t] = counts.get(t, 0) + 1
        plan: List[Optional[str]] = []
        for j in range(n):
            t = self.strategy.map_groups[2][j]
            rt = self.plan.row_tables[t]
            ok = (counts[t] == 1
                  and sparse_update_ops.update_consumes_sort(
                      opt_kind, strategy, max(rt.rows_max, 1), rt.width))
            plan.append("plain" if ok else None)
        return plan

    @staticmethod
    def _stack_sort(sort_g: Optional[GroupSort]) -> Optional[GroupSort]:
        """Add the leading per-device axis residual arrays carry."""
        if sort_g is None:
            return None
        return GroupSort(
            sort_g.sid[None], sort_g.perm[None], sort_g.seg_start[None],
            None if sort_g.inv is None else sort_g.inv[None])

    @staged("lookup")
    def _group_lookup(self, table: jax.Array, ids: jax.Array,
                      weights: Optional[jax.Array],
                      combiner: Optional[str],
                      presorted: Optional[GroupSort] = None,
                      feature_major: bool = False) -> jax.Array:
        """Local fused-bucket lookup + combine: ids [B, f, k] -> [B, f, wf].

        `feature_major` (`_feature_major`'s answer for this group): the
        XLA gather and combine run over the stream flattened (f, k, b),
        `_lookup_feature_major`; the Pallas lookup kernels keep their
        batch-major order, whichever the stream's.

        Path selection (overridable via DET_LOOKUP_PATH=auto|xla|pallas for
        hardware A/B): combined sum/mean groups route through the Pallas
        fused kernel on TPU (the hot-loop equivalent of the reference's CUDA
        combiner, cu:175-336) — in 'auto' only for multi-hot (k > 1), under
        'pallas' for one-hot gathers as well; 'xla' forces take + reduce,
        which XLA fuses. (Offloaded buckets never reach here — their lookups
        run host-side in `_host_group_exchange`.)

        `presorted`: a GroupSort of this group's flattened ids (the tapped
        forward's residual artifact). Only the tiled gather consumes it
        (and only when it carries `inv`) — the sort + inverse-permute it
        would otherwise compute itself fold onto the residual sort.
        """
        b_sz, f, k = ids.shape
        path = os.environ.get("DET_LOOKUP_PATH", "auto")
        if combiner is None and k == 1 and path in ("pallas", "tiled",
                                                    "fused"):
            combiner = "sum"     # identical result at hotness 1
        if (path == "fused" and combiner in ("sum", "mean")
                and self.use_custom_kernel):
            # ISSUE 12 fused gather->combine (ops/pallas_tiled.
            # fused_lookup_combine): one weighted-gather kernel pass +
            # scatter-free unpermute + plain hotness sum, replacing the
            # descriptor-bound XLA table gather AND the separate combine
            # einsum. Off-TPU it runs in interpret mode (tests). The
            # constructor opt-out wins over the knob.
            from distributed_embeddings_tpu.ops import pallas_tiled
            if not pallas_lookup.is_tpu_backend():
                _warn_interpret_once("fused")
            w = (weights if weights is not None
                 else jnp.ones((b_sz, f, k), jnp.float32))
            ps = None
            if presorted is not None and presorted.inv is not None:
                ps = (presorted.sid, presorted.perm, presorted.inv)
            out = pallas_tiled.fused_lookup_combine(
                table, ids.reshape(b_sz * f, k), w.reshape(b_sz * f, k),
                combiner, presorted=ps)
            return self._cast(out.reshape(b_sz, f, out.shape[-1]))
        if (path == "tiled" and combiner in ("sum", "mean")
                and self.use_custom_kernel):
            # round-4 tiled one-hot-matmul gather (ops/pallas_tiled.py):
            # sort + block-streamed table walk, replacing the ~22 ns/row
            # descriptor-bound XLA row gather. Off-TPU it runs in interpret
            # mode (tests). Gated on use_custom_kernel like the pallas path
            # — the constructor opt-out wins over the env knob (ADVICE r4).
            from distributed_embeddings_tpu.ops import pallas_tiled
            if not pallas_lookup.is_tpu_backend():
                _warn_interpret_once("tiled")
            w = (weights if weights is not None
                 else jnp.ones((b_sz, f, k), jnp.float32))
            ps = None
            if presorted is not None and presorted.inv is not None:
                ps = (presorted.sid, presorted.perm, presorted.inv)
            out = pallas_tiled.tiled_embedding_lookup(
                table, ids.reshape(b_sz * f, k), w.reshape(b_sz * f, k),
                combiner, presorted=ps)
            return self._cast(out.reshape(b_sz, f, out.shape[-1]))
        want_pallas = (self.use_custom_kernel
                       and pallas_lookup.is_tpu_backend()
                       and combiner in ("sum", "mean")
                       and path != "xla"
                       and (k > 1 or path == "pallas"))
        if feature_major and not (want_pallas and pallas_lookup.has_kernel(
                table.shape[0], table.shape[1], table.dtype)):
            return self._lookup_feature_major(table, ids, weights, combiner)
        if want_pallas:
            w = (weights if weights is not None
                 else jnp.ones((b_sz, f, k), jnp.float32))
            out = pallas_lookup.fused_embedding_lookup(
                table, ids.reshape(b_sz * f, k), w.reshape(b_sz * f, k),
                combiner)
            return self._cast(out.reshape(b_sz, f, out.shape[-1]))
        emb = self._cast(jnp.take(table, ids, axis=0))      # [B, f, k, w]
        return _combine(emb, weights, combiner)

    def _lookup_feature_major(self, table: jax.Array, ids: jax.Array,
                              weights: Optional[jax.Array],
                              combiner: Optional[str]) -> jax.Array:
        """`_group_lookup`'s XLA gather and combine for a narrow bucket:
        ids [B, f, k] -> [B, f, wf], the same rows and the same products
        as `_combine`'s, gathered in the stream's (f, k, b) order. The
        chip stores such a table column-major, so the gather's result is
        `[w, f, k, B]` there: the hotness sum is k adds of `[w, B]` slabs,
        one input's output a contiguous lane range, and the logical
        transpose back to [B, f, wf] a bitcast, the consumer wanting the
        batch minor-most too."""
        b_sz, f, k = ids.shape
        emb = self._cast(jnp.take(table, _as_stream(ids, True), axis=0))
        if combiner is None:                              # [f, k, B, w]
            return jnp.moveaxis(emb, 2, 0).reshape(b_sz, f, -1)
        eff_w, scale = _effective_weights(weights, k, combiner)
        if eff_w is not None:
            emb = emb * _as_stream(eff_w, True).astype(emb.dtype)[..., None]
        out = jnp.sum(emb, axis=1)                        # [f, B, w]
        if scale != 1.0:
            out = out * jnp.asarray(scale, out.dtype)
        return jnp.swapaxes(out, 0, 1)

    def _cast(self, x: jax.Array) -> jax.Array:
        """Cast a lookup result to compute_dtype (mixed precision no-op when
        unset)."""
        if self.compute_dtype is not None and x.dtype != self.compute_dtype:
            return x.astype(self.compute_dtype)
        return x

    # -------------------------------------------------------------- forward
    def _my_index(self):
        if self.world_size == 1:
            return jnp.int32(0)
        return lax.axis_index(self.axis)

    def _device_const(self, const: np.ndarray):
        """Select this device's row of a [world, ...] planning constant."""
        return jnp.take(jnp.asarray(const), self._my_index(), axis=0)

    def _forward_local(self, dp_params, tp_params, row_params,
                       dp_in, group_ids, group_w, row_in, groups,
                       taps=None, want_res=False, sort_plan=None,
                       row_sort_plan=None, hot_params=None, tp_scales=None):
        """The per-device forward (shard_map body when world > 1).

        Args:
          dp_in / row_in: lists of (ids [B_l, k], weights or None) per input.
          group_ids: per exchange group, stacked ids [B_l, n_g, k_g].
          group_w: matching weights [B_l, n_g, k_g] or None per group.
          groups: the static _ExchangeGroup records.
          taps: optional {'tp': [[1, B, f, w_out]...], 'row': [...]} zero
            arrays added to each bucket-lookup / row-partial output; their
            cotangents under autodiff are exactly the per-device output
            gradients `sparse_update` consumes (no dense table grads).
          want_res: also return TapResiduals arrays (post-exchange ids +
            effective weights).
          sort_plan / row_sort_plan: static per-group / per-row-input sort
            production plan (see `_sort_plan`) — which GroupSort residuals
            to build, and whether the tiled forward consumes them.
          tp_scales: per-bucket stacked per-row scale shards (None at f32
            or host-offloaded buckets) — quantized HBM-resident buckets
            (ISSUE 17) decode at gather time via `_tp_group_out`.

        Returns (dp_outs, ex_list, row_outs, off_ids, off_w, res):
          dp_outs: [B_l, w] (or [B_l, K, w]) per dp input
          ex_list: per group [world_src, B_l, f_max_g, wf]; None at offloaded
            groups (filled by the caller via _host_group_exchange)
          row_outs: [B_l, ...] partial sums scattered over batch.
          off_ids / off_w: per group the exchanged ids / effective weights
            ([1, ...]-stacked) for offloaded groups, None elsewhere.
          res: (tp_ids, tp_w, row_ids, row_w, tp_sort, row_sort) lists
            ([1, ...]-stacked) or None when want_res is False.
        """
        world = self.world_size
        strat = self.strategy

        # ---- data-parallel tables: plain local lookup on replicated params
        dp_outs = []
        for j, (ids, weights) in enumerate(dp_in):
            t_dp = strat.map_groups[0][j]
            cfg = strat.dp_configs[t_dp]
            table = dp_params[t_dp]
            layer = self._dp_custom_layers.get(t_dp)
            if layer is not None:
                # custom layer_class: run the USER's forward on the prepared
                # [B_l, k] ids (reference :820-834 semantics). Contract:
                # params stay {"embeddings": [V, w]}; output rank must match
                # the stock layer ([B, w] with a combiner, [B, k, w] without)
                # so the shard_map out_specs hold.
                if weights is not None:
                    raise NotImplementedError(
                        f"dp table {t_dp}: (ids, weights) inputs are not "
                        "supported for custom embedding layer classes — "
                        "the layer's own __call__ defines its semantics")
                # custom outputs honor the compute_dtype policy like stock
                # tables (ADVICE r5): without the cast, a mixed-precision
                # model would see f32 here and bf16 everywhere else
                with stage("lookup"):
                    out = self._cast(layer({"embeddings": table}, ids))
                want_rank = 2 if cfg.get("combiner") else 3
                if out.ndim != want_rank:
                    raise ValueError(
                        f"dp table {t_dp}: custom layer forward returned "
                        f"rank-{out.ndim} output, expected rank "
                        f"{want_rank} ([batch, width] with a combiner, "
                        "[batch, hotness, width] without)")
                dp_outs.append(out)
                continue
            with stage("lookup"):
                emb = self._cast(jnp.take(table, ids, axis=0))  # [B_l, k, w]
                dp_outs.append(_combine(emb, weights, cfg.get("combiner")))

        # ---- table-parallel: per-group all_to_all id exchange (dp->mp),
        # local fused lookup, all_to_all back (mp->dp). Each destination
        # receives only ids for features it owns (reference hvd.alltoall
        # with splits, :211) — not an all_gather of everything.
        ex_list = []
        off_ids: List[Optional[jax.Array]] = []
        off_w: List[Optional[jax.Array]] = []
        tp_res_ids: List[jax.Array] = []
        tp_res_w: List[Optional[jax.Array]] = []
        tp_res_sort: List[Optional[GroupSort]] = []
        hot_res_pos: List[Optional[jax.Array]] = []
        hot_res_w: List[Optional[jax.Array]] = []
        hot_taps = (taps or {}).get("hot") if taps is not None else None
        for g, grp in enumerate(groups):
            ids = group_ids[g]                               # [B_l, n_g, k]
            blocal = ids.shape[0]
            bucket = self.plan.tp_buckets[grp.bucket]
            offloaded = bucket.offload and self._offload_enabled
            # hot-row replication (ISSUE 4): split the id stream against
            # the bucket's replicated hot shard BEFORE the exchange — hit
            # lanes are served locally from the [H, w] hot param (no
            # all_to_all, no big-table gather); miss lanes take the stock
            # exchange with hits masked to zero-weight id-0 lanes
            hot = (hot_params[grp.bucket]
                   if (hot_params is not None and bucket.hot_rows > 0
                       and not offloaded) else None)
            hot_info = None
            with stage("ids"):
                if hot is not None:
                    send_m, w_send_m, hot_pos, hot_w = self._hot_split_send(
                        grp, ids, group_w[g], world, blocal, hot)
                    ids_x, w_x = self._exchange_send(grp, send_m, w_send_m,
                                                     world, blocal)
                    if w_x is None:
                        # unweighted input: the sentinel is receiver-
                        # detectable — real ids are < their lane's segment
                        # rows <= rows_max and hit lanes are EXACTLY rows_max
                        # — so the 0/scale effective weights reconstruct
                        # locally, bit-identical to exchanging them. (An
                        # INVALID input id == rows_max reads as weight 0 here
                        # where the baseline clamps it onto the last row;
                        # ids past rows_max keep the baseline clamp.)
                        _, scale = _effective_weights(None, grp.k,
                                                      bucket.combiner)
                        w_x = jnp.where(
                            ids_x == jnp.int32(max(bucket.rows_max, 1)),
                            jnp.float32(0.0), jnp.float32(scale))
                    hot_info = (hot_pos, hot_w)
                elif self._use_ragged_exchange(grp, world):
                    ids_x, w_x = self._ragged_id_exchange(
                        grp, ids, group_w[g], world, blocal)
                else:
                    ids_x, w_x = self._padded_id_exchange(
                        grp, ids, group_w[g], world, blocal)
                offs = self._device_const(grp.offs)              # [f_max]
                ids_x = ids_x + offs[None, :, None].astype(ids_x.dtype)
            # sort folding: ONE canonical sort of this group's exchanged id
            # stream, consumed by the tiled forward gather below (when the
            # plan says "inv") and by the sparse update via the residuals
            sort_g = None
            if (want_res and sort_plan is not None and sort_plan[g]
                    and not offloaded):
                with stage("dedup"):
                    sort_g = self._fold_sort(
                        bucket, grp, ids_x,
                        want_inv=(sort_plan[g] == "inv"))
            if offloaded:
                # id exchange happens on-device (above); the lookup itself
                # runs host-side outside the shard_map (reference /CPU:0
                # lookup :829-831) — export the exchanged ids/weights
                with stage("ids"):
                    eff_w, _ = _effective_weights(w_x, grp.k,
                                                  bucket.combiner)
                    off_ids.append(ids_x[None].astype(jnp.int32))
                    off_w.append(None if eff_w is None else eff_w[None])
                ex_list.append(None)
            elif hot_info is not None:
                off_ids.append(None)
                off_w.append(None)
                # miss path: w_x is already the EFFECTIVE weight (scale
                # folded, hits zeroed) — plain weighted sum, tap as usual.
                # The gather gets sentinel lanes CLAMPED: jnp.take's
                # default OOB mode is fill-with-NaN, and 0 * NaN = NaN —
                # the residual/sort streams keep the raw sentinel so the
                # update still drops those lanes outright.
                with stage("lookup"):
                    ids_lu = jnp.minimum(ids_x, max(bucket.rows_max, 1) - 1)
                    out = self._group_lookup(
                        tp_params[grp.bucket][0], ids_lu, w_x, "sum",
                        presorted=sort_g,
                        feature_major=self._feature_major(
                            bucket, grp.k, ids_lu.shape[0]))
                    tap_g = None if taps is None else taps["tp"][g]
                    if tap_g is not None:
                        out = out + tap_g[0].astype(out.dtype)
                ex = self._tp_bucket_exchange(out, bucket.wire_dtype)
                hot_tap = None if hot_taps is None else hot_taps[g]
                contrib = self._hot_contrib(grp, bucket, hot, hot_info[0],
                                            hot_info[1], hot_tap)
                with stage("lookup"):
                    ex_list.append(ex + contrib.astype(ex.dtype))
            else:
                off_ids.append(None)
                off_w.append(None)
                out = self._tp_group_out(
                    tp_params, grp, ids_x, w_x,
                    None if taps is None else taps["tp"][g],
                    presorted=sort_g,
                    scale_s=(None if tp_scales is None
                             else tp_scales[grp.bucket]))
                ex_list.append(self._tp_bucket_exchange(
                    out, bucket.wire_dtype))
            if want_res:
                with stage("ids"):
                    if hot_info is not None:
                        # w_x IS the effective weight stream (see above)
                        eff_w = w_x
                    else:
                        eff_w, _ = _effective_weights(w_x, grp.k,
                                                      bucket.combiner)
                    tp_res_ids.append(ids_x[None].astype(jnp.int32))
                    tp_res_w.append(None if eff_w is None else eff_w[None])
                    tp_res_sort.append(self._stack_sort(sort_g))
                    hot_res_pos.append(None if hot_info is None
                                       else hot_info[0][None])
                    hot_res_w.append(None if hot_info is None
                                     else hot_info[1][None])

        # ---- row-sliced tables: all_gather ids, masked lookup, psum_scatter
        row_outs, row_res = self._row_slice_local(
            row_params, row_in,
            None if taps is None else taps["row"], want_res,
            sort_plan=row_sort_plan)
        res = ((tp_res_ids, tp_res_w) + row_res[:2]
               + (tp_res_sort, row_res[2])
               + (hot_res_pos, hot_res_w)) if want_res else None
        return dp_outs, ex_list, row_outs, off_ids, off_w, res

    def _use_ragged_exchange(self, grp, world: int) -> bool:
        """Per-group dp->mp exchange policy. DET_RAGGED_EXCHANGE '1'
        forces the true-splits exchange, '0' forces padded; 'auto' (the
        default) takes true-splits on the TPU backend when the group's
        padded wire volume exceeds 1.5x its true id volume (static
        accounting, same arithmetic as exchange_padding_report — e.g.
        tiny/comm_balanced pads 2.54x, jumbo 1.16x). The automatic choice
        and the native op ran across four v5e chips and matched the
        one-device run (chip_smoke.py --chips 4, PR 22); the 1.5x
        threshold itself has no timing behind it (ROADMAP S4)."""
        if world <= 1:
            return False
        mode = os.environ.get("DET_RAGGED_EXCHANGE", "auto")
        if mode in ("0", "1"):
            ragged = mode == "1"
        elif jax.default_backend() != "tpu":
            ragged = False    # CPU emulation path is for tests only
        else:
            true_ids = sum(len(s) for s in grp.rank_slots) * grp.k
            padded_ids = world * grp.f_max * grp.k
            ragged = padded_ids > 1.5 * max(true_ids, 1)
        # attributable perf (ADVICE r4): record the decision per group so a
        # hardware regression can be traced to the path that ran — surfaced
        # in exchange_padding_report()["exchange_paths"] and the debug log
        decision = "ragged" if ragged else "padded"
        key = (grp.bucket, grp.f_max, grp.k)
        if self._exchange_path_taken.get(key) != decision:
            self._exchange_path_taken[key] = decision
            logging.getLogger(__name__).debug(
                "exchange group bucket=%d f_max=%d k=%d -> %s "
                "(DET_RAGGED_EXCHANGE=%s)", grp.bucket, grp.f_max, grp.k,
                decision, mode)
        return ragged

    def _padded_id_exchange(self, grp, ids, w, world, blocal):
        """Fixed-shape dp->mp id (+weight) exchange: dense
        [world, B_l, f_max, k] blocks through lax.all_to_all (padding
        bounded by the comm_balanced placement).

        Wire formats (ISSUE 5, from the bucket's plan fields): the id
        block narrows to int16 where the planner proved the key space
        fits (losslessly — see ops/wire.py encode_ids), and the weight
        block rides the bucket's float wire. Both decode back to full
        width before any local math."""
        bucket = self.plan.tp_buckets[grp.bucket]
        sel = jnp.asarray(grp.sel.reshape(-1))           # [world*f_max]
        send = jnp.take(ids, sel, axis=1).reshape(
            blocal, world, grp.f_max, grp.k)
        send = jnp.moveaxis(send, 1, 0)                  # [world, B_l, f, k]
        w_x = None
        if w is not None:
            w_send = jnp.take(w, sel, axis=1).reshape(
                blocal, world, grp.f_max, grp.k)
            w_send = jnp.moveaxis(w_send, 1, 0)
        if world > 1:
            recv = wire_ops.wire_id_all_to_all(send, self.axis,
                                               bucket.id_wire_dtype)
            if w is not None:
                w_recv = wire_ops.wire_all_to_all(w_send, self.axis,
                                                  bucket.wire_dtype)
                w_x = w_recv.reshape(-1, grp.f_max, grp.k)
        else:
            recv = send
            if w is not None:
                w_x = w_send.reshape(-1, grp.f_max, grp.k)
        return recv.reshape(-1, grp.f_max, grp.k), w_x   # [B, f, k]

    def _ragged_exchange_rows(self, grp, operand, world, blocal):
        """One true-splits exchange of destination-major flat rows
        ``operand [S, blocal*k]`` -> receive layout [B, f_max, k] — the
        shared core of `_ragged_id_exchange` and the hot split's
        `_exchange_send` (ONE copy of the split metadata, the
        DET_RAGGED_NATIVE choice and the receive-layout reassembly, so
        the two callers cannot drift).

        The operand crosses at its bucket's wire format (ISSUE 5),
        dispatched by dtype: int operands take the id wire (int16 where
        the planner proved the range), float operands the float wire.
        The float encode/decode pair is differentiable, so the reverse
        ragged exchange of the weight gradient rides the same wire
        (no custom_vjp needed — the cast transposes bound it)."""
        bucket = self.plan.tp_buckets[grp.bucket]
        orig_dtype = operand.dtype
        is_int = jnp.issubdtype(orig_dtype, jnp.integer)
        if is_int:
            operand = wire_ops.encode_ids(operand, bucket.id_wire_dtype)
        else:
            operand = wire_ops.encode_fwd(operand, bucket.wire_dtype)
        me = self._my_index()
        f_pr = jnp.asarray(grp.f_per_rank)
        in_off = jnp.asarray(grp.in_offsets)
        out_off = jnp.full((world,), me * grp.f_max, jnp.int32)
        recv_sz = jnp.full((world,), jnp.take(f_pr, me), jnp.int32)
        native_env = os.environ.get("DET_RAGGED_NATIVE", "auto")
        native = (pallas_lookup.is_tpu_backend() if native_env == "auto"
                  else native_env == "1")
        out_buf = jnp.zeros((world * grp.f_max, blocal * grp.k),
                            operand.dtype)
        recv = _ragged_exchange_op(operand, out_buf, in_off, f_pr,
                                   out_off, recv_sz, self.axis, native)
        if is_int:
            recv = wire_ops.decode_ids(recv, bucket.id_wire_dtype,
                                       orig_dtype)
        else:
            recv = recv.astype(orig_dtype)
        recv = recv.reshape(world, grp.f_max, blocal, grp.k)
        return jnp.moveaxis(recv, 2, 1).reshape(-1, grp.f_max, grp.k)

    def _ragged_id_exchange(self, grp, ids, w, world, blocal):
        """True-splits dp->mp exchange (DET_RAGGED_EXCHANGE=1): each
        destination's features travel unpadded — sum_r f_r rows on the
        wire instead of world*f_max (the reference's hvd.alltoall(splits)
        volume, dist_model_parallel.py:169-288). Weights (explicit or the
        synthesized ragged/sparse masks) ride the same metadata;
        `lax.ragged_all_to_all` carries jvp+transpose rules, so the weight
        gradient flows back through the reverse exchange. The receive
        buffer keeps the [world, f_max] layout (static shapes; unwritten
        slots read as id/weight 0 and are never consumed downstream), so
        everything after the exchange — offsets, lookup, output exchange,
        residuals — is byte-identical to the padded path."""
        flat_sel = jnp.asarray(grp.flat_sel)             # [S]
        s_rows = int(grp.f_per_rank.sum())

        def exchange(x):                                 # [B_l, n_g, k]
            send = jnp.take(x, flat_sel, axis=1)         # [B_l, S, k]
            send = jnp.moveaxis(send, 1, 0).reshape(
                s_rows, blocal * grp.k)
            return self._ragged_exchange_rows(grp, send, world, blocal)

        return exchange(ids), None if w is None else exchange(w)

    # ------------------------------------------- hot-row split (ISSUE 4)
    def _hot_group_meta(self, grp):
        """Static per-group hot-split constants: ``base [world, f_max]``
        — each send lane's flat key base ``rank * rows_max + row_offset``
        — ``lane_valid [world, f_max]`` masking the f_max padding lanes
        (their sel replicates input 0; without the mask a padding lane
        could alias a hot key and pollute the split), and ``lane_rows
        [world, f_max]`` — each lane's backing table-segment row count,
        bounding which ids are in range for THAT lane (an over-range id
        would fold onto a neighboring segment's or the next rank's key
        space and could falsely hit a foreign resident row). Memoized per
        group object (groups live forever in _groups_cache)."""
        hit = self._hot_meta_cache.get(id(grp))
        if hit is not None:
            return hit
        bucket = self.plan.tp_buckets[grp.bucket]
        rows_max = max(bucket.rows_max, 1)
        world = self.world_size
        rows_of = {(pl.rank, pl.row_offset): pl.rows
                   for pl in self.plan.tp_placements
                   if pl.bucket == grp.bucket}
        base = np.zeros((world, grp.f_max), np.int64)
        lane_valid = np.zeros((world, grp.f_max), bool)
        lane_rows = np.zeros((world, grp.f_max), np.int32)
        for r in range(world):
            base[r, :] = r * rows_max
            for j in range(int(grp.f_per_rank[r])):
                base[r, j] += int(grp.offs[r, j])
                lane_valid[r, j] = True
                lane_rows[r, j] = rows_of.get((r, int(grp.offs[r, j])), 0)
        res = (base.astype(np.int32), lane_valid, lane_rows)
        self._hot_meta_cache[id(grp)] = res
        return res

    def _hot_split_send(self, grp, ids, w, world, blocal, hot):
        """Pre-exchange hot-membership split of one exchange group.

        Builds the destination-major send block [world, B_l, f_max, k]
        (ids + EFFECTIVE weights — the explicit weighted-sum form with the
        static mean scale folded in, so hit and miss contributions share
        the baseline's denominators), classifies every lane against the
        bucket's sorted hot membership (`sorted_member_positions`: a
        searchsorted — zero sort ops), and SENTINEL-masks hit lanes out
        of the miss path: their ids go to `rows_max` (post-offset ids
        land >= rows_max — the canonical OOB sentinel every lookup path
        clamps and the sparse update DROPS outright) and their weights to
        0. The canonical rows of resident ids are therefore never even
        touched by the update — which matters for lazy adam, whose
        moment decay runs on every *touched* row regardless of the
        gradient value (a zero-contribution touch at a real row would
        silently diverge its moments from the hot-less baseline).

        Returns (send_ids_m, send_w_m, hot_pos, hot_w): masked send block
        plus, per lane, the hot-shard row position (sentinel H on miss)
        and the effective hit weight (0 on miss).
        """
        bucket = self.plan.tp_buckets[grp.bucket]
        h_cap = bucket.hot_rows
        rows_max = max(bucket.rows_max, 1)
        eff, scale = _effective_weights(w, grp.k, bucket.combiner)
        sel = jnp.asarray(grp.sel.reshape(-1))
        send = jnp.take(ids, sel, axis=1).reshape(
            blocal, world, grp.f_max, grp.k)
        send = jnp.moveaxis(send, 1, 0).astype(jnp.int32)
        if eff is None:
            # unweighted input: every lane's effective weight is the
            # static `scale`, so there is nothing worth exchanging — hit
            # weights below are the scale constant, and the miss weights
            # reconstruct receiver-side from the sentinel (see the
            # caller), sparing a dense f32 all_to_all the stock
            # unweighted exchange never pays
            w_send = None
        else:
            wsum = eff * jnp.asarray(scale, jnp.float32)  # [B_l, n_g, k]
            w_send = jnp.moveaxis(jnp.take(wsum, sel, axis=1).reshape(
                blocal, world, grp.f_max, grp.k), 1, 0)
        base, lane_valid, lane_rows = self._hot_group_meta(grp)
        keys = send + jnp.asarray(base)[:, None, :, None]
        pos, hit = embedding_ops.sorted_member_positions(hot["ids"], keys)
        # out-of-range input ids fold onto a NEIGHBORING segment's (or the
        # next/previous rank's) key range and could alias a resident key
        # there — serving a foreign table's hot row with full weight where
        # the baseline gather handles the invalid id deterministically.
        # Invalid ids always miss: 0 <= id < this lane's segment rows.
        hit = (hit & jnp.asarray(lane_valid)[:, None, :, None]
               & (send >= 0)
               & (send < jnp.asarray(lane_rows)[:, None, :, None]))
        send_m = jnp.where(hit, jnp.int32(rows_max), send)
        hot_pos = jnp.where(hit, pos, jnp.int32(h_cap))
        if w_send is None:
            w_send_m = None
            hot_w = jnp.where(hit, jnp.float32(scale), jnp.float32(0.0))
        else:
            w_send_m = jnp.where(hit, 0.0, w_send)
            hot_w = jnp.where(hit, w_send, 0.0)
        return send_m, w_send_m, hot_pos, hot_w

    def _exchange_send(self, grp, send, w_send, world, blocal):
        """dp->mp exchange of a pre-built destination-major send block
        [world, B_l, f_max, k] (+ weights) — the hot-split form of
        `_padded_id_exchange` / `_ragged_id_exchange` (the split must mask
        per (destination, slot) lane, which only exists post-`sel`).
        Returns (ids_x [B, f, k], w_x [B, f, k]) matching the stock
        exchanges byte for byte (incl. their wire formats, ISSUE 5)."""
        bucket = self.plan.tp_buckets[grp.bucket]
        if not self._use_ragged_exchange(grp, world):
            if world > 1:
                recv = wire_ops.wire_id_all_to_all(send, self.axis,
                                                   bucket.id_wire_dtype)
                w_recv = (None if w_send is None else
                          wire_ops.wire_all_to_all(w_send, self.axis,
                                                   bucket.wire_dtype))
            else:
                recv, w_recv = send, w_send
            return (recv.reshape(-1, grp.f_max, grp.k),
                    None if w_recv is None else
                    w_recv.reshape(-1, grp.f_max, grp.k))
        # ragged: destination-major flat rows (r, j < f_r) selected out of
        # the send block — same operand the stock ragged path builds
        s_rows = int(grp.f_per_rank.sum())
        flat_rows = (np.concatenate(
            [r * grp.f_max + np.arange(n, dtype=np.int64)
             for r, n in enumerate(grp.f_per_rank)]).astype(np.int32)
            if s_rows else np.zeros((0,), np.int32))

        def exchange(x):                          # [world, B_l, f_max, k]
            flat = jnp.transpose(x, (0, 2, 1, 3)).reshape(
                world * grp.f_max, blocal * grp.k)
            op = jnp.take(flat, jnp.asarray(flat_rows), axis=0)
            return self._ragged_exchange_rows(grp, op, world, blocal)

        return exchange(send), (None if w_send is None
                                else exchange(w_send))

    @staged("lookup")
    def _hot_contrib(self, grp, bucket, hot, hot_pos, hot_w, hot_tap):
        """The hit lanes' locally-computed output contribution
        [world, B_l, f_max, w]: gather from the replicated hot shard,
        weighted-sum over hotness — added to the returned exchange block
        (same layout), so hits never touch the exchange or the big table.
        `hot_tap` (the hot-shard tap) rides the addition; its cotangent is
        exactly the per-(serving-rank, sample, slot) output gradient the
        replicated hot update consumes."""
        ph = jnp.minimum(hot_pos, bucket.hot_rows - 1)
        rows = self._cast(jnp.take(hot["rows"], ph, axis=0))
        contrib = jnp.einsum("rbfk,rbfkw->rbfw",
                             hot_w.astype(rows.dtype), rows)
        if hot_tap is not None:
            contrib = contrib + hot_tap.astype(contrib.dtype)
        return contrib

    @staged("lookup")
    def _tp_group_out(self, tp_params, grp, ids_x, w_x, tap, presorted=None,
                      scale_s=None):
        """One exchange group's local bucket output [B, f, w_out], via the
        explicit weighted-sum form (so tapped and untapped paths share
        numerics), plus the optional tap perturbation.

        scale_s: the bucket's stacked per-row scale shard for quantized
        HBM-RESIDENT storage (ISSUE 17) — the payload rows and their
        scales gather together and decode right here, inside the jitted
        program (the device twin of `_host_group_exchange`'s
        decode-at-gather). The kernel lookup paths (pallas/tiled/fused)
        are f32-table programs, so quantized buckets take the explicit
        gather+combine form — the same numerics as `_group_lookup`'s XLA
        route with one decode inserted before the cast."""
        bucket = self.plan.tp_buckets[grp.bucket]
        eff_w, scale = _effective_weights(w_x, grp.k, bucket.combiner)
        if scale_s is not None:
            emb = jnp.take(tp_params[grp.bucket][0], ids_x, axis=0)
            srow = jnp.take(scale_s[0], ids_x, axis=0)
            emb = self._cast(wire_ops.decode_rows(
                emb, srow, bucket.storage_dtype))
            out = _combine(emb, eff_w,
                           None if bucket.combiner is None else "sum")
        else:
            out = self._group_lookup(
                tp_params[grp.bucket][0], ids_x, eff_w,
                None if bucket.combiner is None else "sum",
                presorted=presorted,
                feature_major=self._feature_major(bucket, grp.k,
                                                  ids_x.shape[0]))
        if scale != 1.0:
            out = out * jnp.asarray(scale, out.dtype)
        if tap is not None:
            out = out + tap[0].astype(out.dtype)
        return out

    def _host_group_exchange(self, table_h: jax.Array, grp, ids_g, w_g, tap,
                             g: int, scale_h=None):
        """Offloaded-bucket lookup: gather+combine in pinned host memory
        (compute_on 'device_host'), stream only combined [B, f, w_out] rows
        to the device, then reshard owner-major -> batch-major (the GSPMD
        form of the mp->dp all_to_all). Output layout matches
        `_tp_bucket_exchange` exactly. Reference: /CPU:0 tables with native
        kernels (dist_model_parallel.py:829-831).

        ids_g: [world, B, f, k] device-sharded exchanged absolute rows;
        w_g: matching effective weights or None; tap: optional perturbation;
        scale_h: the bucket's per-row scale stack for quantized storage
        (ISSUE 15) — rows gather at the stored dtype and DECODE here, in
        the same host region as the gather, so only the touched rows'
        payloads+scales ever move and only f32 combined rows go device-ward.
        """
        bucket = self.plan.tp_buckets[grp.bucket]
        world = self.world_size
        k, wf = grp.k, bucket.width
        store_dtype = bucket.storage_dtype
        # bucket identity must key the cache: the same group index can map
        # to a different bucket under another hotness signature, and the
        # closure bakes in rows_max / combiner / scale
        key = (g, grp.bucket, bucket.combiner, ids_g.shape,
               None if w_g is None else w_g.shape,
               None if tap is None else tap.shape,
               None if scale_h is None else store_dtype)
        fn = self._host_fn_cache.get(key)
        if fn is None:
            combiner = bucket.combiner
            # the static mean scale applies only to the uniform-weights case;
            # explicit weights arrive already normalized (_effective_weights'
            # scale-1.0 branch) — mirroring _tp_group_out exactly
            if w_g is None:
                _, scale = _effective_weights(None, k, combiner)
            else:
                scale = 1.0
            rows_max = max(bucket.rows_max, 1)
            if self.mesh is not None:
                host_sh = lambda: NamedSharding(self.mesh, P(self.axis),
                                                memory_kind=self._host_kind)
                dev_sh = NamedSharding(self.mesh, P(self.axis))
            else:
                dev0 = jax.devices()[0]
                host_sh = lambda: jax.sharding.SingleDeviceSharding(
                    dev0, memory_kind=self._host_kind)
                dev_sh = jax.sharding.SingleDeviceSharding(dev0)

            def run(table_h, scale_h, ids_g, w_g, tap):
                B, f = ids_g.shape[1], ids_g.shape[2]
                ids = jnp.clip(ids_g, 0, rows_max - 1).reshape(world, -1)
                ids_h = jax.device_put(ids, host_sh())
                w_h = (None if w_g is None
                       else jax.device_put(
                           w_g.reshape(world, B * f, k), host_sh()))
                from jax.experimental import compute_on
                with compute_on.compute_on("device_host"):
                    rows = jax.vmap(sparse_update_ops.take_rows)(
                        table_h, ids_h)                    # [world, N, wf]
                    if scale_h is not None:
                        # decode-at-gather (ISSUE 15): per-row scales
                        # gather beside their payload rows, all inside
                        # the host region — device-ward traffic stays
                        # the combined f32 rows, exactly the f32 path's
                        srow = jax.vmap(sparse_update_ops.take_rows)(
                            scale_h, ids_h)                # [world, N, 1]
                        rows = wire_ops.decode_rows(rows, srow,
                                                    store_dtype)
                    if combiner is None:
                        out_h = rows.reshape(world, B, f, k * wf)
                    else:
                        rows = rows.reshape(world, B * f, k, wf)
                        out_h = (rows if w_h is None
                                 else rows * w_h[..., None]).sum(axis=2)
                        out_h = out_h.reshape(world, B, f, wf)
                out = jax.device_put(out_h, dev_sh)
                out = self._cast(out)
                if scale != 1.0:
                    out = out * jnp.asarray(scale, out.dtype)
                if tap is not None:
                    out = out + tap.astype(out.dtype)
                if self.mesh is not None and world > 1:
                    out = lax.with_sharding_constraint(
                        out, NamedSharding(self.mesh, P(None, self.axis)))
                return out

            fn = jax.jit(run)
            self._host_fn_cache[key] = fn
        return fn(table_h, scale_h, ids_g, w_g, tap)

    def offload_lookup_scope(self, lookup_fn):
        """Scope an offloaded-bucket lookup override over forwards.

        ``lookup_fn(g, grp, table, ids_g, w_g) -> out | None`` is consulted
        for every offloaded exchange group of a TAPLESS forward (training
        forwards with taps always take the host path — the tap gradient
        contract depends on it). Returning None falls back to the stock
        host-memory lookup. `ids_g`/`w_g` and the required output layout
        are exactly `_host_group_exchange`'s. This is the seam the serving
        subsystem's HBM hot-row cache uses (serving/cache.py); the scope is
        re-entrant per layer instance, not thread-safe.
        """
        import contextlib

        @contextlib.contextmanager
        def scope():
            prev = self._offload_lookup_override
            self._offload_lookup_override = lookup_fn
            try:
                yield self
            finally:
                self._offload_lookup_override = prev
        return scope()

    @staged("lookup")
    def _offload_group_out(self, g, grp, table, scale, off_id, off_w,
                           tap_g):
        """One offloaded group's output: the serving override when scoped
        (and tapless), else the host-memory gather+combine
        (decode-at-gather for quantized storage). The override receives
        the AT-REST table leaf — raw f32 rows, or the quantized payload
        whose decode (via the bucket's scale leaf) is the override's
        job; the serving cache's decode seam (ISSUE 17) fetches that
        scale itself from the same traced params."""
        if tap_g is None and self._offload_lookup_override is not None:
            out = self._offload_lookup_override(g, grp, table, off_id, off_w)
            if out is not None:
                return out
        return self._host_group_exchange(table, grp, off_id, off_w, tap_g,
                                         g, scale_h=scale)

    @staged("acts")
    def _tp_bucket_exchange(self, out: jax.Array,
                            wire: str = "f32") -> jax.Array:
        """mp->dp movement of one bucket's outputs: [B, f, wf] ->
        [world_src, B_l, f, wf] (reference hvd.alltoall :870-872).

        `wire` (the bucket's plan `wire_dtype`, ISSUE 5) compresses the
        activation block on the wire — and, through the custom-vjp
        transpose, the dp->mp GRADIENT block of the backward pass —
        while the math on both sides stays at the caller's dtype. 'f32'
        lowers to the exact pre-seam `lax.all_to_all`."""
        world = self.world_size
        if world > 1:
            blocal = out.shape[0] // world
            x = out.reshape((world, blocal) + out.shape[1:])
            return wire_ops.wire_all_to_all(x, self.axis, wire)
        return out[None]

    def _row_slice_local(self, row_params, row_in, row_taps=None,
                         want_res=False, sort_plan=None):
        world = self.world_size
        strat = self.strategy
        row_outs = []
        res_ids: List[jax.Array] = []
        res_w: List[jax.Array] = []
        res_sort: List[Optional[GroupSort]] = []
        for j, (ids, weights) in enumerate(row_in):
            t = strat.map_groups[2][j]
            rt = self.plan.row_tables[t]
            if world > 1:
                # wire formats (ISSUE 5) from the row-table plan: int16
                # id wire where the TOTAL row count provably fits, the
                # float wire on the weight broadcast
                with stage("ids"):
                    ids = wire_ops.wire_id_all_gather(ids, self.axis,
                                                      rt.id_wire_dtype)
                    if weights is not None:
                        weights = wire_ops.wire_all_gather(
                            weights, self.axis, rt.wire_dtype, world)
            with stage("lookup"):
                base = self._device_const(rt.row_base)
                nrows = self._device_const(
                    np.asarray(rt.rows_per_rank, np.int32))
                local = ids - base.astype(ids.dtype)
                valid = (local >= 0) & (local < nrows.astype(ids.dtype))
                local = jnp.clip(local, 0, max(rt.rows_max - 1, 0))
                table = row_params[t][0]
                emb = self._cast(jnp.take(table, local, axis=0))
                vmask = valid.astype(jnp.float32)
                # explicit weighted-sum form (see _effective_weights): the
                # valid mask folds into the weights so the tapped backward
                # sees the exact per-contribution coefficients
                eff_w, scale = _effective_weights(weights, ids.shape[-1],
                                                  rt.combiner)
                w_full = vmask if eff_w is None else eff_w * vmask
                if rt.combiner is None:
                    out = emb * vmask[..., None].astype(emb.dtype)  # [B, k, w]
                else:
                    out = jnp.einsum("bk,bkw->bw", w_full.astype(emb.dtype),
                                     emb)
                    if scale != 1.0:
                        out = out * jnp.asarray(scale, out.dtype)
                if row_taps is not None:
                    out = out + row_taps[j][0].astype(out.dtype)
            if world > 1:
                # the partial-sum return rides the float wire; under a
                # compressed wire the reduce-scatter re-expresses as
                # all_to_all + LOCAL f32 accumulation, so cross-device
                # adds never run at wire precision (ops/wire.py)
                with stage("acts"):
                    out = wire_ops.wire_psum_scatter(out, self.axis,
                                                     rt.wire_dtype, world)
            row_outs.append(out)
            if want_res:
                # OOB sentinel rows_max: dropped by the sparse scatter
                with stage("ids"):
                    sent = jnp.where(valid, local,
                                     rt.rows_max).astype(jnp.int32)
                    res_ids.append(sent[None])
                    res_w.append((w_full * scale)[None])
                sort_j = None
                if sort_plan is not None and sort_plan[j]:
                    with stage("dedup"):
                        sort_j = canonical_id_sort(sent, max(rt.rows_max, 1))
                res_sort.append(self._stack_sort(sort_j))
        return row_outs, (res_ids, res_w, res_sort)

    def apply(self, params: dict, inputs: Sequence, taps=None,
              return_residuals: bool = False, residual_sort=None,
              _want_exchange: bool = False):
        """Forward pass with data-parallel input.

        Args:
          params: pytree from `init` (or `set_weights`).
          inputs: one per feature — global-batch arrays [B] / [B, k],
            RaggedIds, SparseIds or (ids, weights) tuples.
          taps: optional zero pytree from `make_taps(inputs)`. When supplied,
            differentiating the loss w.r.t. `taps` yields the per-device
            bucket-output gradients that `sparse_update` turns into row-wise
            table updates — the TPU equivalent of the reference's sparse
            IndexedSlices backward (embedding_lookup_ops.py:105-122), with
            no dense [V, w] gradient ever materialized.
          return_residuals: also return the TapResiduals for `sparse_update`.
          residual_sort: sort-folding control. None (default) defers to the
            ambient `residual_sort_scope` (off unless scoped — non-tapped
            and host-offload paths keep their exact pre-fold behavior);
            False forces off; an (optimizer_kind, strategy) tuple forces
            the spec. Only consulted when return_residuals is True.
          _want_exchange: lookahead prefetch mode (ISSUE 9, used by
            `schedule.LookaheadEngine`): return the RAW exchange-stage
            artifacts `(ex_list, row_outs, residuals)` instead of
            assembled per-input outputs — ex_list is the post-all_to_all
            per-group activation block `[world_src, B, f_max_g, wf]`,
            row_outs the post-psum_scatter row-table partials. The
            exchange computation is the IDENTICAL code path the normal
            forward runs (the dp lookup and assembly are traced but
            unused, so XLA drops them); a later `staged_exchange_scope`
            forward re-attaches these artifacts bit-exactly.

        Returns:
          One [B, width] array per input (or [B, k, width] for combiner=None
          multi-hot), in input order — batch-sharded over the mesh.
          With return_residuals, a (outputs, TapResiduals) tuple.
        """
        if not self.dp_input:
            raise ValueError("This layer was built with dp_input=False; "
                             "use apply_mp() instead")
        if self._staged_exchange is not None and not _want_exchange:
            return self._apply_staged(params, inputs, taps=taps,
                                      return_residuals=return_residuals)
        if _want_exchange:
            return_residuals = True
            if taps is not None:
                raise ValueError("_want_exchange is a tapless prefetch "
                                 "mode; gradients reach the tables via "
                                 "the drain-stage transpose, not taps")
        if residual_sort is None:
            sort_spec = self._residual_sort_spec
        else:
            sort_spec = None if residual_sort is False else residual_sort
        prepped = self._prepare_inputs(inputs)
        strat = self.strategy
        world = self.world_size

        batch = prepped[0].ids.shape[0]
        if world > 1 and batch % world != 0:
            raise ValueError(
                f"Global batch {batch} not divisible by device count {world}")

        dp_prep = [prepped[i] for i in strat.input_groups[0]]
        tp_prep = [prepped[i] for i in strat.input_groups[1]]
        row_prep = [prepped[i] for i in strat.input_groups[2]]

        # stack tp inputs per exchange group: [B, n_g, k_g] (+ weights where
        # any member input carries them — same-k members need no pad weights)
        groups, assembly = ([], [])
        group_ids: List[jax.Array] = []
        group_w: List[Optional[jax.Array]] = []
        if tp_prep:
            groups, assembly = self._exchange_groups(tp_prep)
            for grp in groups:
                members = [tp_prep[i] for i in grp.class_inputs]
                with stage("ids"):
                    group_ids.append(jnp.stack(
                        [p.ids.astype(jnp.int32) for p in members], axis=1))
                    group_w.append(jnp.stack(
                        [(p.weights if p.weights is not None
                          else jnp.ones((batch, p.k), jnp.float32))
                         for p in members], axis=1) if grp.need_w else None)

        dp_in = [(p.ids, p.weights) for p in dp_prep]
        row_in = [(p.ids, p.weights) for p in row_prep]

        want_res = bool(return_residuals)
        sort_plan = (self._sort_plan(groups, sort_spec) if want_res
                     else [None] * len(groups))
        row_sort_plan = (self._row_sort_plan(sort_spec) if want_res
                         else [None] * len(row_in))
        offloaded_groups = [
            g for g, grp in enumerate(groups)
            if self.plan.tp_buckets[grp.bucket].offload
            and self._offload_enabled]
        # taps of offloaded groups are applied outside the shard_map (at the
        # host-lookup output); mask them from the inner forward
        inner_taps = taps
        if taps is not None and offloaded_groups:
            inner_taps = {
                "tp": [None if g in offloaded_groups else t
                       for g, t in enumerate(taps["tp"])],
                "row": taps["row"]}
            if "hot" in taps:
                inner_taps["hot"] = taps["hot"]
        hot_params = (params.get("hot")
                      if self._hot_buckets and self.plan.tp_buckets else None)
        # which groups take the hot split (static): mirrors _forward_local
        hot_groups = set()
        if hot_params is not None:
            for g, grp in enumerate(groups):
                if (self.plan.tp_buckets[grp.bucket].hot_rows > 0
                        and hot_params[grp.bucket] is not None
                        and g not in offloaded_groups):
                    hot_groups.add(g)
        if hot_groups and taps is not None and "hot" not in taps:
            # the split masks resident rows' canonical gradients to ZERO
            # by design — their updates flow only through the hot taps, so
            # a hand-built tap pytree without them would silently freeze
            # the hottest rows (tapless forwards are fine: no gradients)
            raise ValueError(
                "tapped hot-split forward needs taps['hot'] — build the "
                "tap pytree with make_taps() (it adds the hot entry when "
                "hot_rows is active), or pass taps=None")
        dev_scales = self._device_bucket_scales(params)
        if world > 1:
            specs = lambda tree, spec: jax.tree.map(lambda _: spec, tree)
            args = (params["dp"], params["tp"], params["row"],
                    dp_in, group_ids, group_w, row_in, inner_taps,
                    hot_params, dev_scales)
            # the hot-shard taps enter batch-sharded with the serving-rank
            # axis intact (P(None, axis)) — each device adds the hot
            # contribution for its OWN batch slice across all source ranks
            tap_specs = None
            if inner_taps is not None:
                tap_specs = {
                    "tp": specs(inner_taps["tp"], P(self.axis)),
                    "row": specs(inner_taps["row"], P(self.axis))}
                if "hot" in inner_taps:
                    tap_specs["hot"] = [
                        None if t is None else P(None, self.axis)
                        for t in inner_taps["hot"]]
            in_specs = (specs(params["dp"], P()),
                        specs(params["tp"], P(self.axis)),
                        specs(params["row"], P(self.axis)),
                        specs(dp_in, P(self.axis)),
                        specs(group_ids, P(self.axis)),
                        specs(group_w, P(self.axis)),
                        specs(row_in, P(self.axis)),
                        tap_specs,
                        specs(hot_params, P()),
                        specs(dev_scales, P(self.axis)))
            off_id_specs = [P(self.axis) if g in offloaded_groups else None
                            for g in range(len(groups))]
            off_w_specs = [
                (P(self.axis) if (g in offloaded_groups
                                  and group_w[g] is not None) else None)
                for g in range(len(groups))]
            out_specs = (
                [P(self.axis)] * len(dp_in),
                [None if g in offloaded_groups else P(None, self.axis)
                 for g in range(len(groups))],
                [P(self.axis)] * len(row_in),
                off_id_specs,
                off_w_specs,
            )
            res_specs = ((
                [P(self.axis)] * len(groups),
                # hot-split groups always carry effective weights, even
                # when the raw input had none
                [P(self.axis) if (w is not None or g in hot_groups)
                 else None for g, w in enumerate(group_w)],
                [P(self.axis)] * len(row_in),
                [P(self.axis)] * len(row_in),
                # GroupSort subtrees take P(axis) as a pytree-prefix spec
                [None if p is None else P(self.axis) for p in sort_plan],
                [None if p is None else P(self.axis)
                 for p in row_sort_plan],
                [P(self.axis) if g in hot_groups else None
                 for g in range(len(groups))],
                [P(self.axis) if g in hot_groups else None
                 for g in range(len(groups))]) if want_res else None,)
            dp_outs, ex_list, row_outs, off_ids, off_w, res = compat.shard_map(
                lambda d, t, r, di, gi, gw, ri, tp, hp, sc:
                self._forward_local(
                    d, t, r, di, gi, gw, ri, groups, taps=tp,
                    want_res=want_res, sort_plan=sort_plan,
                    row_sort_plan=row_sort_plan, hot_params=hp,
                    tp_scales=sc),
                mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs + res_specs,
                check_vma=False,
            )(*args)
        else:
            dp_outs, ex_list, row_outs, off_ids, off_w, res = (
                self._forward_local(
                    params["dp"], params["tp"], params["row"],
                    dp_in, group_ids, group_w, row_in, groups,
                    taps=inner_taps, want_res=want_res,
                    sort_plan=sort_plan, row_sort_plan=row_sort_plan,
                    hot_params=hot_params, tp_scales=dev_scales))

        if _want_exchange:
            # lookahead prefetch return (ISSUE 9): the raw exchange-stage
            # artifacts. Offloaded buckets are refused — their lookup runs
            # host-side OUTSIDE the jitted stage, so there is no device
            # artifact to carry across the pipeline boundary.
            if offloaded_groups:
                raise NotImplementedError(
                    "lookahead prefetch (_want_exchange) does not support "
                    "host-offloaded buckets: their lookups run outside the "
                    "jitted stage and cannot be carried/patched")
            key = tuple((p.k, p.weights is not None) for p in tp_prep)
            return ex_list, row_outs, TapResiduals(
                key, res[0], res[1], res[2], res[3], res[4], res[5],
                res[6], res[7])

        # offloaded buckets: host-side lookup + GSPMD exchange (or the
        # scoped serving override — see offload_lookup_scope)
        for g in offloaded_groups:
            grp = groups[g]
            tap_g = taps["tp"][g] if taps is not None else None
            ex_list[g] = self._offload_group_out(
                g, grp, params["tp"][grp.bucket],
                self._bucket_scale(params, grp.bucket),
                off_ids[g], off_w[g], tap_g)

        # ---- assemble per-input outputs ------------------------------------
        dp_final = []
        for j, out in enumerate(dp_outs):
            p = dp_prep[j]
            cfg = strat.dp_configs[strat.map_groups[0][j]]
            dp_final.append(self._restore_shape(out, p, cfg.get("combiner"),
                                                cfg["output_dim"]))

        tp_final = self._assemble_tp_outputs(ex_list, tp_prep, batch,
                                             groups, assembly)

        row_final = []
        for j, out in enumerate(row_outs):
            p = row_prep[j]
            rt = self.plan.row_tables[strat.map_groups[2][j]]
            row_final.append(self._restore_shape(out, p, rt.combiner, rt.width))

        outputs = dp_final + tp_final + row_final
        outputs = [outputs[idx] for idx in strat.rev_group_ids]
        if want_res:
            key = tuple((p.k, p.weights is not None) for p in tp_prep)
            return outputs, TapResiduals(key, res[0], res[1], res[2], res[3],
                                         res[4], res[5], res[6], res[7])
        return outputs

    @staged("acts")
    def _assemble_tp_outputs(self, ex_list, tp_preps, batch, groups,
                             assembly) -> List[jax.Array]:
        """Slice the exchanged group outputs back into per-input arrays:
        reorder by slot, re-concat column slices (reference :876-886).

        Args:
          ex_list: per exchange group [world_src, B, f_max_g, wf] globals.
          tp_preps: _PreparedInput per tp-group input position.
          groups / assembly: from _exchange_groups (rank-major slot order).
        """
        strat = self.strategy
        tp_final = []
        for i, p in enumerate(tp_preps):
            parts = []
            for (rank, g, j_g) in assembly[i]:
                grp = groups[g]
                bucket = self.plan.tp_buckets[grp.bucket]
                part = ex_list[g][rank, :, j_g, :]          # [B, wf]
                if bucket.combiner is None:
                    part = part.reshape(batch, grp.k, bucket.width)
                parts.append(part)
            out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
            cfg = strat.global_configs[
                strat.table_groups[1][strat.map_groups[1][i]]]
            tp_final.append(self._restore_shape(out, p, cfg.get("combiner"),
                                                out.shape[-1]))
        return tp_final

    # ------------------------------------------ lookahead staging (ISSUE 9)
    @contextlib.contextmanager
    def staged_exchange_scope(self, ex_list, row_outs):
        """Scope forwards over PREFETCHED exchange artifacts.

        Inside the scope, `apply(params, inputs)` skips the id exchange /
        table gather / activation all_to_all and consumes the provided
        per-group activation blocks (`ex_list`, from a prior
        `apply(..., _want_exchange=True)`) and row-table partials
        (`row_outs`) instead — only the dp lookups and the output
        assembly run live. This is the dense stage of the lookahead
        pipeline (schedule.LookaheadEngine): differentiating the scoped
        forward w.r.t. `ex_list`/`row_outs` yields exactly the
        activation cotangents whose explicit dp->mp transpose
        (`exchange_transpose`) reproduces the monolithic step's tap
        gradients bit-exactly."""
        prev = self._staged_exchange
        self._staged_exchange = (list(ex_list), list(row_outs))
        try:
            yield
        finally:
            self._staged_exchange = prev

    def _apply_staged(self, params, inputs, taps=None,
                      return_residuals=False):
        """apply() body under `staged_exchange_scope`: live dp lookups +
        assembly over the carried exchange artifacts (same code path the
        stock forward's tail runs, so values are bit-identical given
        bit-identical artifacts)."""
        if taps is not None or return_residuals:
            raise ValueError(
                "staged_exchange_scope forwards are tapless by design — "
                "table gradients reach the sparse update through the "
                "engine's drain-stage transpose, not taps")
        if self._hot_buckets:
            raise NotImplementedError(
                "staged_exchange_scope does not support hot-row "
                "replicated buckets (the replicated hot shard updates "
                "densely every step, so prefetched activations cannot be "
                "patched from the touched-row set)")
        prepped = self._prepare_inputs(inputs)
        strat = self.strategy
        batch = prepped[0].ids.shape[0]
        dp_prep = [prepped[i] for i in strat.input_groups[0]]
        tp_prep = [prepped[i] for i in strat.input_groups[1]]
        row_prep = [prepped[i] for i in strat.input_groups[2]]
        groups, assembly = ([], [])
        if tp_prep:
            groups, assembly = self._exchange_groups(tp_prep)
        ex_list, row_outs = self._staged_exchange
        if len(ex_list) != len(groups) or len(row_outs) != len(row_prep):
            raise ValueError(
                f"staged exchange artifacts do not match this batch's "
                f"plan: got {len(ex_list)} group blocks / {len(row_outs)} "
                f"row partials, expected {len(groups)} / {len(row_prep)}")
        # dp lookups run live (dense-trained tables must see CURRENT
        # params): replicated table + per-sample gather/combine — the
        # identical math the shard_map body's dp section runs per shard
        dp_outs = []
        for j, p in enumerate(dp_prep):
            t_dp = strat.map_groups[0][j]
            cfg = strat.dp_configs[t_dp]
            if self._dp_custom_layers.get(t_dp) is not None:
                raise NotImplementedError(
                    "staged_exchange_scope does not support custom "
                    "embedding layer classes on dp tables (their forward "
                    "is defined per-device under shard_map)")
            with stage("lookup"):
                rows = self._cast(jnp.take(params["dp"][t_dp], p.ids, axis=0))
                dp_outs.append(_combine(rows, p.weights,
                                        cfg.get("combiner")))
        dp_final = []
        for j, out in enumerate(dp_outs):
            cfg = strat.dp_configs[strat.map_groups[0][j]]
            dp_final.append(self._restore_shape(out, dp_prep[j],
                                                cfg.get("combiner"),
                                                cfg["output_dim"]))
        tp_final = self._assemble_tp_outputs(ex_list, tp_prep, batch,
                                             groups, assembly)
        row_final = []
        for j, out in enumerate(row_outs):
            rt = self.plan.row_tables[strat.map_groups[2][j]]
            row_final.append(self._restore_shape(out, row_prep[j],
                                                 rt.combiner, rt.width))
        outputs = dp_final + tp_final + row_final
        return [outputs[idx] for idx in strat.rev_group_ids]

    @staged("acts")
    def exchange_transpose(self, g_ex, g_row, key) -> dict:
        """Drain-stage gradient transpose (ISSUE 9): move the dense
        stage's activation cotangents dp->mp, producing the exact
        `make_taps`-shaped gradient pytree `sparse_update` consumes.

        In the monolithic step this movement happens inside autodiff (the
        custom-vjp backward of the forward exchange); in the lookahead
        pipeline the forward exchange ran one step earlier in a different
        traced region, so the transpose is invoked explicitly — via
        `ops.wire.wire_all_to_all_t` / `wire_psum_scatter_t`, the same
        bwd rules, which is what keeps lookahead=1 bit-exact.

        Args:
          g_ex: per exchange group, cotangent of the carried activation
            block [world_src, B, f_max_g, wf].
          g_row: per row-sliced input, cotangent of the carried partial
            [B, (k,) w].
          key: the carried TapResiduals.key (selects the group layout).

        Returns {"tp": [[world, B, f, w] ...], "row": [[world, B, ...]]}.
        """
        groups, _ = self._exchange_groups_for_key(key)
        if len(g_ex) != len(groups):
            raise ValueError(f"got {len(g_ex)} group cotangents, plan has "
                             f"{len(groups)} exchange groups")
        wires = [self.plan.tp_buckets[grp.bucket].wire_dtype
                 for grp in groups]
        row_wires = [self.plan.row_tables[t].wire_dtype
                     for t in self.strategy.map_groups[2]]
        world = self.world_size
        if world == 1:
            # forward: ex = out[None]; row partials pass through — the
            # transpose is a leading-axis relabel
            return {"tp": list(g_ex), "row": [g[None] for g in g_row]}

        def body(g_ex_l, g_row_l):
            tp_taps = []
            for g, ge in enumerate(g_ex_l):       # [world_src, B_l, f, w]
                h = wire_ops.wire_all_to_all_t(ge, self.axis, wires[g])
                tp_taps.append(h.reshape((h.shape[0] * h.shape[1],)
                                         + h.shape[2:])[None])
            row_taps = []
            for j, gr in enumerate(g_row_l):      # [B_l, (k,) w]
                h = wire_ops.wire_psum_scatter_t(gr, self.axis,
                                                 row_wires[j], world)
                row_taps.append(h[None])
            return tp_taps, row_taps

        tp_taps, row_taps = compat.shard_map(
            body, mesh=self.mesh,
            in_specs=([P(None, self.axis)] * len(g_ex),
                      [P(self.axis)] * len(g_row)),
            out_specs=([P(self.axis)] * len(g_ex),
                       [P(self.axis)] * len(g_row)),
            check_vma=False,
        )(list(g_ex), list(g_row))
        return {"tp": tp_taps, "row": row_taps}

    def patch_staged_carry(self, ex_list, row_outs, patch_ex, patch_row,
                           patch_idx, batch: int):
        """Overwrite the carried exchange artifacts for the patched
        samples (ISSUE 9): sample `patch_idx[i]` of the carry takes the
        freshly re-exchanged values at patch position i. Out-of-range
        indices (the padding convention: index == batch) drop.

        The scatter runs per shard (each device patches only the rows of
        its own batch slice) so the batch-sharded carry never regathers.
        """
        if self.world_size == 1:
            ex = [e.at[:, patch_idx].set(pe, mode="drop")
                  for e, pe in zip(ex_list, patch_ex)]
            row = [r.at[patch_idx].set(pr, mode="drop")
                   for r, pr in zip(row_outs, patch_row)]
            return ex, row
        blocal = batch // self.world_size

        def body(ex_l, row_l, pex_l, prow_l, idx):
            rank = lax.axis_index(self.axis)
            lidx = idx.astype(jnp.int32) - rank * jnp.int32(blocal)
            # foreign-shard and padding rows land on the OOB slot -> drop
            lidx = jnp.where((lidx >= 0) & (lidx < blocal), lidx,
                             jnp.int32(blocal))
            ex2 = [e.at[:, lidx].set(pe, mode="drop")
                   for e, pe in zip(ex_l, pex_l)]
            row2 = [r.at[lidx].set(pr, mode="drop")
                    for r, pr in zip(row_l, prow_l)]
            return ex2, row2

        return compat.shard_map(
            body, mesh=self.mesh,
            in_specs=([P(None, self.axis)] * len(ex_list),
                      [P(self.axis)] * len(row_outs),
                      # patch blocks replicate: every shard sees every
                      # patched sample and keeps only its own rows
                      [P()] * len(ex_list), [P()] * len(row_outs), P()),
            out_specs=([P(None, self.axis)] * len(ex_list),
                       [P(self.axis)] * len(row_outs)),
            check_vma=False,
        )(list(ex_list), list(row_outs), list(patch_ex), list(patch_row),
          patch_idx)

    def prefetch_stale_mask(self, inputs, touched) -> np.ndarray:
        """Host-side [B] bool mask: which samples of a PREFETCHED batch
        contain at least one id whose row the previous batch's sparse
        update touched (`touched` = that batch's `touched_row_keys`) —
        i.e. which prefetched activations are stale and must be patched
        against the post-update tables (ISSUE 9).

        Same key-space walk as `touched_row_keys`, kept per-sample:
        tp ids map to ``rank * rows_max + row_offset + id`` flat keys,
        row-sliced ids are global rows; out-of-range ids are
        sentinel-dropped by the update and never match. Dense/(ids,
        weights) input forms only (the engine refuses ragged/sparse
        inputs — their per-sample selection would be shape-dynamic)."""
        if len(inputs) != self._n_inputs:
            raise ValueError(
                f"Expected {self._n_inputs} inputs, got {len(inputs)}")

        def host_2d(x):
            if (isinstance(x, tuple) and len(x) == 2
                    and not isinstance(x, RaggedIds)):
                x = x[0]
            if isinstance(x, (RaggedIds, SparseIds)):
                raise NotImplementedError(
                    "prefetch_stale_mask supports dense id inputs only")
            a = np.asarray(jax.device_get(x)).astype(np.int64)
            return a.reshape(a.shape[0], -1)

        seg_rows = {(pl.bucket, pl.rank, pl.row_offset): pl.rows
                    for pl in self.plan.tp_placements}
        mask = None
        for pos, i in enumerate(self.strategy.input_groups[1]):
            ids = host_2d(inputs[i])
            if mask is None:
                mask = np.zeros(ids.shape[0], bool)
            for (rank, b, slot_idx) in self.plan.tp_input_slots[pos]:
                t = touched.get(("tp", b))
                if t is None or not len(t):
                    continue
                bucket = self.plan.tp_buckets[b]
                off = bucket.slots[rank][slot_idx].row_offset
                rows = seg_rows.get((b, rank, off), 0)
                valid = (ids >= 0) & (ids < rows)
                keys = rank * max(bucket.rows_max, 1) + off + ids
                mask |= (valid & np.isin(keys, t)).any(axis=1)
        for j, i in enumerate(self.strategy.input_groups[2]):
            t_id = self.strategy.map_groups[2][j]
            t = touched.get(("row", t_id))
            ids = host_2d(inputs[i])
            if mask is None:
                mask = np.zeros(ids.shape[0], bool)
            if t is None or not len(t):
                continue
            total = int(sum(self.plan.row_tables[t_id].rows_per_rank))
            valid = (ids >= 0) & (ids < total)
            mask |= (valid & np.isin(ids, t)).any(axis=1)
        if mask is None:
            # no mp inputs at all — nothing prefetched, nothing stale
            x = inputs[0]
            n = (np.asarray(x[0]).shape[0] if isinstance(x, tuple)
                 else np.asarray(x).shape[0])
            mask = np.zeros(n, bool)
        return mask

    def apply_mp(self, params: dict, inputs, taps=None,
                 return_residuals: bool = False, residual_sort=None):
        """Forward pass with model-parallel input (dp_input=False).

        The reference mp-input contract (:729-731, :846-851): each rank
        receives ids at *global* batch size for exactly the features it owns,
        in ``strategy.input_ids_list[rank]`` order, skipping the dp->mp
        exchange (the data loader already reads feature-sharded data, see
        models/data.py RawBinaryDataset).

        Args:
          params: pytree from `init`.
          inputs: nested per-rank lists — ``inputs[r][j]`` feeds the j-th
            local input of rank r (dense [B]/[B,k] ids, RaggedIds, SparseIds
            or (ids, weights)). With world_size == 1 a flat list is accepted.
            In multi-process runs, ``inputs[r]`` may be None for ranks whose
            devices this process cannot address (each process supplies only
            its own ranks' data); that mode requires `input_max_hotness` for
            every input so all processes trace identical shapes.

        Returns:
          One [B, width] array per input in original input order,
          batch-sharded over the mesh.
        """
        if self.dp_input:
            raise ValueError("This layer was built with dp_input=True; "
                             "use apply() instead")
        strat = self.strategy
        world = self.world_size
        if world == 1 and (not inputs or not isinstance(inputs[0], list)):
            inputs = [list(inputs)]
        if len(inputs) != world:
            raise ValueError(
                f"apply_mp expects {world} per-rank input lists, got {len(inputs)}")
        partial_ranks = any(x is None for x in inputs)
        if partial_ranks and (
                self.input_max_hotness is None
                or any(self.input_max_hotness[strat.input_groups[1][pos]]
                       is None
                       for pos in range(len(strat.input_groups[1])))):
            raise ValueError(
                "apply_mp with per-process inputs (None for remote ranks) "
                "requires input_max_hotness for every input: each process "
                "must trace the same static shapes")

        prepped: List[Optional[List[_PreparedInput]]] = []
        rank_pos: List[dict] = []   # per rank: tp input pos -> local index
        input_prep = {}             # tp input pos -> representative prep
        local_ranks = ({r for r, _ in self._rank_of_device()}
                       if self.mesh is not None else {0})
        for r in range(world):
            ids_list = strat.input_ids_list[r] if strat.input_ids_list else []
            if inputs[r] is None:
                if r in local_ranks:
                    raise ValueError(
                        f"rank {r} is addressable by this process; its "
                        "apply_mp inputs cannot be None")
                prepped.append(None)
                rank_pos.append({})
                continue
            if len(inputs[r]) != len(ids_list):
                raise ValueError(
                    f"rank {r}: expected {len(ids_list)} inputs "
                    f"(features {ids_list}), got {len(inputs[r])}")
            plist, pos = [], {}
            for j, (x, inp_pos) in enumerate(zip(inputs[r], ids_list)):
                orig = strat.input_groups[1][inp_pos]
                mh = (self.input_max_hotness[orig]
                      if self.input_max_hotness is not None else None)
                p = self._prepare_one(x, mh)
                if partial_ranks and p.k != mh:
                    raise ValueError(
                        f"rank {r} input {j}: hotness {p.k} != "
                        f"input_max_hotness {mh}; with per-process inputs "
                        "all ids must be padded to the declared max hotness")
                if partial_ranks and p.k == 1 and not p.orig_1d:
                    raise ValueError(
                        f"rank {r} input {j}: feed hotness-1 ids as 1-D [B] "
                        "arrays in per-process mode — every process must "
                        "agree on the restored output shape")
                if partial_ranks and p.weights is None:
                    # uniform weights-presence across processes keeps every
                    # process's exchange-group shapes identical
                    p = _PreparedInput(
                        p.ids, jnp.ones((p.ids.shape[0], p.k), jnp.float32),
                        p.orig_1d, p.k)
                plist.append(p)
                pos[inp_pos] = j
                input_prep.setdefault(inp_pos, p)
            prepped.append(plist)
            rank_pos.append(pos)
        if partial_ranks:
            # synthesize shape-only representatives for inputs that only
            # occur on remote ranks (content irrelevant: each device reads
            # its own shard)
            batches = [p.ids.shape[0] for p in input_prep.values()]
            if not batches:
                raise ValueError("no local rank inputs provided")
            b0 = batches[0]
            for inp_pos in range(len(strat.input_groups[1])):
                if inp_pos not in input_prep:
                    orig = strat.input_groups[1][inp_pos]
                    mh = self.input_max_hotness[orig]
                    # hotness-1 inputs are fed 1-D on their owning process
                    # (enforced above), so mirror orig_1d = (mh == 1) here to
                    # keep every process's restored shapes identical
                    input_prep[inp_pos] = _PreparedInput(
                        jnp.zeros((b0, mh), jnp.int32),
                        jnp.zeros((b0, mh), jnp.float32), mh == 1, mh)
        if not input_prep:
            return []
        batch = next(iter(input_prep.values())).ids.shape[0]
        if world > 1 and batch % world != 0:
            raise ValueError(
                f"Global batch {batch} not divisible by device count {world}")

        # mp input skips the dp->mp exchange entirely (the loader already
        # read feature-sharded data) — stack each rank's local features per
        # exchange group: ids [world, B, f_max_g, k_g] (+ weights). When
        # called eagerly with a mesh, each rank's block is staged directly on
        # that rank's device so only local shards materialize (not a
        # replicated [world, ...] host stack).
        tp_preps = [input_prep[i] for i in range(len(strat.input_groups[1]))]
        groups, assembly = self._exchange_groups(tp_preps)

        @staged("ids")
        def rank_block(grp, r):
            """One rank's [B, f_max, k] ids (+ weights) for one group."""
            cols_i, cols_w = [], []
            for s in grp.rank_slots[r]:
                p = prepped[r][rank_pos[r][s.tp_input]]
                cols_i.append(p.ids.astype(jnp.int32))
                if grp.need_w:
                    cols_w.append(p.weights if p.weights is not None
                                  else jnp.ones((batch, p.k), jnp.float32))
            while len(cols_i) < grp.f_max:
                cols_i.append(jnp.zeros((batch, grp.k), jnp.int32))
                if grp.need_w:
                    cols_w.append(jnp.zeros((batch, grp.k), jnp.float32))
            ids_b = jnp.stack(cols_i, axis=1)               # [B, f, k]
            w_b = jnp.stack(cols_w, axis=1) if grp.need_w else None
            return ids_b, w_b

        def is_traced():
            for plist in prepped:
                for p in (plist or []):
                    if isinstance(p.ids, jax.core.Tracer):
                        return True
            return False

        group_ids, group_w = [], []
        if self.mesh is not None and not is_traced():
            id_shard = NamedSharding(self.mesh, P(self.axis))
            for grp in groups:
                i_shards, w_shards = [], []
                for r, dev in self._rank_of_device():
                    ids_b, w_b = rank_block(grp, r)
                    i_shards.append(jax.device_put(ids_b[None], dev))
                    if grp.need_w:
                        w_shards.append(jax.device_put(w_b[None], dev))
                gshape = (world,) + tuple(i_shards[0].shape[1:])
                group_ids.append(jax.make_array_from_single_device_arrays(
                    gshape, id_shard, i_shards))
                if grp.need_w:
                    wshape = (world,) + tuple(w_shards[0].shape[1:])
                    group_w.append(jax.make_array_from_single_device_arrays(
                        wshape, id_shard, w_shards))
                else:
                    group_w.append(None)
        else:
            if partial_ranks:
                raise ValueError(
                    "per-process (None) apply_mp inputs cannot be used under "
                    "jit/grad tracing; stage arrays eagerly first")
            for grp in groups:
                blocks = [rank_block(grp, r) for r in range(world)]
                with stage("ids"):
                    group_ids.append(jnp.stack([b[0] for b in blocks]))
                    group_w.append(jnp.stack([b[1] for b in blocks])
                                   if grp.need_w else None)

        offloaded_groups = [
            g for g, grp in enumerate(groups)
            if self.plan.tp_buckets[grp.bucket].offload
            and self._offload_enabled]
        inner_taps = taps
        if taps is not None and offloaded_groups:
            inner_taps = {"tp": [None if g in offloaded_groups else t
                                 for g, t in enumerate(taps["tp"])],
                          "row": taps.get("row", [])}

        if residual_sort is None:
            sort_spec = self._residual_sort_spec
        else:
            sort_spec = None if residual_sort is False else residual_sort
        sort_plan = (self._sort_plan(groups, sort_spec) if return_residuals
                     else [None] * len(groups))

        def body(tp_params, group_ids, group_w, taps_l, tp_scales):
            ex_list, off_ids, off_w = [], [], []
            res_ids, res_w, res_sort = [], [], []
            for g, grp in enumerate(groups):
                with stage("ids"):
                    ids_l = group_ids[g][0]                     # [B, f, k]
                    offs = self._device_const(grp.offs)
                    ids_l = ids_l + offs[None, :, None].astype(ids_l.dtype)
                    w_l = group_w[g][0] if group_w[g] is not None else None
                bucket = self.plan.tp_buckets[grp.bucket]
                sort_g = None
                if return_residuals and sort_plan[g]:
                    with stage("dedup"):
                        sort_g = self._fold_sort(
                            bucket, grp, ids_l,
                            want_inv=(sort_plan[g] == "inv"))
                if g in offloaded_groups:
                    with stage("ids"):
                        eff_w, _ = _effective_weights(w_l, grp.k,
                                                      bucket.combiner)
                        off_ids.append(ids_l[None].astype(jnp.int32))
                        off_w.append(None if eff_w is None else eff_w[None])
                    ex_list.append(None)
                else:
                    off_ids.append(None)
                    off_w.append(None)
                    out = self._tp_group_out(
                        tp_params, grp, ids_l, w_l,
                        None if taps_l is None else taps_l["tp"][g],
                        presorted=sort_g,
                        scale_s=(None if tp_scales is None
                                 else tp_scales[grp.bucket]))
                    ex_list.append(self._tp_bucket_exchange(
                        out, bucket.wire_dtype))
                if return_residuals:
                    with stage("ids"):
                        eff_w, _ = _effective_weights(w_l, grp.k,
                                                      bucket.combiner)
                        res_ids.append(ids_l[None].astype(jnp.int32))
                        res_w.append(None if eff_w is None else eff_w[None])
                        res_sort.append(self._stack_sort(sort_g))
            res = ((res_ids, res_w, res_sort) if return_residuals
                   else None)
            return ex_list, off_ids, off_w, res

        dev_scales = self._device_bucket_scales(params)

        if world > 1:
            specs = lambda tree, spec: jax.tree.map(lambda _: spec, tree)
            out_specs = (
                [None if g in offloaded_groups else P(None, self.axis)
                 for g in range(len(groups))],
                [P(self.axis) if g in offloaded_groups else None
                 for g in range(len(groups))],
                [(P(self.axis) if (g in offloaded_groups
                                   and group_w[g] is not None) else None)
                 for g in range(len(groups))],
                (([P(self.axis)] * len(groups),
                  [None if g is None else P(self.axis) for g in group_w],
                  [None if p is None else P(self.axis) for p in sort_plan])
                 if return_residuals else None),
            )
            ex_list, off_ids, off_w, res = compat.shard_map(
                body, mesh=self.mesh,
                in_specs=(specs(params["tp"], P(self.axis)),
                          specs(group_ids, P(self.axis)),
                          specs(group_w, P(self.axis)),
                          specs(inner_taps, P(self.axis)),
                          specs(dev_scales, P(self.axis))),
                out_specs=out_specs,
                check_vma=False,
            )(params["tp"], group_ids, group_w, inner_taps, dev_scales)
        else:
            ex_list, off_ids, off_w, res = body(params["tp"], group_ids,
                                                group_w, inner_taps,
                                                dev_scales)

        for g in offloaded_groups:
            grp = groups[g]
            tap_g = taps["tp"][g] if taps is not None else None
            ex_list[g] = self._offload_group_out(
                g, grp, params["tp"][grp.bucket],
                self._bucket_scale(params, grp.bucket),
                off_ids[g], off_w[g], tap_g)

        outputs = self._assemble_tp_outputs(ex_list, tp_preps, batch,
                                            groups, assembly)
        outputs = [outputs[idx] for idx in strat.rev_group_ids]
        if return_residuals:
            key = tuple((p.k, p.weights is not None) for p in tp_preps)
            return outputs, TapResiduals(key, res[0], res[1], [], [],
                                         res[2], [])
        return outputs

    # ------------------------------------------------- sparse training path
    def make_taps(self, inputs) -> dict:
        """Zero perturbation pytree for `apply(..., taps=...)`: one
        [world, B, f_max_g, w_out] array per exchange group and one
        [world, B, (k,) w] array per row-sliced input. Create inside the
        jitted train step — XLA folds the zero adds away in the forward while
        autodiff still delivers their cotangents. Accepts dp-form flat inputs
        (dp_input=True) or the nested per-rank lists of apply_mp."""
        strat = self.strategy
        dtype = self.compute_dtype or jnp.float32
        taps = {"tp": [], "row": []}
        if self.dp_input:
            prepped = self._prepare_inputs(inputs)
            batch = prepped[0].ids.shape[0]
            tp_prep = [prepped[i] for i in strat.input_groups[1]]
        else:
            tp_prep, batch = self._mp_tp_preps(inputs)
            prepped = None
        if tp_prep:
            groups, _ = self._exchange_groups(tp_prep)
            for grp in groups:
                bucket = self.plan.tp_buckets[grp.bucket]
                w_out = (bucket.width if bucket.combiner is not None
                         else bucket.width * grp.k)
                taps["tp"].append(jnp.zeros(
                    (self.world_size, batch, grp.f_max, w_out), dtype))
            if self._hot_buckets and self.dp_input:
                # hot-shard taps (ISSUE 4): one per hot-split group, added
                # at the hit-contribution merge — their cotangents are the
                # per-(serving rank, sample, slot) output grads the
                # replicated hot update consumes
                taps["hot"] = [
                    (jnp.zeros((self.world_size, batch, grp.f_max,
                                self.plan.tp_buckets[grp.bucket].width),
                               dtype)
                     if self.plan.tp_buckets[grp.bucket].hot_rows > 0
                     else None)
                    for grp in groups]
        for pos, j in enumerate(strat.input_groups[2]):
            p = prepped[j]
            rt = self.plan.row_tables[strat.map_groups[2][pos]]
            shape = ((self.world_size, batch, rt.width)
                     if rt.combiner is not None
                     else (self.world_size, batch, p.k, rt.width))
            taps["row"].append(jnp.zeros(shape, dtype))
        return taps

    def _mp_tp_preps(self, inputs):
        """Representative _PreparedInputs per tp input from nested per-rank
        apply_mp inputs (None ranks allowed when input_max_hotness covers
        their inputs). Returns (tp_preps, global_batch)."""
        strat = self.strategy
        if self.world_size == 1 and (not inputs
                                     or not isinstance(inputs[0], list)):
            inputs = [list(inputs)]
        input_prep: dict = {}
        for r, ids_list in enumerate(strat.input_ids_list or []):
            if r >= len(inputs) or inputs[r] is None:
                continue
            for x, inp_pos in zip(inputs[r], ids_list):
                orig = strat.input_groups[1][inp_pos]
                mh = (self.input_max_hotness[orig]
                      if self.input_max_hotness is not None else None)
                input_prep.setdefault(inp_pos, self._prepare_one(x, mh))
        if not input_prep:
            return [], 0
        batch = next(iter(input_prep.values())).ids.shape[0]
        for pos in range(len(strat.input_groups[1])):
            if pos not in input_prep:
                orig = strat.input_groups[1][pos]
                if self.input_max_hotness is None or \
                        self.input_max_hotness[orig] is None:
                    raise ValueError(
                        "make_taps with per-process mp inputs requires "
                        "input_max_hotness for remote-rank features")
                mh = self.input_max_hotness[orig]
                input_prep[pos] = _PreparedInput(
                    jnp.zeros((batch, mh), jnp.int32),
                    jnp.zeros((batch, mh), jnp.float32), mh == 1, mh)
        return ([input_prep[i] for i in range(len(strat.input_groups[1]))],
                batch)

    def _state_spec(self, leaf):
        """Sharding spec rule for sparse-optimizer state leaves: table-shaped
        stacked arrays ([world, rows, w]) shard over the axis, scalars (adam
        step count) replicate."""
        return P(self.axis) if getattr(leaf, "ndim", 0) == 3 else P()

    def _group_contrib(self, g, grp, res_tp_ids, res_tp_w, tp_g,
                       stacked: bool) -> SparseRowGrad:
        """Build one exchange group's SparseRowGrad from residual ids /
        effective weights and the tap gradient. stacked=False squeezes the
        leading [1] device axis (shard_map body); True keeps the [world]
        axis (global host-offload path)."""
        bucket = self.plan.tp_buckets[grp.bucket]
        ids_x = res_tp_ids[g] if stacked else res_tp_ids[g][0]
        gtap = tp_g[g] if stacked else tp_g[g][0]
        k, wf = grp.k, bucket.width
        lead = gtap.shape[:-1]                        # [..., B, f]
        if bucket.combiner is None:
            gk = gtap.reshape(lead + (k, wf))
        else:
            gk = gtap[..., None, :]
        eff = res_tp_w[g]
        if eff is not None and not stacked:
            eff = eff[0]
        # the stream's order is the forward's (`_feature_major`): ids,
        # weights and tap gradients are viewed [f, k, B] before they
        # flatten, so that the folded sort's `perm` indexes these rows
        fm = not stacked and self._feature_major(bucket, k, ids_x.shape[0])
        ids_x, gk = _as_stream(ids_x, fm), _as_stream(gk, fm)
        if eff is None:
            _, scale = _effective_weights(None, k, bucket.combiner)
            contrib = jnp.broadcast_to(gk.astype(jnp.float32) * scale,
                                       ids_x.shape + (wf,))
        else:
            contrib = (gk.astype(jnp.float32)
                       * _as_stream(eff, fm)[..., None])
        if stacked:
            world = ids_x.shape[0]
            return SparseRowGrad(ids_x.reshape(world, -1),
                                 contrib.reshape(world, -1, wf))
        return SparseRowGrad(ids_x.reshape(-1), contrib.reshape(-1, wf))

    @staticmethod
    def _unstack_sort(s: Optional[GroupSort]) -> Optional[GroupSort]:
        """Strip the leading per-device axis of a residual GroupSort."""
        if s is None:
            return None
        return GroupSort(s.sid[0], s.perm[0], s.seg_start[0],
                         None if s.inv is None else s.inv[0])

    def _sparse_update_body(self, tp_params, row_params, tp_states,
                            row_states, tp_g, row_g, res_tp_ids, res_tp_w,
                            res_row_ids, res_row_w, res_tp_sort,
                            res_row_sort, hot_tabs, hot_states, hot_g,
                            res_hot_pos, res_hot_w, tp_scales, groups, opt,
                            dev_buckets):
        """Per-device sparse updates (stacked [1, rows, w] shards in/out).
        tp_params/tp_states hold only the non-offloaded buckets, in
        dev_buckets order. res_tp_sort / res_row_sort carry the forward's
        per-group sort artifacts (sort folding) — consumed only where a
        bucket's grad comes from a single group, so the folded update is
        bit-identical to the fresh-sort one.

        hot_tabs/hot_states (ISSUE 4): the replicated [H, w] hot shards in
        self._hot_buckets order; hot_g the hot-tap gradients and
        res_hot_pos/res_hot_w the forward's membership split. Hot grads
        aggregate into a dense [H, w] partial per device (H is small by
        construction), psum to the global gradient, then apply the SAME
        optimizer rule dense-masked (`sparse_update.apply_dense_rows`) on
        every device — replicated in, replicated out, no sort ops."""

        def split_state(state):
            return tuple(x[0] if getattr(x, "ndim", 0) == 3 else x
                         for x in state)

        def stack_state(state):
            return tuple(x[None] if getattr(x, "ndim", 0) == 2 else x
                         for x in state)

        bucket_groups: dict = {}
        for g, grp in enumerate(groups):
            bucket_groups.setdefault(grp.bucket, []).append(g)

        new_tp, new_tp_s = [], []
        new_tp_sc = []
        for pos, b in enumerate(dev_buckets):
            scale_s = None if tp_scales is None else tp_scales[pos]
            gs = bucket_groups.get(b, [])
            grads = [self._group_contrib(g, groups[g], res_tp_ids, res_tp_w,
                                         tp_g, stacked=False)
                     for g in gs]
            if not grads:
                new_tp.append(tp_params[pos])
                new_tp_s.append(tp_states[pos])
                new_tp_sc.append(scale_s)
                continue
            sort_b = (self._unstack_sort(res_tp_sort[gs[0]])
                      if len(gs) == 1 else None)
            # kwarg only when an artifact exists: pre-fold user-built
            # SparseOptimizers with 3-arg update callables keep working
            # whenever no fold is active
            kw = {} if sort_b is None else {"presorted": sort_b}
            if scale_s is not None:
                # master-weight-free quantized row update (ISSUE 17):
                # decode touched rows -> f32 math -> hash-SR re-encode,
                # no resident f32 mirror of the table
                hp = dict(opt.hp)
                if opt.kind == "adagrad" and "eps" in hp:
                    kw["eps"] = hp["eps"]
                p_new, s_new_sc, st_new = \
                    sparse_update_ops.quantized_row_update(
                        opt.kind, tp_params[pos][0], scale_s[0],
                        split_state(tp_states[pos]), concat_grads(grads),
                        self._bucket_store_dtype(b), opt.lr, **kw)
                new_tp.append(p_new[None])
                new_tp_sc.append(s_new_sc[None])
                new_tp_s.append(stack_state(st_new))
                continue
            t_new, s_new = opt.update(tp_params[pos][0],
                                      split_state(tp_states[pos]),
                                      concat_grads(grads), **kw)
            new_tp.append(t_new[None])
            new_tp_s.append(stack_state(s_new))
            new_tp_sc.append(None)

        # row-sliced tables: multiple inputs may share one table
        table_inputs: dict = {}
        for j in range(len(res_row_ids)):
            t = self.strategy.map_groups[2][j]
            table_inputs.setdefault(t, []).append(j)
        new_row = list(row_params)
        new_row_s = list(row_states)
        for t, js in table_inputs.items():
            rt = self.plan.row_tables[t]
            grads = []
            for j in js:
                ids = res_row_ids[j][0]                   # [B, k]
                w = res_row_w[j][0]                       # [B, k]
                gtap = row_g[j][0]                        # [B, w] | [B, k, w]
                gk = (gtap[:, None, :] if rt.combiner is not None else gtap)
                contrib = gk.astype(jnp.float32) * w[..., None]
                grads.append(SparseRowGrad(
                    ids.reshape(-1), contrib.reshape(-1, rt.width)))
            sort_t = (self._unstack_sort(res_row_sort[js[0]])
                      if len(js) == 1 else None)
            kw = {} if sort_t is None else {"presorted": sort_t}
            t_new, s_new = opt.update(row_params[t][0],
                                      split_state(row_states[t]),
                                      concat_grads(grads), **kw)
            new_row[t] = t_new[None]
            new_row_s[t] = stack_state(s_new)

        # hot shards: dense local aggregate -> psum -> replicated apply
        new_hot_t, new_hot_s = [], []
        hp = dict(opt.hp)
        hot_kw = {k: hp[k] for k in ("eps", "b1", "b2") if k in hp}
        for pos_h, b in enumerate(self._hot_buckets):
            bucket = self.plan.tp_buckets[b]
            h_cap, wf = bucket.hot_rows, bucket.width
            gs = [g for g, grp in enumerate(groups)
                  if grp.bucket == b and res_hot_pos[g] is not None
                  and hot_g[g] is not None]
            if not gs:
                new_hot_t.append(hot_tabs[pos_h])
                new_hot_s.append(hot_states[pos_h])
                continue
            ids_l, con_l = [], []
            for g in gs:
                pos = res_hot_pos[g][0]            # [world, B_l, f, k]
                wv = res_hot_w[g][0]
                gh = hot_g[g]                      # [world, B_l, f, wf]
                contrib = gh[..., None, :].astype(jnp.float32) \
                    * wv[..., None]
                ids_l.append(pos.reshape(-1))
                con_l.append(contrib.reshape(-1, wf))
            g_dense, counts = sparse_update_ops._dense_sum(
                jnp.concatenate(ids_l), jnp.concatenate(con_l), h_cap)
            if self.world_size > 1:
                g_dense = lax.psum(g_dense, self.axis)
                counts = lax.psum(counts, self.axis)
            t_new, s_new = sparse_update_ops.apply_dense_rows(
                opt.kind, hot_tabs[pos_h], hot_states[pos_h], g_dense,
                counts > 0, opt.lr, **hot_kw)
            new_hot_t.append(t_new)
            new_hot_s.append(tuple(s_new))
        return (new_tp, new_row, new_tp_s, new_row_s, new_hot_t, new_hot_s,
                new_tp_sc if tp_scales is not None else None)

    def init_sparse_state(self, params: dict, opt: SparseOptimizer) -> dict:
        """Sparse-optimizer state for the tp/row tables (dp tables train
        dense). Table-shaped state leaves (adagrad accumulator, adam moments)
        are created directly with the tables' shardings — never materialized
        unsharded (the init-OOM concern behind the reference's CPU-side init,
        embedding.py:28-47)."""
        def init_host(stack):
            # constant-fill leaves staged shard-wise straight into pinned
            # host memory via numpy (XLA cannot emit host-placed outputs on
            # every backend, and a device-side init would need HBM the
            # offloaded bucket was too big for in the first place)
            # f32 probe regardless of the stack's storage dtype: the
            # optimizer state of a quantized (int8/fp8) bucket is f32 —
            # only the TABLE is stored compressed (ISSUE 15)
            tiny = opt.init(jnp.zeros((1, stack.shape[-1]), jnp.float32))
            out = []
            for x in tiny:
                if getattr(x, "ndim", 0) == 2:
                    fill = float(np.asarray(x)[0, 0])
                    if self.mesh is None:
                        host = jax.sharding.SingleDeviceSharding(
                            jax.devices()[0], memory_kind=self._host_kind)
                        out.append(jax.device_put(
                            np.full(stack.shape, fill, np.float32), host))
                    else:
                        out.append(self._stack_sharded(
                            lambda rank: np.full(stack.shape[1:], fill,
                                                 np.float32),
                            memory_kind=self._host_kind))
                else:
                    out.append(x)
            return tuple(out)

        def init_one(stack, memory_kind=None):
            if memory_kind:
                return init_host(stack)
            if self.mesh is None:
                return opt.init(stack)
            shard = NamedSharding(self.mesh, P(self.axis))
            rep = NamedSharding(self.mesh, P())
            probe = jax.eval_shape(opt.init, stack)
            out_sh = tuple(shard if x.ndim == 3 else rep for x in probe)
            return jax.jit(opt.init, out_shardings=out_sh)(stack)
        out = {"tp": [init_one(t, self._bucket_memory_kind(b))
                      for b, t in enumerate(params["tp"])],
               "row": [init_one(t) for t in params["row"]]}
        if self._hot_buckets and "hot" in params:
            # replicated optimizer state over the replicated hot shards
            # (ISSUE 4): every device applies the identical (psummed)
            # dense update, so the state never shards
            def init_hot(entry):
                st = opt.init(entry["rows"])
                if self.mesh is not None:
                    rep = NamedSharding(self.mesh, P())
                    st = tuple(jax.device_put(x, rep) for x in st)
                return st
            out["hot"] = [init_hot(params["hot"][b])
                          for b in self._hot_buckets]
        return out

    @staged("contrib")
    def sparse_update(self, params: dict, opt_states: dict, tap_grads: dict,
                      residuals: "TapResiduals", opt: SparseOptimizer):
        """Row-wise sparse optimizer step for tp/row tables. Traced under
        the stage `contrib` (tap gradients and residuals -> per-row
        contributions); the duplicate sum and the row rules it calls open
        `dedup` and `apply` inside it.

        Args:
          params: full param pytree (dp untouched, returned as-is).
          opt_states: from `init_sparse_state`.
          tap_grads: gradient w.r.t. the `make_taps` pytree.
          residuals: TapResiduals from `apply(..., return_residuals=True)`.
          opt: a SparseOptimizer (make_sparse_optimizer).

        Returns (new_params, new_opt_states). The O(touched rows) analogue
        of the reference backward + IndexedSlices apply
        (embedding_lookup_kernels.cu:603-775): no [V, w] dense gradient, no
        full-table optimizer pass.
        """
        n_buckets = len(self.plan.tp_buckets)
        off_buckets = [b for b in range(n_buckets)
                       if self._bucket_memory_kind(b)]
        dev_buckets = [b for b in range(n_buckets) if b not in off_buckets]
        if off_buckets and opt.kind not in sparse_update_ops.HOST_SPARSE_APPLY:
            raise NotImplementedError(
                f"sparse optimizer {opt.kind!r} has no host-memory apply "
                "rule for offloaded buckets (available: "
                f"{sorted(sparse_update_ops.HOST_SPARSE_APPLY)})")
        q_dev = [b for b in dev_buckets
                 if self._bucket_store_dtype(b) != "f32"]
        if q_dev and opt.kind not in sparse_update_ops.QUANTIZED_ROW_KINDS:
            raise NotImplementedError(
                f"sparse optimizer {opt.kind!r} has no master-weight-free "
                f"quantized row-update rule (HBM-quantized buckets "
                f"{q_dev}; available: "
                f"{sorted(sparse_update_ops.QUANTIZED_ROW_KINDS)}). adam's "
                "moment-normalized steps fall below the per-row "
                "quantization grid during bias correction and are "
                "systematically lost even under stochastic rounding; its "
                "f32 moments also dwarf the table saving. Keep such "
                "buckets at storage_dtype='f32', or offload them "
                "(host apply keeps f32 math end-to-end).")
        groups, _ = self._exchange_groups_for_key(residuals.key)
        tp_dev_sc = ([self._bucket_scale(params, b)
                      if self._bucket_store_dtype(b) != "f32" else None
                      for b in dev_buckets] if q_dev else None)
        tp_dev = [params["tp"][b] for b in dev_buckets]
        tp_dev_s = [opt_states["tp"][b] for b in dev_buckets]
        # sort-folding artifacts (absent on pre-fold / residual_sort-off
        # residual pytrees: normalize to per-entry None)
        tp_sort = residuals.tp_sort or [None] * len(residuals.tp_ids)
        row_sort = residuals.row_sort or [None] * len(residuals.row_ids)
        # hot-shard inputs (ISSUE 4): replicated [H, w] tables/state in
        # self._hot_buckets order; residual membership split + hot-tap
        # grads per group (None everywhere on hot-less layers/residuals)
        n_groups = len(residuals.tp_ids)
        hot_on = bool(self._hot_buckets and "hot" in params
                      and residuals.hot_pos is not None)
        hot_tabs = ([params["hot"][b]["rows"] for b in self._hot_buckets]
                    if hot_on else [])
        hot_states = list(opt_states.get("hot", [])) if hot_on else []
        hot_g = (list(tap_grads.get("hot") or [None] * n_groups)
                 if hot_on else [None] * n_groups)
        res_hot_pos = (residuals.hot_pos if hot_on else [None] * n_groups)
        res_hot_w = (residuals.hot_w if hot_on else [None] * n_groups)

        args = (tp_dev, params["row"], tp_dev_s,
                opt_states["row"], tap_grads["tp"], tap_grads["row"],
                residuals.tp_ids, residuals.tp_w, residuals.row_ids,
                residuals.row_w, tp_sort, row_sort,
                hot_tabs, hot_states, hot_g, res_hot_pos, res_hot_w,
                tp_dev_sc)
        if self.world_size > 1:
            sspec = lambda tree: jax.tree.map(self._state_spec, tree)
            pspec = lambda tree, s: jax.tree.map(lambda _: s, tree)
            in_specs = (pspec(tp_dev, P(self.axis)),
                        pspec(params["row"], P(self.axis)),
                        sspec(tp_dev_s), sspec(opt_states["row"]),
                        pspec(tap_grads["tp"], P(self.axis)),
                        pspec(tap_grads["row"], P(self.axis)),
                        pspec(residuals.tp_ids, P(self.axis)),
                        pspec(residuals.tp_w, P(self.axis)),
                        pspec(residuals.row_ids, P(self.axis)),
                        pspec(residuals.row_w, P(self.axis)),
                        pspec(tp_sort, P(self.axis)),
                        pspec(row_sort, P(self.axis)),
                        pspec(hot_tabs, P()),
                        sspec(hot_states),
                        [None if g is None else P(None, self.axis)
                         for g in hot_g],
                        pspec(res_hot_pos, P(self.axis)),
                        pspec(res_hot_w, P(self.axis)),
                        pspec(tp_dev_sc, P(self.axis)))
            out_specs = (pspec(tp_dev, P(self.axis)),
                         pspec(params["row"], P(self.axis)),
                         sspec(tp_dev_s), sspec(opt_states["row"]),
                         pspec(hot_tabs, P()), sspec(hot_states),
                         pspec(tp_dev_sc, P(self.axis)))
            (new_tp_dev, new_row, new_tp_dev_s, new_row_s, new_hot_t,
             new_hot_s, new_tp_sc) = compat.shard_map(
                lambda *a: self._sparse_update_body(*a, groups, opt,
                                                    dev_buckets),
                mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)(*args)
        else:
            (new_tp_dev, new_row, new_tp_dev_s, new_row_s, new_hot_t,
             new_hot_s, new_tp_sc) = (
                self._sparse_update_body(*args, groups, opt, dev_buckets))

        new_tp = list(params["tp"])
        new_tp_s = list(opt_states["tp"])
        for pos, b in enumerate(dev_buckets):
            new_tp[b] = new_tp_dev[pos]
            new_tp_s[b] = new_tp_dev_s[pos]
        # offloaded buckets: dedup to (rep, sums) here (device-side, inside
        # the caller's jit); the host-memory apply happens OUTSIDE the step
        # jit (host_bucket_apply) — XLA only honors host placement of
        # outputs at top level, and host params must stay read-only inside
        # the SPMD program
        pending = {b: self._host_bucket_pending(b, groups, tap_grads["tp"],
                                                residuals)
                   for b in off_buckets}
        new_params = {"dp": params["dp"], "tp": new_tp, "row": new_row}
        if "tp_scale" in params:
            # offloaded-bucket scales are read-only inside the jitted
            # step (the out-of-jit host apply refreshes them);
            # HBM-resident quantized buckets re-derive theirs in the
            # master-weight-free row update above (ISSUE 17)
            new_scales = list(params["tp_scale"])
            if new_tp_sc is not None:
                for pos, b in enumerate(dev_buckets):
                    if new_tp_sc[pos] is not None:
                        new_scales[b] = new_tp_sc[pos]
            new_params["tp_scale"] = new_scales
        new_states = {"tp": new_tp_s, "row": new_row_s}
        if "hot" in params:
            new_hot = list(params["hot"])
            if hot_on:
                for pos_h, b in enumerate(self._hot_buckets):
                    new_hot[b] = {"ids": params["hot"][b]["ids"],
                                  "rows": new_hot_t[pos_h]}
                new_states["hot"] = list(new_hot_s)
            elif "hot" in opt_states:
                new_states["hot"] = opt_states["hot"]
            new_params["hot"] = new_hot
        return new_params, new_states, pending

    def _host_bucket_pending(self, b, groups, tp_g, residuals):
        """Deduped (rep, sums) update rows for one offloaded bucket,
        computed on device: [world, N] / [world, N, w] arrays sharded over
        the mesh axis (vmap over the world axis keeps each shard's sort
        local — no cross-device traffic)."""
        bucket = self.plan.tp_buckets[b]
        rows = max(bucket.rows_max, 1)
        gs = [g for g, grp in enumerate(groups) if grp.bucket == b]
        grad = concat_grads([
            self._group_contrib(g, groups[g], residuals.tp_ids,
                                residuals.tp_w, tp_g, stacked=True)
            for g in gs])
        return jax.vmap(
            lambda i, c: sparse_update_ops.prepare_safe_grad(i, c, rows))(
                grad.ids, grad.contribs)

    def host_bucket_apply(self, b, table_h, state_h, rep, sums, valid,
                          opt: SparseOptimizer, lr_value=None,
                          scale_h=None):
        """Storage-dtype dispatch over `_host_bucket_apply_f32` (ISSUE
        15). f32 buckets pass straight through (bit-exact, the
        early-return contract). Quantized buckets update
        TOUCHED-ROWS-ONLY (ISSUE 17): per local shard, decode exactly
        the rows the pending delta names into a compact f32 block, run
        the stock host row kernels on it, and hash-SR re-encode those
        rows back in place — O(touched rows) bytes moved per apply, vs
        the v1 whole-bucket f32 round-trip (kept behind
        DET_HOST_APPLY=roundtrip for hardware A/B; it transits device
        memory and needs the decoded f32 bucket to fit there).
        Keyless hash-SR on the write-back centers the rounding error
        on zero across a step's many updated values instead of
        accumulating RNE bias. Returns (table, state) at f32 and
        (payload, scale, state) when `scale_h` is given."""
        sd = self._bucket_store_dtype(b)
        if sd == "f32":
            if scale_h is not None:
                raise ValueError(
                    f"bucket {b} stores f32 rows but a scale leaf was "
                    "passed — params['tp_scale'] drifted from the plan")
            return self._host_bucket_apply_f32(
                b, table_h, state_h, rep, sums, valid, opt,
                lr_value=lr_value)
        if scale_h is None:
            raise ValueError(
                f"bucket {b} stores {sd} rows: host_bucket_apply needs "
                "the params['tp_scale'] leaf alongside the payload")
        if os.environ.get("DET_HOST_APPLY") == "roundtrip":
            ckey = ("store_codec", b, sd)
            codec = self._store_codec_cache.get(ckey)
            if codec is None:
                codec = (jax.jit(functools.partial(wire_ops.decode_rows,
                                                   store_dtype=sd)),
                         jax.jit(functools.partial(wire_ops.encode_rows,
                                                   store_dtype=sd,
                                                   sr=True)))
                self._store_codec_cache[ckey] = codec
            decode, encode_sr = codec
            back = table_h.sharding
            self._host_fn_cache[("host_apply_mode", b, opt.kind)] = \
                "roundtrip"
            table_f = jax.device_put(decode(table_h, scale_h), back)
            new_f, new_state = self._host_bucket_apply_f32(
                b, table_f, state_h, rep, sums, valid, opt,
                lr_value=lr_value)
            payload, scale = encode_sr(new_f)
            return (jax.device_put(payload, back),
                    jax.device_put(scale, back), new_state)
        return self._host_quantized_touched_apply(
            b, sd, table_h, scale_h, state_h, rep, sums, valid, opt,
            lr_value=lr_value)

    def _host_quantized_touched_apply(self, b, sd, table_h, scale_h,
                                      state_h, rep, sums, valid,
                                      opt: SparseOptimizer, lr_value=None):
        """Touched-rows-only quantized host apply (ISSUE 17): the
        `_host_pershard_apply` walk specialized to (payload, scale)
        buckets. Per local shard and world slice, fetch the deduped
        update rows off device (the native wire volume), decode ONLY
        those rows to a compact f32 block, apply them with the
        C++/numpy row kernels against the f32 optimizer state, then
        hash-SR re-encode the block back into the payload/scale
        buffers in place. Bytes moved per apply are
        O(touched rows x delta_row_bytes), independent of bucket
        size; `store/quantized_rows_applied_total` (default registry)
        and the layer's raw totals record the volume."""
        apply_fn = sparse_update_ops.HOST_SPARSE_APPLY[opt.kind]
        hp = dict(opt.hp)
        kw = {k: hp[k] for k in ("eps", "b1", "b2")
              if k in hp and opt.kind in ("adagrad", "adam")}
        lr = float(jax.device_get(opt.lr if lr_value is None
                                  else lr_value))
        self._host_fn_cache[("host_apply_mode", b, opt.kind)] = "pershard"

        def by_device(x):
            return {s.device: s.data for s in x.addressable_shards}

        p_shards = list(table_h.addressable_shards)
        sc_d = by_device(scale_h)
        rep_d, sums_d, valid_d = by_device(rep), by_device(sums), \
            by_device(valid)
        arr_state = [x for x in state_h if getattr(x, "ndim", 0) >= 1]
        state_d = [by_device(x) for x in arr_state]
        scalar_after = {
            i: jax.device_get(x) + (1 if opt.kind == "adam" else 0)
            for i, x in enumerate(state_h)
            if getattr(x, "ndim", 0) == 0}

        rows_applied = 0
        new_p, new_sc, new_s = [], [], [[] for _ in arr_state]
        for sh in p_shards:
            dev = sh.device
            p_np = np.array(sh.data)            # host->host copy, mutable
            sc_np = np.array(sc_d[dev])
            s_nps = [np.array(sd_[dev]) for sd_ in state_d]
            rep_np = np.asarray(rep_d[dev])     # rows only cross the wire
            sums_np = np.asarray(sums_d[dev])
            valid_np = np.asarray(valid_d[dev])
            nw = p_np.shape[0]
            drift = [(name, a.shape) for name, a in
                     (("scale", sc_np), ("rep", rep_np), ("sums", sums_np),
                      ("valid", valid_np),
                      *((f"state[{i}]", s) for i, s in enumerate(s_nps)))
                     if a.shape[0] != nw]
            if drift:
                raise RuntimeError(
                    f"quantized per-shard apply: device {dev} holds "
                    f"{nw} world slice(s) of the payload but the update "
                    f"arrays have mismatched leading dims {drift} — "
                    "sharding layout drifted between the step jit's "
                    "pending outputs and the pinned-host bucket")
            for j in range(nw):                 # world slices on this shard
                ok = valid_np[j] > 0
                ru = rep_np[j][ok]
                m = int(ru.shape[0])
                if m == 0:
                    continue
                # compact f32 block of exactly the touched rows
                sub = np.ascontiguousarray(wire_ops.decode_rows_np(
                    p_np[j][ru], sc_np[j][ru], sd))
                st_subs = [np.ascontiguousarray(s[j][ru]) for s in s_nps]
                if opt.kind == "adam":
                    st = (st_subs[0], st_subs[1],
                          next(iter(scalar_after.values())))
                else:
                    st = tuple(st_subs)
                sparse_update_ops.host_apply_rows_inplace(
                    opt.kind, sub, st,
                    np.arange(m, dtype=rep_np.dtype),
                    np.ascontiguousarray(sums_np[j][ok]),
                    np.ones(m, dtype=valid_np.dtype), lr, **kw)
                for s, st_sub in zip(s_nps, st_subs):
                    s[j][ru] = st_sub           # fancy-index wrote a copy
                pay, scl = wire_ops.encode_rows_np(sub, sd, sr=True)
                p_np[j][ru] = pay
                sc_np[j][ru] = scl
                rows_applied += m
            new_p.append(jax.device_put(p_np, sh.data.sharding))
            new_sc.append(jax.device_put(sc_np, sc_d[dev].sharding))
            for i, s_np in enumerate(s_nps):
                new_s[i].append(
                    jax.device_put(s_np, state_d[i][dev].sharding))

        self.quantized_rows_applied_total += rows_applied
        self.quantized_apply_bytes_total += rows_applied * \
            wire_ops.delta_row_bytes(table_h.shape[-1], sd)
        from distributed_embeddings_tpu.obs.registry import default_registry
        default_registry().counter(
            "store/quantized_rows_applied_total").inc(rows_applied)

        out_state, ai = [], 0
        for i, x in enumerate(state_h):
            if getattr(x, "ndim", 0) >= 1:
                out_state.append(compat.assemble_like(x, new_s[ai]))
                ai += 1
            else:
                out_state.append(jax.device_put(
                    jnp.asarray(scalar_after[i], dtype=x.dtype),
                    x.sharding))
        return (compat.assemble_like(table_h, new_p),
                compat.assemble_like(scale_h, new_sc),
                tuple(out_state))

    def _host_bucket_apply_f32(self, b, table_h, state_h, rep, sums, valid,
                               opt: SparseOptimizer, lr_value=None):
        """Apply deduped rows to an offloaded bucket's host-resident table.

        Three implementations, best-available (force with DET_HOST_APPLY=
        native|pershard|roundtrip):

        * 'native' — a top-level jit whose outputs are pinned host memory,
          with the row scatter in a compute_on host region (zero full-table
          traffic, overlappable with device work). Preferred where the
          backend partitions host placements.
        * 'pershard' — XLA-free: per local shard, fetch ONLY the deduped
          update rows off-device (the native wire volume) and apply them to
          the pinned-host table/state buffers with the C++/numpy kernels
          (ops/sparse_update.host_apply_rows_inplace, native/host_apply.cpp).
          Sidesteps the SPMD partitioner entirely — there is no XLA program
          to partition — so it works at any world size on any backend.
          This is the reference's design point: host tables update with host
          ops (reference dist_model_parallel.py:829-831, :971-1017).
        * 'roundtrip' — pull the bucket shard to device, update, place back;
          a full-bucket transfer per step. Kept only as the last resort for
          non-f32 offloaded tables (the host kernels are f32) and for
          hardware A/B (tools/tpu_offload_probe.py).
        """
        apply_fn = sparse_update_ops.HOST_SPARSE_APPLY[opt.kind]
        hp = dict(opt.hp)
        kw = {k: hp[k] for k in ("eps", "b1", "b2")
              if k in hp and opt.kind in ("adagrad", "adam")}
        if self.mesh is not None:
            host_sh = NamedSharding(self.mesh, P(self.axis),
                                    memory_kind=self._host_kind)
            dev_sh = NamedSharding(self.mesh, P(self.axis))
        else:
            dev0 = jax.devices()[0]
            host_sh = jax.sharding.SingleDeviceSharding(
                dev0, memory_kind=self._host_kind)
            dev_sh = jax.sharding.SingleDeviceSharding(dev0)
        # per-world-shard state leaves map over axis 0; global scalars
        # (adam's step count) are shared across shards and stay unmapped
        state_axes = jax.tree.map(
            lambda x: 0 if getattr(x, "ndim", 0) >= 1 else None, state_h)
        vapply = jax.vmap(
            lambda t, s, r, sm, v, l: apply_fn(t, s, r, sm, v, l, **kw),
            in_axes=(0, state_axes, 0, 0, 0, None),
            out_axes=(0, state_axes))
        lr_in = opt.lr if lr_value is None else lr_value

        key = ("host_apply", b, opt.kind, rep.shape, sums.shape,
               lr_value is None)
        mode_key = ("host_apply_mode", b, opt.kind)
        fn = self._host_fn_cache.get(key)
        if fn is None:
            from jax.experimental import compute_on

            def run_native(table_h, state_h, rep, sums, valid, lr_a):
                rep_h = jax.device_put(rep, host_sh)
                sums_h = jax.device_put(sums, host_sh)
                valid_h = jax.device_put(valid, host_sh)
                with compute_on.compute_on("device_host"):
                    return vapply(table_h, state_h, rep_h, sums_h, valid_h,
                                  lr_a)

            if self.mesh is not None:
                scalar_sh = NamedSharding(self.mesh, P())
            else:
                scalar_sh = jax.sharding.SingleDeviceSharding(
                    jax.devices()[0])
            out_sh = jax.tree.map(
                lambda x: host_sh if getattr(x, "ndim", 0) >= 1
                else scalar_sh, (table_h, state_h))
            native = jax.jit(run_native, out_shardings=out_sh)
            roundtrip_core = jax.jit(vapply)

            def run_roundtrip(table_h, state_h, rep, sums, valid, lr_a):
                t_dev = jax.device_put(table_h, dev_sh)
                s_dev = jax.tree.map(
                    lambda x: jax.device_put(
                        x, dev_sh if x.ndim >= 1 else scalar_sh), state_h)
                new_t, new_s = roundtrip_core(t_dev, s_dev, rep, sums,
                                              valid, lr_a)
                return (jax.device_put(new_t, host_sh),
                        jax.tree.map(
                            lambda x: jax.device_put(
                                x, host_sh if x.ndim >= 1 else scalar_sh),
                            new_s))

            f32_ok = (table_h.dtype == jnp.float32 and all(
                x.dtype == jnp.float32
                for x in jax.tree.leaves(state_h)
                if getattr(x, "ndim", 0) >= 1))

            def run_pershard(table_h, state_h, rep, sums, valid, lr_a):
                return self._host_pershard_apply(
                    opt.kind, kw, table_h, state_h, rep, sums, valid, lr_a)

            forced = os.environ.get("DET_HOST_APPLY", "auto")
            if forced == "pershard" and not f32_ok:
                # the forced knob must not reach the f32-only host kernels
                # with a non-f32 bucket (heap corruption, not an error)
                import warnings
                warnings.warn(
                    f"DET_HOST_APPLY=pershard ignored for offloaded bucket "
                    f"{b}: the host kernels are float32-only and this "
                    "bucket is not; using the device round-trip",
                    RuntimeWarning, stacklevel=2)
                forced = "roundtrip"
            mode = (forced if forced in ("native", "pershard", "roundtrip")
                    else self._host_fn_cache.get(mode_key))
            if mode in ("native", "pershard", "roundtrip"):
                # forced modes must be visible to host_apply_modes() too
                self._host_fn_cache[mode_key] = mode
            if mode == "roundtrip":
                fn = run_roundtrip
            elif mode == "native":
                fn = native
            elif mode == "pershard":
                fn = run_pershard
            else:
                fallback = run_pershard if f32_ok else run_roundtrip
                fb_mode = ("pershard" if fallback is run_pershard
                           else "roundtrip")
                # the native-mode verdict is a property of (backend,
                # world_size), not of this layer/bucket/optimizer: consult
                # the process-wide cache before compiling the probe again
                # (VERDICT r5 weak #3 — re-probing spewed one XLA RET_CHECK
                # stack trace per offloaded init)
                vkey = (jax.default_backend(), self.world_size)
                verdict = _HOST_NATIVE_VERDICT.get(vkey)
                if verdict is True:
                    self._host_fn_cache[mode_key] = "native"
                    fn = native
                elif verdict is False:
                    if fb_mode == "roundtrip":
                        # the cached verdict must not silence the per-step
                        # perf-cliff signal the probe path emits
                        import warnings
                        warnings.warn(
                            "host-memory sparse apply unsupported on this "
                            "backend (cached verdict) and the bucket is "
                            "not f32; falling back to a device round-trip "
                            f"per step for offloaded bucket {b}",
                            RuntimeWarning, stacklevel=2)
                    self._host_fn_cache[mode_key] = fb_mode
                    fn = fallback

                def probe(table_h, state_h, rep, sums, valid, lr_a):
                    err, cap = None, {}
                    # fd-level capture: the partitioner RET_CHECK is
                    # LOG(ERROR)'d from C++ before the Python exception
                    # exists, so sys.stderr redirection cannot catch it
                    with _capture_fd2(cap):
                        try:
                            out = native(table_h, state_h, rep, sums,
                                         valid, lr_a)
                        except jax.errors.JaxRuntimeError as e:
                            err = e
                    if err is None:
                        _HOST_NATIVE_VERDICT[vkey] = True
                        if cap.get("data"):
                            os.write(2, cap["data"])   # replay non-error spew
                        self._host_fn_cache[mode_key] = "native"
                        self._host_fn_cache[key] = native
                        return out
                    # only the known backend gaps fall back: SPMD
                    # partitioners that cannot place host-memory outputs
                    # (two phrasings depending on whether the offender is
                    # an array or a scalar placement annotation) and
                    # backends with no host-placement custom-call at all
                    # (XLA:CPU single-device). Anything else replays the
                    # captured spew and re-raises — never hide an
                    # unexpected failure.
                    if ("cannot be replicated" not in str(err)
                            and "Side-effect HLO must have sharding"
                            not in str(err)
                            and "annotate_device_placement" not in
                            str(err)):
                        if cap.get("data"):
                            os.write(2, cap["data"])
                        raise err
                    _HOST_NATIVE_VERDICT[vkey] = False
                    first_line = str(err).splitlines()[0][:160]
                    if fallback is run_roundtrip:
                        import warnings
                        warnings.warn(
                            "host-memory sparse apply unsupported on "
                            "this backend and the bucket is not f32; "
                            "falling back to a device round-trip per "
                            f"step for offloaded bucket {b}",
                            RuntimeWarning, stacklevel=2)
                        self._host_fn_cache[mode_key] = "roundtrip"
                    else:
                        logging.getLogger(__name__).info(
                            "offloaded bucket %d: backend cannot partition "
                            "host-placement outputs (%s); using the "
                            "XLA-free per-shard host apply (row-only wire "
                            "traffic). Probe spew suppressed; verdict "
                            "cached for %s.", b, first_line, vkey)
                        self._host_fn_cache[mode_key] = "pershard"
                    self._host_fn_cache[key] = fallback
                    return fallback(table_h, state_h, rep, sums,
                                    valid, lr_a)
                if verdict is None:
                    fn = probe
            self._host_fn_cache.setdefault(key, fn)
        return fn(table_h, state_h, rep, sums, valid,
                  jnp.asarray(lr_in, jnp.float32))

    def host_apply_modes(self) -> dict:
        """{(bucket, optimizer_kind): 'native'|'pershard'|'roundtrip'} for
        every offloaded apply that has run (or been env-forced) in this
        process — keyed per BUCKET so a round-trip fallback on one bucket is
        never masked by another bucket's mode."""
        return {(k[1], k[2]): v for k, v in self._host_fn_cache.items()
                if isinstance(k, tuple) and k[0] == "host_apply_mode"}

    def _host_pershard_apply(self, kind, kw, table_h, state_h, rep, sums,
                             valid, lr_a):
        """XLA-free offloaded apply: for each LOCAL shard of the stacked
        pinned-host bucket, fetch that shard's deduped update rows from
        device (rows only — the bucket itself never crosses the wire),
        update the host buffers in place with the C++/numpy row kernels,
        and reassemble the global arrays shard-by-shard. Works at any world
        size on any backend because no XLA program ever sees the host
        placement. Scalar state leaves (adam's step count) increment here,
        mirroring host_sparse_adam's `count + 1`."""
        lr = float(jax.device_get(lr_a))

        def by_device(x):
            return {s.device: s.data for s in x.addressable_shards}

        t_shards = list(table_h.addressable_shards)
        rep_d, sums_d, valid_d = by_device(rep), by_device(sums), \
            by_device(valid)
        arr_state = [x for x in state_h if getattr(x, "ndim", 0) >= 1]
        state_d = [by_device(x) for x in arr_state]
        scalar_after = {
            i: jax.device_get(x) + (1 if kind == "adam" else 0)
            for i, x in enumerate(state_h)
            if getattr(x, "ndim", 0) == 0}

        new_t, new_s = [], [[] for _ in arr_state]
        for sh in t_shards:
            dev = sh.device
            t_np = np.array(sh.data)            # host->host copy, mutable
            s_nps = [np.array(sd[dev]) for sd in state_d]
            rep_np = np.asarray(rep_d[dev])     # rows only cross the wire
            sums_np = np.asarray(sums_d[dev])
            valid_np = np.asarray(valid_d[dev])
            # indexing below pairs world-slice j of the table shard with
            # world-slice j of the pending arrays — valid ONLY while both
            # carry the same P(axis) layout. If XLA ever materializes the
            # pending arrays differently (e.g. replicated), silently
            # applying the wrong slices would corrupt training (ADVICE r5).
            nw = t_np.shape[0]
            drift = [(name, a.shape) for name, a in
                     (("rep", rep_np), ("sums", sums_np), ("valid", valid_np),
                      *((f"state[{i}]", s) for i, s in enumerate(s_nps)))
                     if a.shape[0] != nw]
            if drift:
                raise RuntimeError(
                    f"offloaded per-shard apply: device {dev} holds "
                    f"{nw} world slice(s) of the table but the update "
                    f"arrays have mismatched leading dims {drift} — "
                    "sharding layout drifted between the step jit's "
                    "pending outputs and the pinned-host bucket")
            for j in range(nw):                 # world slices on this shard
                if kind == "adam":
                    st = (s_nps[0][j], s_nps[1][j],
                          next(iter(scalar_after.values())))
                else:
                    st = tuple(s[j] for s in s_nps)
                sparse_update_ops.host_apply_rows_inplace(
                    kind, t_np[j], st, rep_np[j], sums_np[j], valid_np[j],
                    lr, **kw)
            new_t.append(jax.device_put(t_np, sh.data.sharding))
            for i, s_np in enumerate(s_nps):
                new_s[i].append(
                    jax.device_put(s_np, state_d[i][dev].sharding))

        out_table = compat.assemble_like(table_h, new_t)
        out_state, ai = [], 0
        for i, x in enumerate(state_h):
            if getattr(x, "ndim", 0) >= 1:
                out_state.append(compat.assemble_like(x, new_s[ai]))
                ai += 1
            else:
                out_state.append(jax.device_put(
                    jnp.asarray(scalar_after[i], dtype=x.dtype), x.sharding))
        return out_table, tuple(out_state)

    @staticmethod
    @staged("acts")
    def _restore_shape(out, p: _PreparedInput, combiner, width):
        if combiner is not None:
            return out
        # combiner None: canonical shape [B, k, w]; 1-D inputs drop the axis
        if out.ndim == 2:
            out = out.reshape(out.shape[0], -1, width)
        if p.orig_1d:
            out = out[:, 0, :]
        return out

    def __call__(self, params, inputs, taps=None,
                 return_residuals: bool = False, residual_sort=None):
        if self.dp_input:
            return self.apply(params, inputs, taps=taps,
                              return_residuals=return_residuals,
                              residual_sort=residual_sort)
        return self.apply_mp(params, inputs, taps=taps,
                             return_residuals=return_residuals,
                             residual_sort=residual_sort)

    # ------------------------------------- hot-row admission + consistency
    @staticmethod
    def _host_flat_ids(x) -> np.ndarray:
        """Flatten one apply-style input (dense ids, (ids, weights)
        tuple, RaggedIds, SparseIds) to its locally-visible id stream as
        int64 numpy — the shared host-side mirror feeding both hot-row
        admission (`observe_hot_ids`) and touched-row accounting
        (`touched_row_keys`)."""

        def _local_parts(arr):
            # multi-process staged batches are global jax.Arrays that are
            # NOT fully addressable — device_get would raise. The local
            # batch shard is both available and exactly what this process
            # should observe (sync_hot_rows reconciles the per-process
            # counters by broadcasting the admitted set from process 0).
            if getattr(arr, "is_fully_addressable", True):
                return np.asarray(jax.device_get(arr)), 0
            shards = sorted(arr.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            start = shards[0].index[0].start or 0
            return np.concatenate(
                [np.asarray(s.data).reshape(-1) for s in shards]), start

        if (isinstance(x, tuple) and len(x) == 2
                and not isinstance(x, RaggedIds)):
            x = x[0]
        if isinstance(x, RaggedIds):
            # values past row_splits[-1] are padding by contract —
            # counting them would attribute phantom lookups to row 0.
            # Trim to the flat span the locally visible row_splits
            # cover: fully-addressable, that is exactly [0, n); on a
            # sharded batch it is always real values (padding lives
            # past the LAST split), at worst dropping a boundary
            # sliver of a row that straddles the shard edge — fine
            # for frequency statistics.
            vals, v0 = _local_parts(x.values)
            sp, _ = _local_parts(x.row_splits)
            sp = sp.reshape(-1)
            lo, hi = int(sp[0]), int(sp[-1])
            x = vals.reshape(-1)[max(lo - v0, 0):max(hi - v0, 0)]
        elif isinstance(x, SparseIds):
            x = x.values
        if not isinstance(x, np.ndarray):
            x = _local_parts(x)[0]
        return x.reshape(-1).astype(np.int64)

    def touched_row_keys(self, inputs) -> dict:
        """Host-side mirror of the rows one batch's sparse update may
        write (the weight-streaming producer's accounting, ISSUE 6):
        {("tp", b): sorted unique int64 flat keys
        (``rank * rows_max + row`` — the `HotRowCache`/hot-shard key
        space), ("row", t): sorted unique GLOBAL row ids}.

        The sets are deliberately a tight SUPERSET of the rows the
        update writes: sentinel-masked OOB ids are excluded (the update
        drops them), while hot-HIT lanes are included — they skip the
        canonical scatter but move the replicated hot shard, i.e. the
        MERGED row value a delta must republish. Zero-weight lanes are
        included too (lazy adam decays moments on id presence). A
        superset is the safe direction for SET-payload deltas: applying
        an unchanged row is a no-op, missing a changed one is silent
        divergence. dp tables never appear — they train densely and are
        published whole."""
        if len(inputs) != self._n_inputs:
            raise ValueError(
                f"Expected {self._n_inputs} inputs, got {len(inputs)}")
        seg_rows = {(pl.bucket, pl.rank, pl.row_offset): pl.rows
                    for pl in self.plan.tp_placements}
        per: dict = {}
        for pos, i in enumerate(self.strategy.input_groups[1]):
            ids = self._host_flat_ids(inputs[i])
            for (rank, b, slot_idx) in self.plan.tp_input_slots[pos]:
                bucket = self.plan.tp_buckets[b]
                off = bucket.slots[rank][slot_idx].row_offset
                rows = seg_rows.get((b, rank, off), 0)
                rows_max = max(bucket.rows_max, 1)
                v = ids[(ids >= 0) & (ids < rows)]
                if len(v):
                    per.setdefault(("tp", b), []).append(
                        rank * rows_max + off + v)
        for j, i in enumerate(self.strategy.input_groups[2]):
            t = self.strategy.map_groups[2][j]
            rt = self.plan.row_tables[t]
            total = int(sum(rt.rows_per_rank))
            ids = self._host_flat_ids(inputs[i])
            v = ids[(ids >= 0) & (ids < total)]
            if len(v):
                per.setdefault(("row", t), []).append(v)
        return {k: np.unique(np.concatenate(chunks))
                for k, chunks in per.items()}

    def duplicate_shares(self, params: dict, inputs: Sequence,
                         sort_spec=("adagrad", "auto")) -> dict:
        """{bucket: 1 - distinct rows / valid slots} of one batch's
        exchanged id stream (`sparse_update.dup_share`), for every
        table-parallel bucket whose sparse update takes the forward's
        folded sort under `sort_spec` = (optimizer kind, strategy), as
        `residual_sort_scope` reads it: how much of the stream the update's
        duplicate sum aggregates. Forward only and jittable: it reads the
        sort's `seg_start` and gathers no row's gradient. The values are
        device scalars; `obs.instrument.export_update_gauges` fetches
        them."""
        _, res = self.apply(params, inputs, return_residuals=True,
                            residual_sort=sort_spec)
        groups, _ = self._exchange_groups_for_key(res.key)
        return {grp.bucket: sparse_update_ops.dup_share(
                    sort_g, max(self.plan.tp_buckets[grp.bucket].rows_max, 1))
                for grp, sort_g in zip(groups, res.tp_sort)
                if sort_g is not None}

    def stream_orders(self, batch: int) -> dict:
        """{bucket: 1 | 0} for every table-parallel bucket: 1 where its
        exchange groups' id streams run feature-major, (f, k, b), at this
        global batch (what `_feature_major` answers the lookup, the folded
        sort and the contributions), 0 where batch-major. Static: the
        plan's widths and the batch decide, no device is read.
        `obs.instrument.export_update_gauges` sets
        ``lookup/stream_order{bucket=}`` from it."""
        return {b: int(self._feature_major(bucket, 1, batch))
                for b, bucket in enumerate(self.plan.tp_buckets)}

    def hot_resident_rows(self, params) -> dict:
        """{bucket: (sorted valid int64 keys [n], rows [n, w])} — the
        AUTHORITATIVE hot-resident rows per hot bucket. This is the ONE
        source both consistency consumers read (ISSUE 6): the
        `get_weights` portable-dump overlay and the table store's
        versioned `read_rows` — so a stale overlay after
        `sync_hot_rows` cannot exist by construction (there is no second
        derivation to drift). Empty dict on hot-less layers/params."""
        out = {}
        if not (self._hot_buckets and "hot" in params):
            return out
        for b in self._hot_buckets:
            entry = params["hot"][b]
            if entry is None:
                continue
            keys = np.asarray(jax.device_get(entry["ids"])) \
                .astype(np.int64)
            rows = np.asarray(jax.device_get(entry["rows"]))
            valid = (keys >= 0) & (keys < self._hot_sentinel(b))
            if valid.any():
                out[b] = (keys[valid], rows[valid])
        return out

    def _hot_tracker(self, b: int) -> HotnessTracker:
        tr = self._hot_trackers.get(b)
        if tr is None:
            tr = HotnessTracker(self.plan.tp_buckets[b].hot_rows,
                                promote_threshold=1)
            self._hot_trackers[b] = tr
        return tr

    def observe_hot_ids(self, inputs) -> dict:
        """Host-side frequency observation for hot-row admission — the
        'warmup scan' feed (and the online counter feed between
        `sync_hot_rows` calls). `inputs` are the SAME per-feature arrays
        `apply` takes (dense ids, (ids, weights) tuples, RaggedIds,
        SparseIds); observation is pure numpy on this process's view — it
        never touches device state. Shares the counter/admission core with
        the serving cache (`utils.hotness.HotnessTracker`).

        Returns {bucket: hit_rate} of the stream observed so far against
        each tracker's CURRENT resident set (the measured rates
        `exchange_padding_report` folds into its post-hot accounting).
        """
        if not self._hot_buckets:
            return {}

        per_bucket: dict = {b: [] for b in self._hot_buckets}
        hot_set = set(self._hot_buckets)
        # the device split only ever hits ids inside the lane's backing
        # segment (`_hot_split_send` lane_rows guard) — mirror it here so
        # an over-range id can neither inflate a NEIGHBORING segment's
        # counts (aliased flat key) nor count as a hit the device forces
        # to miss
        seg_rows = {b: {(pl.rank, pl.row_offset): pl.rows
                        for pl in self.plan.tp_placements if pl.bucket == b}
                    for b in self._hot_buckets}
        for pos, i in enumerate(self.strategy.input_groups[1]):
            ids = self._host_flat_ids(inputs[i])
            for (rank, b, slot_idx) in self.plan.tp_input_slots[pos]:
                if b not in hot_set:
                    continue
                bucket = self.plan.tp_buckets[b]
                off = bucket.slots[rank][slot_idx].row_offset
                rows = seg_rows[b].get((rank, off), 0)
                rows_max = max(bucket.rows_max, 1)
                v = ids[(ids >= 0) & (ids < rows)]
                per_bucket[b].append(rank * rows_max + off + v)
        rates = {}
        for b, chunks in per_bucket.items():
            if not chunks:
                continue
            tr = self._hot_tracker(b)
            tr.lookup_slots(np.concatenate(chunks), observe=True)
            rates[b] = tr.hit_rate
        return rates

    def hot_keys_from_counts(self, counts: Sequence) -> dict:
        """Planner-driven admission input from per-input id frequencies
        (e.g. ``IntegerLookup.counts()`` after ingestion, truncated to the
        table's input_dim): ``counts[i]`` is a [input_dim_i] array for
        input i, or None for unobserved inputs. Duplicate keys (shared
        tables / column slices) aggregate. Returns {bucket: top-H keys}
        for `sync_hot_rows(new_keys=...)`."""
        if len(counts) != self._n_inputs:
            raise ValueError(
                f"counts has {len(counts)} entries, expected "
                f"{self._n_inputs} (one per input)")
        out = {}
        hot_set = set(self._hot_buckets)
        agg: dict = {b: ([], []) for b in self._hot_buckets}
        for pos, i in enumerate(self.strategy.input_groups[1]):
            if counts[i] is None:
                continue
            c = np.asarray(counts[i], np.int64).reshape(-1)
            # clamp to the table's row count: an over-length counts array
            # (e.g. IntegerLookup.counts() is [max_tokens + 1] — index 0
            # is the OOV slot, so it runs one past a table with
            # input_dim == max_tokens rows) would otherwise generate keys
            # past the slot's rows — aliasing NEIGHBORING tables'/ranks'
            # rows as "hot"
            table = self.strategy.input_table_map[i]
            in_dim = int(self.strategy.global_configs[table]["input_dim"])
            c = c[:in_dim]
            for (rank, b, slot_idx) in self.plan.tp_input_slots[pos]:
                if b not in hot_set:
                    continue
                bucket = self.plan.tp_buckets[b]
                off = bucket.slots[rank][slot_idx].row_offset
                rows_max = max(bucket.rows_max, 1)
                keys = (rank * rows_max + off
                        + np.arange(len(c), dtype=np.int64))
                agg[b][0].append(keys)
                agg[b][1].append(c)
        for b, (keys_l, counts_l) in agg.items():
            if not keys_l:
                continue
            keys = np.concatenate(keys_l)
            cnts = np.concatenate(counts_l)
            uniq, inv = np.unique(keys, return_inverse=True)
            tot = np.zeros(len(uniq), np.int64)
            np.add.at(tot, inv, cnts)
            h_cap = self.plan.tp_buckets[b].hot_rows
            nz = tot > 0
            order = np.argsort(-tot[nz], kind="stable")[:h_cap]
            out[b] = uniq[nz][order]
        return out

    def _hot_fn(self, b: int, kind: str):
        """Cached jitted scatter/gather between a stacked canonical param
        and a [H]-keyed hot array (keys = world_slice*rows_max + row;
        sentinel/OOB keys drop out)."""
        key = (b, kind)
        fn = self._hot_fn_cache.get(key)
        if fn is not None:
            return fn
        rows_max = max(self.plan.tp_buckets[b].rows_max, 1)
        world = self.world_size

        def scatter(stack, keys, rows):
            w_idx = keys // rows_max
            r_idx = keys % rows_max
            return stack.at[w_idx, r_idx].set(
                rows.astype(stack.dtype), mode="drop")

        def gather(stack, keys):
            valid = (keys >= 0) & (keys < world * rows_max)
            w_idx = jnp.clip(keys // rows_max, 0, world - 1)
            r_idx = jnp.clip(keys % rows_max, 0, rows_max - 1)
            picked = stack[w_idx, r_idx]
            return jnp.where(valid[:, None], picked,
                             jnp.zeros((), picked.dtype))

        fn = jax.jit(scatter if kind == "scatter" else gather)
        self._hot_fn_cache[key] = fn
        return fn

    def sync_hot_rows(self, params: dict, opt_states: Optional[dict] = None,
                      new_keys: Optional[dict] = None, admit: bool = False):
        """The hot shard's explicit consistency step (ISSUE 4).

        While rows are hot-resident, the replicated hot shard (and its
        replicated optimizer state) is AUTHORITATIVE for them — the
        canonical MP table rows receive zero gradient (the forward masks
        hit lanes out of the miss path). This step:

          1. writes every resident hot row (and its table-shaped optimizer
             state rows) back into the canonical stacked params, and
          2. optionally re-admits a new hot set: ``new_keys`` maps bucket
             -> flat row keys (``world_slice * rows_max + row``), or
             ``admit=True`` derives them from the observed frequency
             counters (`observe_hot_ids`); the new residents' rows AND
             state rows gather from the (just-synced) canonical arrays, so
             admission is numerically a no-op.

        Call it before checkpointing via `save_global_weights` semantics
        you derive from raw params, before a serving handoff, and whenever
        re-admission should happen. (`get_weights` overlays hot rows
        itself, so the portable dump is correct even mid-residency.)
        Purely functional: returns ``(params, opt_states)`` new pytrees.
        """
        if not self._hot_buckets or "hot" not in params:
            return params, opt_states
        if admit and new_keys is None:
            # each process's tracker only observed its local batch shard,
            # so per-process top keys differ — but the membership array is
            # consumed as a REPLICATED param, so every process must admit
            # the identical set or the sentinel masks feeding all_to_all
            # silently diverge. Broadcast process 0's choice (callers
            # passing `new_keys` explicitly own that same contract).
            new_keys = {b: tr.top_keys()
                        for b, tr in self._hot_trackers.items()}
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                # the broadcast pytree must have IDENTICAL structure on
                # every process, so it spans ALL hot buckets (the lazy
                # _hot_trackers dict only holds observed ones, and which
                # buckets were observed can differ per process); buf[0]
                # flags whether process 0 observed the bucket — unflagged
                # buckets drop out below and keep their current residents
                padded = {}
                for b in self._hot_buckets:
                    cap = self.plan.tp_buckets[b].hot_rows
                    buf = np.full((cap + 1,), -1, np.int64)
                    if b in new_keys:
                        buf[0] = 1
                        k = np.asarray(new_keys[b],
                                       np.int64).reshape(-1)[:cap]
                        buf[1:1 + len(k)] = k
                    padded[b] = buf
                bcast = multihost_utils.broadcast_one_to_all(padded)
                new_keys = {b: np.asarray(buf)[1:]      # -1 pads filter out
                            for b, buf in bcast.items()
                            if int(np.asarray(buf)[0]) == 1}
        rep = (NamedSharding(self.mesh, P()) if self.mesh is not None
               else None)

        def _rep(x):
            return x if rep is None else jax.device_put(x, rep)

        new_params = dict(params)
        new_params["tp"] = list(params["tp"])
        new_params["hot"] = list(params["hot"])
        new_states = None
        if opt_states is not None:
            new_states = dict(opt_states)
            new_states["tp"] = list(opt_states["tp"])
            new_states["hot"] = list(opt_states.get("hot", []))
        for pos_h, b in enumerate(self._hot_buckets):
            entry = params["hot"][b]
            bucket = self.plan.tp_buckets[b]
            h_cap = bucket.hot_rows
            sent = self._hot_sentinel(b)
            scatter = self._hot_fn(b, "scatter")
            # 1. write-back: resident rows (+ state rows) -> canonical
            new_params["tp"][b] = scatter(new_params["tp"][b],
                                          entry["ids"], entry["rows"])
            if new_states is not None and pos_h < len(new_states["hot"]):
                can_st = list(new_states["tp"][b])
                hot_st = list(new_states["hot"][pos_h])
                for li, (cx, hx) in enumerate(zip(can_st, hot_st)):
                    if getattr(cx, "ndim", 0) == 3 \
                            and getattr(hx, "ndim", 0) == 2:
                        can_st[li] = scatter(cx, entry["ids"], hx)
                new_states["tp"][b] = tuple(can_st)
            # 2. optional re-admission from the synced canonical arrays
            if new_keys is not None and b in new_keys:
                keys = np.asarray(new_keys[b], np.int64).reshape(-1)
                keys = keys[(keys >= 0) & (keys < sent)]
                # over-capacity key lists truncate in CALLER order (e.g.
                # top_keys passes hottest first), never in numeric order —
                # dedup keeps each key's first occurrence
                _, first = np.unique(keys, return_index=True)
                keys = keys[np.sort(first)][:h_cap]
                pad = np.full((h_cap,), sent, np.int32)
                pad[:len(keys)] = np.sort(keys).astype(np.int32)
                # jnp.array COPIES and the block pins the transfer while
                # `pad` is still alive: a zero-copy/async staging of the
                # dying temp intermittently produced a membership array
                # holding foreign bytes (observed: the int64 key buffer
                # reinterpreted as int32 — silently wrong hits)
                kj = _rep(jnp.array(pad))
                kj.block_until_ready()
                gather = self._hot_fn(b, "gather")
                # pin the hot-shard dtype across re-admissions (a dtype
                # flip would retrace the donated step mid-run)
                new_params["hot"][b] = {
                    "ids": kj,
                    "rows": _rep(gather(new_params["tp"][b], kj)
                                 .astype(entry["rows"].dtype))}
                if new_states is not None \
                        and pos_h < len(new_states["hot"]):
                    can_st = new_states["tp"][b]
                    hot_st = list(new_states["hot"][pos_h])
                    for li, (cx, hx) in enumerate(zip(can_st, hot_st)):
                        if getattr(cx, "ndim", 0) == 3 \
                                and getattr(hx, "ndim", 0) == 2:
                            hot_st[li] = _rep(gather(cx, kj))
                        # scalar leaves (adam's count) keep the hot copy:
                        # hot and canonical counts increment in lockstep
                        # (one update each per step), and aliasing the
                        # canonical array here would donate one buffer
                        # twice in the next step
                    new_states["hot"][pos_h] = tuple(hot_st)
                # the host-side tracker mirrors the device-resident set so
                # observed hit rates describe what the step actually hits;
                # hit/miss stats re-window to this residency epoch (the
                # all-miss pre-admission stream must not dilute the rates
                # the padding report folds in)
                tr = self._hot_tracker(b)
                tr.set_resident(keys)
                tr.reset_stats()
        return new_params, new_states

    def hot_stats(self) -> dict:
        """Per-bucket admission/hit statistics of the host-side trackers
        ({} until observe_hot_ids/sync_hot_rows have run)."""
        return {b: tr.stats() for b, tr in self._hot_trackers.items()}

    # --------------------------------------------------------- weights I/O
    def _shard_host(self, arr: jax.Array, rank: int,
                    cache: Optional[dict] = None) -> np.ndarray:
        """One rank's [rows_max, w] block of a stacked param, fetched
        shard-wise (never materializing the global stack on host). Remote
        ranks' shards (multi-process runs) come from the pre-gathered
        `cache` — see get_weights, which issues the collective gathers in a
        fixed order BEFORE any per-rank reads (a conditional gather here
        would run collectives in a process-dependent order and deadlock)."""
        if cache and id(arr) in cache:
            return cache[id(arr)][rank]
        if hasattr(arr, "addressable_shards"):
            for sh in arr.addressable_shards:
                idx = sh.index[0]
                start = 0 if idx.start is None else idx.start
                stop = arr.shape[0] if idx.stop is None else idx.stop
                if start <= rank < stop:
                    return np.asarray(sh.data)[rank - start]
        return np.asarray(arr)[rank]

    # reference parity: get_weights chunks its collectives so no single
    # gather exceeds ~128M elements (reference dist_model_parallel.py
    # _split_1d + :1024-1089 bounds both the 2e9-element collective limit
    # and peak memory). Overridable for tests / small-RAM hosts.
    GATHER_CHUNK_ELEMS = int(os.environ.get("DET_GATHER_CHUNK_ELEMS",
                                            128 * 1024 * 1024))

    def _gather_global_chunked(self, arr: jax.Array) -> np.ndarray:
        """Replicate a non-fully-addressable stacked param host-side in
        row chunks: each collective moves (and each device holds) at most
        ~GATHER_CHUNK_ELEMS elements, so the peak device/temp footprint is
        O(chunk) + the unavoidable host result, never a second full bucket
        (VERDICT r4 item 5; the single-call process_allgather it replaces
        replicated the ENTIRE stacked bucket on every device first)."""
        from jax.experimental import multihost_utils
        world = max(int(arr.shape[0]), 1)
        rows = int(arr.shape[1]) if arr.ndim > 1 else 1
        tail = int(np.prod(arr.shape[2:])) if arr.ndim > 2 else 1
        chunk = max(1, self.GATHER_CHUNK_ELEMS // max(world * tail, 1))
        # offloaded (pinned-host) buckets: process_allgather's replicated
        # jit cannot consume host-placement inputs (the same partitioner
        # RET_CHECK the train-path pershard apply sidesteps). A jit SLICE of
        # the host input lands in device memory partitioned — so each chunk
        # is moved host->device per-shard first, and only device arrays ever
        # meet the collective. Chunking bounds the device temp to O(chunk).
        host_kind = getattr(arr.sharding, "memory_kind", "device") not in (
            None, "device")
        if arr.ndim < 2:
            if host_kind:
                arr = jax.device_put(
                    arr, arr.sharding.with_memory_kind("device"))
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True))
        if chunk >= rows and not host_kind:
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True))
        out = np.empty(arr.shape, dtype=arr.dtype)
        for r0 in range(0, rows, chunk):
            r1 = min(rows, r0 + chunk)
            # jit-sliced for BOTH memory kinds: eager indexing of a
            # non-fully-addressable device array is backend-dependent
            # (ADVICE r5), while the cached jitted slice is always legal
            piece = _slice_rows_jit(arr, r0, r1)
            out[:, r0:r1] = np.asarray(
                multihost_utils.process_allgather(piece, tiled=True))
        return out

    @spanned("embedding/get_weights")
    def get_weights(self, params, all_ranks: bool = False) -> List[np.ndarray]:
        """Reassemble global per-table weights in original table order
        (reference get_weights :1139-1162), reading device shards one at a
        time. Multi-process: every non-fully-addressable stacked param is
        first replicated host-side by a collective all-gather, in fixed
        (tp-bucket, row-table) order — so ALL processes must call
        get_weights together (the reference's get_weights is likewise
        collective, :1084-1089).
        """
        del all_ranks  # SPMD: every process sees the global jax.Array
        cache: dict = {}
        if self.mesh is not None and jax.process_count() > 1:
            scales = [s for s in params.get("tp_scale", []) if s is not None]
            for arr in list(params["tp"]) + list(params["row"]) + scales:
                if (hasattr(arr, "is_fully_addressable")
                        and not arr.is_fully_addressable):
                    cache[id(arr)] = self._gather_global_chunked(arr)
        strat = self.strategy
        n = len(strat.global_configs)
        out: List[Optional[np.ndarray]] = [None] * n

        for j, gtid in enumerate(strat.table_groups[0]):
            out[gtid] = np.asarray(params["dp"][j])

        for t_local, gtid in enumerate(strat.table_groups[1]):
            cols = []
            for pl_ in sorted((p for p in self.plan.tp_placements
                               if p.table_id == t_local),
                              key=lambda p: p.col_start):
                shard = self._shard_host(params["tp"][pl_.bucket], pl_.rank,
                                         cache)
                sd = self._bucket_store_dtype(pl_.bucket)
                if sd != "f32":
                    # quantized storage (ISSUE 15): the portable dump is
                    # ALWAYS f32 — decode payload x per-row scale here,
                    # so checkpoints/streams stay format-stable
                    sshard = self._shard_host(
                        params["tp_scale"][pl_.bucket], pl_.rank, cache)
                    shard = wire_ops.decode_rows_np(shard, sshard, sd)
                cols.append(shard[pl_.row_offset:pl_.row_offset + pl_.rows, :])
            out[gtid] = np.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]

        for t_local, gtid in enumerate(strat.table_groups[2]):
            rt = self.plan.row_tables[t_local]
            parts = [self._shard_host(params["row"][t_local], r,
                                      cache)[:rt.rows_per_rank[r], :]
                     for r in range(self.world_size)]
            out[gtid] = np.concatenate(parts, axis=0)

        # hot-row overlay (ISSUE 4): while resident, the replicated hot
        # shard is authoritative for its rows (the canonical table stops
        # receiving their gradients) — merge them into the portable dump
        # so get_weights is correct even without a prior sync_hot_rows.
        # The resident set comes from `hot_resident_rows`, the SAME
        # single source the table store's versioned `read_rows` overlays
        # from (ISSUE 6): both consumers see one derivation, so they
        # cannot drift.
        for b, (keys_v, rows_v) in self.hot_resident_rows(params).items():
            rows_max = max(self.plan.tp_buckets[b].rows_max, 1)
            w_idx = keys_v // rows_max
            r_idx = keys_v % rows_max
            for pl_ in self.plan.tp_placements:
                if pl_.bucket != b:
                    continue
                m = ((w_idx == pl_.rank) & (r_idx >= pl_.row_offset)
                     & (r_idx < pl_.row_offset + pl_.rows))
                if not m.any():
                    continue
                gtid = strat.table_groups[1][pl_.table_id]
                if not out[gtid].flags.writeable:
                    out[gtid] = out[gtid].copy()
                out[gtid][r_idx[m] - pl_.row_offset,
                          pl_.col_start:pl_.col_end] = rows_v[m]
        return out

    def set_weights(self, weights: Sequence) -> dict:
        """Build a new params pytree from global per-table weights
        (numpy arrays or .npy file paths; reference set_weights :971-1022).
        Purely functional: returns new params with the same shardings.
        Each rank's shard is assembled and staged independently, so peak host
        memory is one shard — .npy paths are mmap'd and only the placed
        slices are read (reference np.load(mmap_mode='r') :911-950 and
        128M-element chunked scatter :1002-1017 serve the same purpose).
        """
        strat = self.strategy
        if len(weights) != len(strat.global_configs):
            raise ValueError(
                f"Expected {len(strat.global_configs)} weights, got {len(weights)}")
        weights = [np.load(w, mmap_mode="r") if isinstance(w, str) else np.asarray(w)
                   for w in weights]
        for w, cfg in zip(weights, strat.global_configs):
            expect = (cfg["input_dim"], cfg["output_dim"])
            if tuple(w.shape) != expect:
                raise ValueError(f"Weight shape {w.shape} != expected {expect}")

        new = {"dp": [], "tp": [], "row": []}
        for j, gtid in enumerate(strat.table_groups[0]):
            new["dp"].append(jnp.asarray(weights[gtid]))

        def tp_shard(rank: int, b: int) -> np.ndarray:
            bucket = self.plan.tp_buckets[b]
            arr = np.zeros((max(bucket.rows_max, 1), bucket.width), np.float32)
            for pl_ in self.plan.tp_placements:
                if pl_.bucket != b or pl_.rank != rank:
                    continue
                gtid = strat.table_groups[1][pl_.table_id]
                arr[pl_.row_offset:pl_.row_offset + pl_.rows, :] = (
                    weights[gtid][:, pl_.col_start:pl_.col_end])
            return arr

        def row_shard(rank: int, t_local: int, gtid: int) -> np.ndarray:
            rt = self.plan.row_tables[t_local]
            arr = np.zeros((max(rt.rows_max, 1), rt.width), np.float32)
            start = int(sum(rt.rows_per_rank[:rank]))
            rows = rt.rows_per_rank[rank]
            arr[:rows, :] = weights[gtid][start:start + rows, :]
            return arr

        qbs = self.quantized_buckets
        scales: Dict[int, jax.Array] = {}
        q_shard = self._encoded_shard_fn(tp_shard, wire_ops.encode_rows_np)
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            new["dp"] = [jax.device_put(a, rep) for a in new["dp"]]
            for b in range(len(self.plan.tp_buckets)):
                mk = self._bucket_memory_kind(b)
                if b in qbs:
                    new["tp"].append(self._stack_sharded(
                        lambda rank, b=b: q_shard(rank, b, 0),
                        memory_kind=mk))
                    scales[b] = self._stack_sharded(
                        lambda rank, b=b: q_shard(rank, b, 1),
                        memory_kind=mk)
                else:
                    new["tp"].append(self._stack_sharded(
                        lambda rank, b=b: tp_shard(rank, b),
                        memory_kind=mk))
            for t_local, gtid in enumerate(strat.table_groups[2]):
                new["row"].append(self._stack_sharded(
                    lambda rank, t=t_local, g=gtid: row_shard(rank, t, g)))
        else:
            for b in range(len(self.plan.tp_buckets)):
                mk = self._bucket_memory_kind(b)
                scale = None
                if b in qbs:
                    arr = np.stack([q_shard(r, b, 0)
                                    for r in range(self.world_size)])
                    scale = jnp.asarray(np.stack(
                        [q_shard(r, b, 1) for r in range(self.world_size)]))
                    arr = jnp.asarray(arr)
                else:
                    arr = jnp.stack([jnp.asarray(tp_shard(r, b))
                                     for r in range(self.world_size)])
                if mk:
                    hsh = jax.sharding.SingleDeviceSharding(
                        jax.devices()[0], memory_kind=mk)
                    arr = jax.device_put(arr, hsh)
                    if scale is not None:
                        scale = jax.device_put(scale, hsh)
                new["tp"].append(arr)
                if scale is not None:
                    scales[b] = scale
            for t_local, gtid in enumerate(strat.table_groups[2]):
                new["row"].append(jnp.stack(
                    [jnp.asarray(row_shard(r, t_local, gtid))
                     for r in range(self.world_size)]))
        if qbs:
            new["tp_scale"] = [scales.get(b)
                               for b in range(len(self.plan.tp_buckets))]
        if self._hot_buckets:
            # global weights are the canonical tables; the hot set starts
            # empty (re-admit + sync after loading to repopulate it)
            new["hot"] = self._init_hot_params()
        return new


def broadcast_variables(params, root_rank: int = 0):
    """Reference-API shim (dist_model_parallel.py:1219-1239).

    Under SPMD there is nothing to broadcast: every process constructs the
    same global jax.Arrays (same program, same seed). For multi-process
    setups initializing from process-local data, broadcast from process 0.
    """
    if root_rank != 0:
        raise NotImplementedError(
            "broadcast_one_to_all always originates from process 0; "
            "root_rank != 0 is not supported")
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(params)
    return params
