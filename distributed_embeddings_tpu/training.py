"""Training integration: the reference's Horovod-patch layer, re-thought SPMD.

The reference ships four Horovod integration shims
(reference: distributed_embeddings/python/layers/dist_model_parallel.py:1217-1326):

  * ``DistributedGradientTape`` (:1242) — patches Horovod's tape so
    model-parallel variables (tagged ``var.de_local``) are excluded from the
    allreduce while data-parallel grads are averaged.
  * ``DistributedOptimizer`` (:1270) — same patch for the Keras-fit path.
  * ``broadcast_variables`` (:1219) — initial DP weight sync that skips MP vars.
  * ``BroadcastGlobalVariablesCallback`` (:1303) — Keras callback form.

Under SPMD none of the patching is load-bearing: a jit-compiled train step
over a Mesh computes gradients that automatically follow parameter shardings
(MP-sharded grads stay device-local; replicated-param grads are psummed by the
shard_map/pjit transpose), and every process builds identical initial weights
from the same seed. The behavioral contract — "MP gradients never cross
workers, DP gradients are averaged, one backward pass handles both" (:1242-1267)
— is a property of sharded autodiff here, not of a wrapper.

These classes therefore exist for API parity and for the places where a real
action remains (multi-process weight sync from process-local state, gradient
postprocessing hooks). They are thin, documented, and jit-compatible.
"""

import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.layers.dist_model_parallel import (
    broadcast_variables)
from distributed_embeddings_tpu.obs import spans as host_spans
from distributed_embeddings_tpu.obs.stages import stage
from distributed_embeddings_tpu.ops.sparse_update import (
    drain_sparse_apply, make_sparse_optimizer, prevalidate_active_impl)

__all__ = [
    "DistributedGradientTape",
    "DistributedOptimizer",
    "BroadcastGlobalVariablesCallback",
    "broadcast_variables",
    "make_train_step",
    "make_sparse_train_step",
    "fit",
    "evaluate",
]


class DistributedGradientTape:
    """API-parity shim for the reference DistributedGradientTape (:1242).

    Usage: ``tape = DistributedGradientTape(); loss, grads =
    tape.gradient(loss_fn, params, *args)``. The heavy lifting the reference
    wrapper did (allreduce DP grads, keep MP grads local, sparse_as_dense) is
    inherent to sharded autodiff — grads follow param shardings.
    """

    def __init__(self, sparse_as_dense: bool = True):
        # sparse_as_dense is vacuous: XLA grads of gather are dense
        # scatter-adds already (no IndexedSlices analogue in JAX).
        del sparse_as_dense

    def gradient(self, loss_fn: Callable, params, *args, **kwargs):
        return jax.value_and_grad(loss_fn)(params, *args, **kwargs)


class DistributedOptimizer:
    """Optax wrapper with the reference DistributedOptimizer API (:1270).

    ``init``/``update`` pass through to the wrapped optax optimizer; no
    gradient communication is inserted because none is needed (see module
    docstring). Keeps a hook point (``postprocess``) mirroring the
    reference's gradient-postprocess ability.
    """

    def __init__(self, optimizer,
                 postprocess: Optional[Callable[[Any], Any]] = None):
        self._opt = optimizer
        self._postprocess = postprocess

    def init(self, params):
        return self._opt.init(params)

    def update(self, grads, opt_state, params=None):
        if self._postprocess is not None:
            grads = self._postprocess(grads)
        return self._opt.update(grads, opt_state, params)

    def apply(self, params, updates):
        return apply_updates(params, updates)


class BroadcastGlobalVariablesCallback:
    """API-parity shim for the reference Keras callback (:1303).

    Under SPMD the initial weights are already identical (same program, same
    seed). For multi-process runs restoring from process-local state, call
    ``on_train_begin(params)`` to broadcast from process 0.
    """

    def __init__(self, root_rank: int = 0):
        if root_rank != 0:
            raise NotImplementedError(
                "broadcast_one_to_all always originates from process 0; "
                "root_rank != 0 is not supported")
        self.root_rank = root_rank
        self._done = False

    def on_train_begin(self, params):
        if self._done:
            return params
        self._done = True
        return broadcast_variables(params, root_rank=self.root_rank)


def apply_updates(params, updates):
    """params + updates (optax convention). Delegates to optax.apply_updates,
    which also handles None update leaves (masked optimizers) and casts
    updates to each param's dtype."""
    import optax
    return optax.apply_updates(params, updates)


def default_donate() -> bool:
    """Default for the train-step factories' ``donate`` argument:
    ``DET_STEP_DONATE`` (unset/'1' -> True). '0' builds steps that update
    out of place — numerically identical, and twice the table memory —
    for a caller that must keep reading the arrays it passed in."""
    return os.environ.get("DET_STEP_DONATE", "1") != "0"


def make_train_step(loss_fn: Callable, optimizer,
                    donate: Optional[bool] = None,
                    param_shardings: Any = None):
    """Build the canonical jitted SPMD train step.

    Args:
      loss_fn: (params, *batch) -> scalar loss (mean over the global batch —
        this is what makes replicated-param grads come out averaged, the
        reference's hvd.allreduce(average) semantics :1260).
      optimizer: optax optimizer (or DistributedOptimizer).
      donate: donate params/opt_state buffers (in-place update on TPU);
        None defers to `default_donate()` (the DET_STEP_DONATE default).
      param_shardings: optional full params-tree sharding pytree, pinned on
        the step's params output (keeps placement stable across steps).

    Returns:
      step(params, opt_state, *batch) -> (params, opt_state, loss), jitted
      under the name `obs.stages.STEP_NAME`.
    """
    def det_train_step(params, opt_state, *batch):
        with stage("model"):
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        with stage("dense_opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, loss

    host_spans.install_gc_hook()
    if donate is None:
        donate = default_donate()
    donate_argnums = (0, 1) if donate else ()
    out_shardings = ((param_shardings, None, None)
                     if param_shardings is not None else None)
    return jax.jit(det_train_step, donate_argnums=donate_argnums,
                   out_shardings=out_shardings)


def _dense_part(params):
    """The densely-trained subtree: everything except the tp/row tables."""
    emb = params["embedding"]
    rest = {k: v for k, v in params.items() if k != "embedding"}
    return {**rest, "embedding": {"dp": emb["dp"]}}


def _merge_dense(dense, params):
    emb = dict(params["embedding"])
    emb["dp"] = dense["embedding"]["dp"]
    out = {k: v for k, v in dense.items() if k != "embedding"}
    out["embedding"] = emb
    return out


def _sparse_optimizer_setup(optimizer: str, lr, strategy: str,
                            dense_optimizer, widths=None):
    """Sparse + dense optimizer construction shared by the monolithic
    step (`make_sparse_train_step`) and the lookahead engine
    (`schedule.LookaheadEngine`) — ONE home for the eps parity
    constants, the kernel prevalidation, and the scheduled-lr per-step
    rebuild rule; the engine's bit-exact-vs-monolithic contract depends
    on these matching exactly.

    Returns ``(scheduled, sopt_for, dense_optimizer)``:
    ``sopt_for(None)`` is the static optimizer (lr 0.0 under a
    schedule); ``sopt_for(opt_state)`` rebuilds it at
    ``lr(opt_state["count"])`` inside the traced step when `lr` is a
    schedule callable, and returns the static one otherwise."""
    import optax

    # eps matches optax's adagrad so dp tables and tp/row tables see the
    # same rule (reference: one Keras optimizer instance for the whole
    # model)
    sparse_hp = {"adagrad": {"eps": 1e-7}, "adam": {}, "sgd": {}}[optimizer]
    scheduled = callable(lr)
    # eagerly check, compiled on the attached chip, every kernel the step
    # will dispatch to: a DET_SCATTER_IMPL choice, and the tile stream
    # adagrad takes by default at the widths the chip stores column-major
    prevalidate_active_impl(strategy=strategy, widths=widths, kind=optimizer)
    sopt = make_sparse_optimizer(optimizer, 0.0 if scheduled else lr,
                                 strategy=strategy, **sparse_hp)
    if dense_optimizer is None:
        dense_optimizer = {
            "sgd": lambda: optax.sgd(lr),
            "adagrad": lambda: optax.adagrad(lr),
            "adam": lambda: optax.adam(lr),
        }[optimizer]()

    def sopt_for(opt_state=None):
        if not scheduled or opt_state is None:
            return sopt
        return make_sparse_optimizer(optimizer, lr(opt_state["count"]),
                                     strategy=strategy, **sparse_hp)

    return scheduled, sopt_for, dense_optimizer


def make_sparse_train_step(model, optimizer: str = "adagrad", lr=0.01,
                           dense_optimizer=None, strategy: str = "auto",
                           donate: Optional[bool] = None,
                           fold_sort: bool = True):
    """Build a train step whose embedding-table updates are row-wise sparse.

    This is the TPU-native analogue of the reference's full sparse training
    path: custom backward emitting (unique_ids, grads)
    (embedding_lookup_kernels.cu:603-775) consumed by the TF optimizer's
    sparse apply. Plain `jax.grad` + optax would materialize a dense [V, w]
    gradient per table and run a full-table optimizer pass per step — O(vocab)
    HBM traffic and memory that caps out far below the reference. Here the
    embedding forward is "tapped" (see DistributedEmbedding.apply taps);
    the backward delivers per-device output gradients, and
    DistributedEmbedding.sparse_update applies O(batch x hotness) row updates
    in place.

    Args:
      model: exposes `.embedding` (DistributedEmbedding) and
        `loss_fn(params, numerical, cats, labels, taps=, return_residuals=)`.
      optimizer: 'sgd' | 'adagrad' | 'adam' — applied sparsely to tp/row
        tables and densely (optax) to everything else.
      lr: learning rate — a scalar, or a schedule callable step -> lr
        (applied to both the sparse and dense parts; a 'count' scalar is
        kept in the opt state).
      dense_optimizer: optional optax optimizer for the dense part
        (default: the optax twin of `optimizer`).
      strategy: sparse aggregation strategy ('auto' | 'sort' | 'dense' |
        'tiled' — the Pallas one-hot-matmul kernels).
      fold_sort: sort folding (ISSUE 2, default on): the tapped forward
        produces each exchange group's canonical id sort ONCE
        (TapResiduals.tp_sort/row_sort) and the sparse update consumes the
        precomputed order instead of re-sorting — bit-identical numerics,
        ≤1 sort op per (bucket, hotness) exchange group in the compiled
        step (the reference CUDA backward's reuse of forward-sorted ids,
        embedding_lookup_kernels.cu:706-773). False keeps the unfolded
        (re-sorting) step, e.g. as the parity baseline in tests.

    Returns (init_fn, step_fn):
      init_fn(params) -> opt_state
      step_fn(params, opt_state, numerical, cats, labels)
        -> (params, opt_state, loss);  jit with donated params/opt_state.
      A call is the host span ``train/dispatch`` (`obs.spans`: one
      recorder entry with the step's ordinal, a ``det:train/dispatch``
      annotation on a profiler's host plane, about 3 us); the first
      call's span holds the step's trace, lowering and compile or cache
      load.
      `step_fn.name` is the jitted function's name (`obs.stages.STEP_NAME`:
      traces say ``jit(det_train_step)``) and `step_fn.lower` the `lower` of
      the very `jax.jit` object a call dispatches to: arrays or
      ShapeDtypeStructs in, and from its ``.compile()`` the step's
      `memory_analysis()`, cost analysis and text (it returns the offloaded
      buckets' pending rows as a fourth output).
    """
    emb = model.embedding
    scheduled, sopt_for, dense_optimizer = _sparse_optimizer_setup(
        optimizer, lr, strategy, dense_optimizer,
        widths=emb.plan_widths())
    sopt = sopt_for()

    def init_fn(params):
        state = {"emb": emb.init_sparse_state(params["embedding"], sopt),
                 "dense": dense_optimizer.init(_dense_part(params))}
        if scheduled:
            state["count"] = jnp.zeros((), jnp.int32)
        return state

    off_buckets = [b for b in range(len(emb.plan.tp_buckets))
                   if emb._bucket_memory_kind(b)]
    sort_spec = (optimizer, strategy) if fold_sort else None

    def det_train_step(params, opt_state, numerical, cats, labels):
        cats = list(cats)
        with stage("lookup"):
            taps = emb.make_taps(cats)
        with stage("apply"):
            sopt_t = sopt_for(opt_state)     # a schedule's lr(count)

        def loss_with_taps(dense, taps):
            p = _merge_dense(dense, params)
            return model.loss_fn(p, numerical, cats, labels, taps=taps,
                                 return_residuals=True)

        dense0 = _dense_part(params)
        # residual_sort_scope is trace-time state: the model's loss_fn
        # reaches emb.apply without a residual_sort channel of its own, so
        # the fold spec rides the layer for exactly this traced region
        with emb.residual_sort_scope(sort_spec), stage("model"):
            (loss, res), (g_dense, g_taps) = jax.value_and_grad(
                loss_with_taps, argnums=(0, 1), has_aux=True)(dense0, taps)
        # the shared drain-stage tail (also the lookahead engine's): sparse
        # update + off-bucket output zeroing (host leaves never leave jit)
        new_emb, new_emb_state, pending = drain_sparse_apply(
            emb, params["embedding"], opt_state["emb"], g_taps, res, sopt_t,
            off_buckets)
        with stage("dense_opt"):
            updates, new_dense_state = dense_optimizer.update(
                g_dense, opt_state["dense"], dense0)
            new_dense = apply_updates(dense0, updates)
        new_params = _merge_dense(new_dense, {**params, "embedding": new_emb})
        new_state = {"emb": new_emb_state, "dense": new_dense_state}
        if scheduled:
            with stage("dense_opt"):
                new_state["count"] = opt_state["count"] + 1
            # concrete per-step lr for the out-of-jit host apply (offload)
            with stage("apply"):
                pending = {b: v + (lr(opt_state["count"]),)
                           for b, v in pending.items()}
        return new_params, new_state, loss, pending

    # jit is load-bearing, not just speed: memory-kind placement (offloaded
    # pinned-host buckets) only propagates from concrete input shardings at
    # a top-level jit boundary; donation lets XLA update tables in place.
    if donate is None:
        donate = default_donate()

    host_spans.install_gc_hook()

    def with_handle(run, core):
        """`run` as the step function: under the host span
        ``train/dispatch`` with the process's next step ordinal (the
        flattening of the arguments' leaves, the dispatch and, on an
        offloaded bucket, the host apply)."""
        def step_fn(params, opt_state, numerical, cats, labels):
            with host_spans.span("train/dispatch", rooted=True,
                                 step=host_spans.next_step()):
                return run(params, opt_state, numerical, cats, labels)
        step_fn.name, step_fn.lower = det_train_step.__name__, core.lower
        return init_fn, step_fn

    if not off_buckets:
        core = jax.jit(det_train_step,
                       donate_argnums=(0, 1) if donate else ())

        def run(params, opt_state, numerical, cats, labels):
            p, s, loss, _ = core(params, opt_state, numerical, cats, labels)
            return p, s, loss
        return with_handle(run, core)

    # Offloaded buckets: host tables/state are READ-ONLY inside the jitted
    # step (forward lookups + dedup happen there); the host-memory row apply
    # runs afterwards at top level, where XLA honors pinned_host output
    # placement. Donation skips params/opt_state because the host leaves
    # must survive the call.
    core = jax.jit(det_train_step)

    def run(params, opt_state, numerical, cats, labels):
        new_params, new_state, loss, pending = core(
            params, opt_state, numerical, cats, labels)
        tp = list(new_params["embedding"]["tp"])
        tp_s = list(new_state["emb"]["tp"])
        scales = new_params["embedding"].get("tp_scale")
        tp_scale = list(scales) if scales is not None else None
        for b, pend in pending.items():
            rep, sums, valid = pend[0], pend[1], pend[2]
            lr_t = pend[3] if len(pend) > 3 else None
            # the INPUT scale leaf: the step's output slot for a host
            # bucket is a zeroed placeholder (see drain_sparse_apply)
            scale_b = (params["embedding"]["tp_scale"][b]
                       if tp_scale is not None else None)
            out = emb.host_bucket_apply(
                b, params["embedding"]["tp"][b], opt_state["emb"]["tp"][b],
                rep, sums, valid, sopt, lr_value=lr_t, scale_h=scale_b)
            if scale_b is not None:
                # quantized storage (ISSUE 15): the SR write-back
                # refreshed both the payload and the per-row scales
                tp[b], tp_scale[b], tp_s[b] = out
            else:
                tp[b], tp_s[b] = out
        new_emb = {**new_params["embedding"], "tp": tp}
        if tp_scale is not None:
            new_emb["tp_scale"] = tp_scale
        new_params = {**new_params, "embedding": new_emb}
        new_state = {**new_state, "emb": {**new_state["emb"], "tp": tp_s}}
        return new_params, new_state, loss

    return with_handle(run, core)


def fit(model, params, data, steps: int, optimizer: str = "adagrad",
        lr=0.01, sparse: bool = True, opt_state=None, dense_optimizer=None,
        callbacks=(), eval_data=None, eval_every: int = 0,
        eval_steps: int = 16, log_every: int = 100, log_fn=print,
        stage=None, sync_every=None, preprocess=None, pipelined: bool = True,
        pipeline_depth=None, hot_sync_every: int = 0,
        store=None, publish_every=None, publish_dir=None,
        vocab=None, vocab_every: int = 16,
        lookahead=None, stale_ok: bool = False, registry=None):
    """Minimal training-loop driver — the role the reference fills with
    Keras `model.fit` + `DistributedOptimizer` + callbacks
    (reference dist_model_parallel.py:1270-1326, synthetic main.py:104-114).

    The loop never blocks on the loss between sync points: step dispatch is
    async, so the host stays ahead of the device the way the reference's
    graph-mode fit does (loss printed per interval, not materialized per
    step — reference examples/dlrm/main.py:219-221).

    Args:
      model: exposes `.embedding`, `loss_fn(params, numerical, cats, labels,
        taps=..., return_residuals=...)` and (for eval) `apply`.
      params: initial parameter pytree ({'embedding': ..., ...}).
      data: iterable/callable yielding (numerical, cats, labels) batches
        (jax or numpy arrays; a callable receives the step index).
      steps: number of optimizer steps.
      optimizer / lr / dense_optimizer: see make_sparse_train_step.
      sparse: use the sparse tapped path (default) or dense optax grads.
      callbacks: objects with optional `on_train_begin(params)` (e.g.
        BroadcastGlobalVariablesCallback) and/or
        `on_step(step, params, loss)` hooks (loss is a device scalar —
        call float() in the callback only if you accept a sync).
      eval_data / eval_every / eval_steps: run `evaluate` periodically.
      stage: per-batch staging function applied in the ingestion pipeline
        for iterable `data` (e.g. ``lambda b: stage_dp_batch(mesh, b)``).
        Default: mesh-aware dp staging when the model has a mesh, plain
        device_put otherwise. Multi-process numpy iterables require the
        mesh-aware form — a committed single-device array cannot be
        resharded onto a non-addressable global mesh.
      preprocess: optional host transform run between the reader and the
        staging worker (e.g. ``RawBinaryDataset.preprocess`` when `data`
        yields raw buffers, or an IntegerLookup raw-key translation).
        Iterable `data` only.
      pipelined: True (default) runs read/preprocess/stage each in a
        persistent background worker (utils.pipeline.IngestPipeline) so
        host ingestion overlaps the device step; False keeps the serial
        inline form (identical batch order — the A/B baseline). Iterable
        `data` only; callable `data` is always pulled inline.
      pipeline_depth: bound of each inter-stage queue (backpressure).
        ``None`` (default) reads ``DET_PIPELINE_DEPTH`` (default 2).
      sync_every: block on the loss every N steps. Default: 1 on
        multi-process runs (keeps per-process collectives in lockstep)
        and on the CPU backend (XLA:CPU's in-process collectives can
        deadlock when many steps are dispatched asynchronously), else 0
        (TPU: never block mid-run).
      store / publish_every / publish_dir: weight streaming (ISSUE 6):
        pass a `store.TableStore` over `params["embedding"]` and a
        publish cadence to turn this run into a live publisher — every
        step's touched-row keys accumulate host-side
        (`store.observe`; per-step numpy work proportional to the
        batch's unique ids — the price of delta completeness, unlike
        the SAMPLED hot-admission feed below), and every
        `publish_every` steps (``None`` reads ``DET_PUBLISH_EVERY``,
        default 0 = disabled) the loop commits the current pytrees and
        writes the next row-delta file (first publish = full snapshot)
        into `publish_dir` for `InferenceEngine.poll_updates` replicas.
        Leftover steps publish once more at the end. Sparse path only.
        History gains a 'published' list of publish infos.
      vocab / vocab_every: dynamic vocabulary (ISSUE 7, sparse path
        only): pass a `vocab.VocabManager` over `model.embedding` and
        the loop treats every batch's categorical inputs as RAW keys —
        each step translates them to physical rows host-side (unknown
        keys ride the fallback row) and feeds the admission tracker;
        every `vocab_every` steps the manager runs one
        admission/eviction cycle against the live params/opt-state
        (`maintain` — shapes never change, so the jitted step never
        recompiles). `vocab_every=0` disables maintenance entirely
        (translate/observe only — the 0-disables idiom of
        publish_every/hot_sync_every). Composes with publishing:
        rebound rows merge into the next delta's key set and the
        binding state is published as a ``vocab_v{version}.npz``
        sidecar consumers (`InferenceEngine.poll_updates`) load
        alongside the rows. History gains 'vocab_stats'.
      lookahead / stale_ok: device-pipeline depth (ISSUE 9, sparse path
        only). ``lookahead=1`` runs training through a
        `schedule.LookaheadEngine`: batch N+1's id exchange, table
        gather and activation all_to_all are issued in the same fused
        device program as batch N's dense forward/backward (no data
        dependency between them — auditable, see tools/hlo_audit.py's
        overlap arm), with the gradient transpose + sparse update
        trailing as the drain stage. Bit-exact against lookahead=0 by
        default (the engine patches prefetched activations for rows the
        previous step touched); ``stale_ok=True`` skips the patch with
        documented one-step-stale semantics (docs/userguide.md).
        ``lookahead=None`` reads ``DET_LOOKAHEAD`` (default 0).
        Refused compositions (loudly, here at fit time): the dense
        (sparse=False) path, hot-row replication (`hot_sync_every` /
        hot-sharded layers), and a `VocabManager` with maintenance
        cycles (``vocab_every != 0``) — a mid-window evict+rebind would
        invalidate already-prefetched physical rows. Translate-only
        vocab use (``vocab_every=0``) composes: batches are translated
        when PULLED, before the engine prefetches them.
      registry: optional `obs.MetricRegistry` — the run's ONE metric
        namespace (ISSUE 11). fit threads it through everything it
        drives: the ingest pipeline (``ingest/stage_seconds{stage=}``),
        the lookahead engine (patch counters + the compile-count
        gauges), and — via their ``use_registry`` rebind, only when an
        explicit registry is passed here — the publisher `store` and
        the `vocab` manager (a caller-attached registry on those
        components is respected otherwise); fit's own loop adds
        ``span_seconds{span=train/step}`` wall-time spans,
        ``train/steps`` / ``train/examples`` counters, the
        ``train/examples_per_sec`` / ``train/publish_cadence_steps``
        gauges, and the static ``exchange/*`` gauges from
        `exchange_padding_report` (exported at run end, so they reflect
        the final vocab occupancy). ``None`` creates a private per-run
        registry — either way the final snapshot lands in
        ``history["metrics_snapshot"]``, and ``DET_OBS_EXPORT=<path>``
        appends it as one JSONL line there (the soak-run export).
      hot_sync_every: hot-row replication cadence (layers built with
        `hot_rows=`, sparse path only): every N steps the loop runs
        `sync_hot_rows(admit=True)` — write hot rows back to the
        canonical tables and re-admit the currently-hottest set. The
        frequency feed (`observe_hot_ids` — host-side numpy counter
        work) is SAMPLED, not per-step: ~8 observed batches per sync
        window (`max(1, N // 8)` stride), because the per-unique-key
        counter update is real host time and zipfian admission only
        needs a frequency ESTIMATE — per-step observation would
        serialize exactly the class of host work the ingest pipeline
        exists to hide. 0 (default) leaves admission entirely to the
        caller.

    Returns (params, opt_state, history) — history is a dict of lists
    ('loss' as floats, drained from device at sync/log boundaries;
    optionally 'eval_auc').
    """
    from distributed_embeddings_tpu.obs.registry import MetricRegistry
    from distributed_embeddings_tpu.obs.spans import span
    reg = registry if registry is not None else MetricRegistry()
    if pipeline_depth is None:
        pipeline_depth = int(os.environ.get("DET_PIPELINE_DEPTH", "2"))
    if publish_every is None:
        publish_every = int(os.environ.get("DET_PUBLISH_EVERY", "0"))
    if lookahead is None:
        from distributed_embeddings_tpu.schedule import default_lookahead
        lookahead = default_lookahead()
    la_engine = None
    if lookahead:
        # unsupported compositions are refused HERE, loudly, not degraded:
        if not sparse:
            raise ValueError(
                "lookahead requires the sparse tapped path (sparse=True)")
        if hot_sync_every or getattr(getattr(model, "embedding", None),
                                     "_hot_buckets", None):
            raise NotImplementedError(
                "lookahead>0 does not compose with hot-row replication: "
                "the replicated hot shard moves densely every step, so "
                "prefetched activations cannot be patched from the "
                "touched-row set (at most one of hot_rows / lookahead "
                "per run, mirroring the hot-rows x vocab refusal)")
        if vocab is not None and vocab_every:
            raise NotImplementedError(
                "lookahead>0 does not compose with VocabManager "
                "maintenance cycles (vocab_every != 0): a same-window "
                "evict+rebind would invalidate physical rows the engine "
                "already prefetched — run with vocab_every=0 "
                "(translate-only) or lookahead=0")
        from distributed_embeddings_tpu.schedule import LookaheadEngine
        la_engine = LookaheadEngine(
            model, optimizer, lr=lr, dense_optimizer=dense_optimizer,
            lookahead=lookahead, stale_ok=stale_ok, registry=reg)
        step_fn = None
        if opt_state is None:
            opt_state = la_engine.init(params)
    elif sparse:
        init_fn, step_fn = make_sparse_train_step(
            model, optimizer, lr=lr, dense_optimizer=dense_optimizer)
        if opt_state is None:
            opt_state = init_fn(params)
    else:
        import optax
        opt = dense_optimizer or {
            "sgd": lambda: optax.sgd(lr),
            "adagrad": lambda: optax.adagrad(lr),
            "adam": lambda: optax.adam(lr)}[optimizer]()

        def loss_fn(p, numerical, cats, labels):
            return model.loss_fn(p, numerical, cats, labels)
        step_fn = make_train_step(loss_fn, opt, donate=False)
        if opt_state is None:
            opt_state = opt.init(params)

    for cb in callbacks:
        if hasattr(cb, "on_train_begin"):
            params = cb.on_train_begin(params)

    if sync_every is None:
        sync_every = (1 if (jax.process_count() > 1
                            or jax.default_backend() == "cpu") else 0)

    get_batch = data if callable(data) else None
    pipeline = None
    if get_batch is None:
        # full ingestion overlap: read, preprocess and device staging each
        # run in a persistent worker thread ahead of the consumer, so the
        # host-side batch cost hides under the device step (the reference's
        # prefetch-executor role, examples/dlrm/utils.py:231-254, extended
        # to every stage — docs/perf_model.md "Ingestion pipeline")
        from distributed_embeddings_tpu.utils.pipeline import staged_batches
        if stage is None:
            mesh = getattr(getattr(model, "embedding", None), "mesh", None)
            if mesh is not None:
                from distributed_embeddings_tpu.parallel.staging import (
                    stage_dp_batch)
                stage = lambda b: stage_dp_batch(mesh, b)  # noqa: E731
        # islice: the background reader must never pull past the batches
        # this run will consume — an over-pull would silently eat items
        # from a shared/reused source iterator when close() drains
        import itertools
        pipeline = staged_batches(itertools.islice(iter(data), steps),
                                  stage=stage, preprocess=preprocess,
                                  depth=pipeline_depth, pipelined=pipelined,
                                  registry=reg)
        it = iter(pipeline)
    else:
        it = None
    history = {"loss": []}
    pending = []     # device scalars since the last sync; drained to floats
    # at sync/log boundaries (where a block happens anyway) so long runs
    # never hold an unbounded number of live device buffers

    def drain():
        history["loss"].extend(float(l) for l in jax.device_get(pending))
        pending.clear()

    hot_emb = getattr(model, "embedding", None)
    hot_active = (sparse and hot_sync_every
                  and getattr(hot_emb, "_hot_buckets", None))
    hot_observe_stride = max(1, hot_sync_every // 8) if hot_active else 0
    publishing = bool(sparse and store is not None and publish_every)
    if publishing and publish_dir is None:
        raise ValueError("publish_every requires publish_dir")
    # one metric namespace per run (ISSUE 11): with an EXPLICIT run
    # registry, caller-built components rebind onto it so their
    # counters land in the same snapshot as fit's own. Without one,
    # they keep whatever registry they were built with — silently
    # stealing a store/vocab off a registry the caller attached for
    # their own export would freeze that registry mid-run.
    if registry is not None:
        if store is not None:
            store.use_registry(reg)
        if vocab is not None:
            vocab.use_registry(reg)
    if publishing:
        reg.gauge("train/publish_cadence_steps").set(publish_every)
    if vocab is not None and not sparse:
        raise ValueError("vocab management requires the sparse path "
                         "(sparse=True)")
    if vocab is not None and vocab.emb is not getattr(model, "embedding",
                                                      None):
        # same guard InferenceEngine applies: the manager's flat row
        # keys are plan-specific — maintaining another layer's params
        # with them would scatter into wrong rows silently
        raise ValueError(
            "vocab manager was built over a different layer than "
            "model.embedding; binding rows are plan-specific")
    steps_since_publish = 0

    def publish_now():
        drain()                     # params are about to be read host-side
        store.commit(params["embedding"], opt_state["emb"],
                     touched=(vocab.drain_touched()
                              if vocab is not None else None))
        from distributed_embeddings_tpu import faults
        try:
            if vocab is not None:
                # binding sidecar for the version about to publish —
                # written BEFORE the stream file, so any consumer that
                # can see the rows can also see the matching key->row
                # map (the reverse order would open a window where a
                # poll applies version V's rows but only finds the V-1
                # binding)
                from distributed_embeddings_tpu.vocab import (
                    vocab_state_path)
                import os as _os
                _os.makedirs(publish_dir, exist_ok=True)
                # full=False: the publish sidecar is the serving-grade
                # binding (keys + free list), NOT the trainer's counters
                # and stash — those are checkpoint state and would make
                # every sidecar table-sized under sustained drift
                vocab.save_state(
                    vocab_state_path(publish_dir, store.version),
                    full=False)
            history.setdefault("published", []).append(
                store.publish(publish_dir))
        except faults.InjectedCrash as e:
            # simulated publisher crash+restart (ISSUE 13): the tmp file
            # is orphaned on disk (the restarted publisher's first
            # publish sweeps it), nothing was renamed into the stream,
            # and the store's pending touched keys survive — the next
            # cadence republishes them under a later version, so no
            # consumer ever misses a row. ONLY the injected type is
            # caught; real publish failures still propagate.
            reg.counter("store/publish_crashes_total").inc()
            history.setdefault("publish_crashes", []).append(str(e)[:200])

    def pull(s):
        b = get_batch(s) if get_batch else next(it)
        if la_engine is not None and vocab is not None:
            # translate at PULL time under lookahead: the engine
            # prefetches this batch's exchange before the loop body
            # consumes it, so raw->physical translation must happen
            # first. Maintenance is refused with lookahead, so the
            # binding the early translation sees is the same one the
            # consume step would.
            n, c, lbl = b
            b = (n, vocab.translate(list(c), observe=True), lbl)
        return b

    next_batch = None
    examples_total = 0
    # per-strategy update-phase attribution (ISSUE 12): the gauge
    # update/impl{impl=} names the sparse-update kernel family the traced
    # step dispatches to (xla/tiled/pallas — resolved once, from the env
    # knobs and from what the dispatch sees of each bucket: optimizer,
    # rows, width), so snapshots and the soak harness can see WHICH path
    # actually ran: a kernel's label where any bucket takes one.
    if sparse:
        from distributed_embeddings_tpu.ops.sparse_update import (
            active_scatter_impl)
        impls = [active_scatter_impl(kind=optimizer, rows=b.rows_max,
                                     width=b.width)
                 for b in model.embedding.plan.tp_buckets]
        update_impl = next((i for i in impls if i != "xla"),
                           active_scatter_impl())
    else:
        update_impl = "dense"
    reg.gauge("update/impl", impl=update_impl).set(1)
    import time as _time
    t_run0 = _time.perf_counter()
    try:
        for step in range(steps):
            if la_engine is not None:
                batch = next_batch if next_batch is not None else pull(step)
                next_batch = pull(step + 1) if step + 1 < steps else None
            else:
                batch = pull(step)
            numerical, cats, labels = batch
            if vocab is not None and la_engine is None:
                # maintain BEFORE translating this batch: a maintain
                # cycle can evict key K and immediately rebind K's freed
                # row to a fresh key — a batch translated before the
                # cycle would still carry K -> row and land K's gradient
                # on the new tenant's zero-initialized row. Maintaining
                # first means every translation this step sees the
                # post-cycle binding.
                if vocab_every and step and step % vocab_every == 0:
                    p_emb, s_emb = vocab.maintain(params["embedding"],
                                                  opt_state["emb"])
                    params = {**params, "embedding": p_emb}
                    opt_state = {**opt_state, "emb": s_emb}
                # raw keys -> physical rows (host-side; admission
                # counters fed from the same stream), BEFORE the store's
                # touched-row observation — the delta key space is
                # physical rows
                cats = vocab.translate(list(cats), observe=True)
            if publishing:
                # EVERY step: the delta's key set must cover every row
                # the update touches (a sampled feed would silently
                # drop rows from the published view)
                store.observe(list(cats))
            if hot_active:
                if step % hot_observe_stride == 0:
                    hot_emb.observe_hot_ids(list(cats))
                if step and step % hot_sync_every == 0:
                    drain()     # params are about to be rewritten: sync
                    p_emb, s_emb = hot_emb.sync_hot_rows(
                        params["embedding"], opt_state["emb"], admit=True)
                    params = {**params, "embedding": p_emb}
                    opt_state = {**opt_state, "emb": s_emb}
            # span = host wall time of the step DISPATCH (plus any host
            # work the engine does); device time hides behind async
            # dispatch except at sync boundaries — the honest host-side
            # reading, same clock the reference's fit loop shows
            with span("train/step", reg):
                if la_engine is not None:
                    params, opt_state, loss = la_engine.step(
                        params, opt_state, batch, next_batch)
                else:
                    params, opt_state, loss = step_fn(
                        params, opt_state, jnp.asarray(numerical),
                        [jnp.asarray(c) for c in cats],
                        jnp.asarray(labels))
            pending.append(loss)
            shp = getattr(labels, "shape", None)
            n_ex = int(shp[0]) if shp else len(labels)
            examples_total += n_ex
            reg.counter("train/steps").inc()
            reg.counter("train/examples").inc(n_ex)
            if publishing:
                steps_since_publish += 1
                if steps_since_publish >= publish_every:
                    publish_now()
                    steps_since_publish = 0
            if sync_every and (step + 1) % sync_every == 0:
                drain()                       # explicit lockstep barrier
            if log_every and step % log_every == 0:
                drain()
                log_fn(f"step {step}/{steps}: loss={history['loss'][-1]:.5f}")
            elif len(pending) >= 4096:
                drain()    # no-sync runs still bound live device buffers
            for cb in callbacks:
                if hasattr(cb, "on_step"):
                    cb.on_step(step, params, loss)
            if eval_data is not None and eval_every and \
                    (step + 1) % eval_every == 0:
                auc = evaluate(model, params, eval_data, eval_steps)
                history.setdefault("eval_auc", []).append(auc)
                log_fn(f"step {step}: eval AUC={auc:.5f}")
    finally:
        if pipeline is not None:
            # ingestion accounting rides the history so callers (and the
            # bench record) can see where host time went this run
            history["ingest_stages"] = pipeline.stage_summaries()
            pipeline.close()
    drain()
    if la_engine is not None:
        history["lookahead_stats"] = dict(la_engine.stats)
    if hot_active:
        # leave the returned params canonical-consistent (hot rows written
        # back; residency unchanged) so raw-param consumers need no extra
        # sync — a numeric no-op for the training state itself
        p_emb, s_emb = hot_emb.sync_hot_rows(params["embedding"],
                                             opt_state["emb"])
        params = {**params, "embedding": p_emb}
        opt_state = {**opt_state, "emb": s_emb}
        history["hot_stats"] = hot_emb.hot_stats()
    if vocab is not None:
        if vocab_every:
            # tail cycle: keys that crossed the threshold after the last
            # scheduled maintain still admit before the run hands back
            # (vocab_every=0 = maintenance off: translate/observe only,
            # matching publish_every/hot_sync_every's 0-disables idiom)
            p_emb, s_emb = vocab.maintain(params["embedding"],
                                          opt_state["emb"])
            params = {**params, "embedding": p_emb}
            opt_state = {**opt_state, "emb": s_emb}
        history["vocab_stats"] = vocab.stats()
    if publishing and (steps_since_publish
                       or (vocab is not None and vocab.pending_publication)):
        # leftover tail steps — and any rows the tail vocab cycle just
        # rebound — reach replicas too
        publish_now()
    # ---- run-end telemetry (ISSUE 11): throughput gauge, the static
    # exchange/* gauges (exported LAST so occupancy reflects the tail
    # vocab cycle), the embedded snapshot, and the JSONL export hook
    elapsed = max(_time.perf_counter() - t_run0, 1e-9)
    reg.gauge("train/examples_per_sec").set(examples_total / elapsed)
    try:
        # kernel dispatch telemetry (ISSUE 12): gate verdicts per impl so
        # the SLO rule file can require the verdict's presence
        from distributed_embeddings_tpu.obs.instrument import (
            export_kernel_gauges)
        export_kernel_gauges(reg)
    except Exception as e:  # noqa: BLE001 - accounting never kills a run
        history["metrics_error"] = str(e)[:200]
    emb = getattr(model, "embedding", None)
    if emb is not None and hasattr(emb, "exchange_padding_report"):
        try:
            from distributed_embeddings_tpu.obs.instrument import (
                export_exchange_gauges)
            export_exchange_gauges(
                reg, emb, batch=max(examples_total // max(steps, 1), 1),
                vocab=vocab, lookahead=int(lookahead or 0))
        except Exception as e:  # noqa: BLE001 - accounting never kills a run
            history["metrics_error"] = str(e)[:200]
    history["metrics_snapshot"] = reg.snapshot()
    export_path = os.environ.get("DET_OBS_EXPORT")
    if export_path:
        # fsync: this is the run's FINAL export line — the postmortem
        # tail a crashed follow-on must still find on disk
        reg.export_jsonl(export_path, extra={"source": "fit"}, fsync=True)
    trace_path = os.environ.get("DET_OBS_TRACE")
    if trace_path:
        # flight-recorder window as a Perfetto-loadable chrome trace
        # (ISSUE 14): span timeline + version-lineage tracks for this run
        try:
            from distributed_embeddings_tpu.obs.trace import (
                default_recorder)
            default_recorder().export(trace_path)
        except Exception as e:  # noqa: BLE001 - accounting never kills a run
            history["metrics_error"] = str(e)[:200]
    return params, opt_state, history


def evaluate(model, params, data, steps: int = 16, preprocess=None,
             pipelined: bool = True) -> float:
    """Streaming AUC over `steps` batches (the reference's eval loop,
    examples/dlrm/main.py:223-243, without the hvd.allgather — outputs are
    already global jax.Arrays under SPMD). Iterable `data` is pulled through
    the background ingestion pipeline (read/preprocess workers) like `fit`;
    staging stays in the consumer here because the forward's inputs are
    tiny and eval runs are short."""
    from distributed_embeddings_tpu.utils.metrics import StreamingAUC

    auc = StreamingAUC()
    state = auc.init()
    get_batch = data if callable(data) else None
    pipeline = None
    if get_batch is None:
        import itertools
        from distributed_embeddings_tpu.utils.pipeline import (
            IngestPipeline, SerialPipeline)
        stages = ([("preprocess", preprocess)] if preprocess is not None
                  else [])
        # islice bounds the background read-ahead to exactly `steps`
        # items: eval is often called repeatedly on one shared iterator
        # (fit's eval_every loop) and must not eat batches beyond its run
        source = itertools.islice(iter(data), steps)
        pipeline = (IngestPipeline(source, stages) if pipelined
                    else SerialPipeline(source, stages))
        it = iter(pipeline)
    else:
        it = None
    fwd = jax.jit(lambda p, n, c: model.apply(p, n, c))
    try:
        for step in range(steps):
            numerical, cats, labels = (get_batch(step) if get_batch
                                       else next(it))
            logits = fwd(params, jnp.asarray(numerical),
                         [jnp.asarray(c) for c in cats])
            state = auc.update(state, jnp.asarray(labels).reshape(-1),
                               logits.reshape(-1))
    finally:
        if pipeline is not None:
            pipeline.close()
    return float(auc.result(state))
