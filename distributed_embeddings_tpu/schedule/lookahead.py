"""Lookahead execution engine: overlap batch N+1's embedding exchanges
with batch N's dense compute (ISSUE 9, ROADMAP item 1).

The production sparse step is strictly sequential on device:

    id exchange -> gather -> activation all_to_all -> dense fwd/bwd
                -> gradient transpose -> sparse update

The reference hides the exchange behind Horovod's NCCL streams; under
SPMD the same latency win needs the step itself restructured. This
engine splits it into three stages with a TWO-BATCH carry:

  prefetch  batch N+1's id exchange, table gather and activation
            all_to_all/psum_scatter run as a detached subgraph
            (`DistributedEmbedding.apply(_want_exchange=True)`) whose
            ops have NO data dependency on the dense stage — inside the
            one fused jitted step, XLA's latency-hiding scheduler is
            free to run these collectives under the dense compute
            (auditable: tools/hlo_audit.py's overlap arm proves the
            independence on the lowered HLO).
  dense     batch N's forward/backward over the CARRIED activations
            (`staged_exchange_scope`) — dp tables and the MLPs see
            current params; grads w.r.t. the carried activation blocks
            fall out of autodiff.
  drain     the dp->mp gradient transpose (`exchange_transpose`, the
            exact bwd collectives the monolithic step's autodiff runs)
            + the row-sparse table update (`ops.sparse_update.
            drain_sparse_apply` — the tail shared with
            `make_sparse_train_step`).

Correctness seam — the one real coupling between stages: batch N's
sparse update rewrites rows batch N+1's prefetch may have already
gathered. Both sides of that intersection are knowable HOST-side from
ids alone (`touched_row_keys` of N x the prefetched ids of N+1, per
sample via `prefetch_stale_mask`), so the engine re-exchanges exactly
the affected SAMPLES against the post-update tables at the start of the
next fused step (`patch_staged_carry`) — a fixed-capacity sub-batch, so
the compiled step never re-specializes. Untouched rows are unchanged by
a row-sparse update (sgd/adagrad write only touched rows; adam is lazy
per-touched-row by construction — the load-bearing property PR 4
documented), so patched == sequential BIT-exactly, by induction over
steps. A stale set larger than the patch capacity falls back to
re-running the already-compiled prefetch executable on the current
tables (bit-exact recompute, zero extra compiles). ``stale_ok=True``
skips the patch entirely: documented one-step-stale semantics (the
async-embedding trade common to prefetching parameter servers) for the
throughput ceiling.

Refused compositions (loud, at construction / fit time): hot-row
replication (the replicated hot shard moves DENSELY every step — under
adam even rows absent from the batch, so the touched-row patch cannot
cover it), host-offloaded buckets (their lookup runs outside the jitted
stage), multi-process runs (per-process patch bookkeeping under SPMD
lockstep), ragged/sparse input forms (per-sample patch selection would
be shape-dynamic), custom dp layer classes, and VocabManager rebind
cycles mid-window (fit refuses `vocab_every != 0`).

``lookahead=0`` delegates wholesale to `make_sparse_train_step` — the
bit-identical pre-pipeline step.
"""

import os
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds, SparseIds
from distributed_embeddings_tpu.ops.sparse_update import drain_sparse_apply
from distributed_embeddings_tpu.parallel.staging import DoubleBufferSlots
from distributed_embeddings_tpu.training import (
    _dense_part, _merge_dense, _sparse_optimizer_setup, apply_updates,
    default_donate, make_sparse_train_step)

__all__ = ["LookaheadEngine", "default_lookahead"]


def default_lookahead() -> int:
    """``DET_LOOKAHEAD`` environment default for `training.fit`'s
    ``lookahead`` argument (0 = the sequential step; an explicit
    argument always wins)."""
    v = os.environ.get("DET_LOOKAHEAD", "0")
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"DET_LOOKAHEAD={v!r}: expected an integer")
    if n not in (0, 1):
        raise ValueError(
            f"DET_LOOKAHEAD={n}: only depths 0 (sequential) and 1 "
            "(one-batch prefetch) are supported")
    return n


class LookaheadEngine:
    """Staged-pipeline train step with a two-batch carry (module doc).

    Args:
      model: the `make_sparse_train_step` contract — exposes
        ``.embedding`` and ``loss_fn(params, numerical, cats, labels)``.
      optimizer / lr / dense_optimizer / strategy / fold_sort / donate:
        as `make_sparse_train_step` (the engine's lookahead=0 path IS
        that step; the fused step shares its optimizer construction).
      lookahead: 0 (sequential, bit-identical to the monolithic step) or
        1 (one-batch prefetch).
      stale_ok: skip the correctness patch — prefetched activations may
        be one sparse-update stale (bit-exactness forfeited, documented
        in docs/userguide.md).
      patch_capacity: max stale samples the fused step re-exchanges per
        step (default batch//8, rounded up to a multiple of the device
        count). Overflow falls back to a full prefetch recompute on the
        current tables — still bit-exact, no extra compile.

    Use:
      engine = LookaheadEngine(model, "adagrad", lr=0.05)
      opt_state = engine.init(params)
      for i in range(steps):
          params, opt_state, loss = engine.step(
              params, opt_state, batches[i],
              batches[i + 1] if i + 1 < steps else None)
    """

    def __init__(self, model, optimizer: str = "adagrad", lr=0.01,
                 dense_optimizer=None, strategy: str = "auto",
                 lookahead: int = 1, stale_ok: bool = False,
                 patch_capacity: Optional[int] = None,
                 donate: Optional[bool] = None, fold_sort: bool = True,
                 registry=None):
        if lookahead not in (0, 1):
            raise ValueError(
                f"lookahead={lookahead}: only depths 0 and 1 are "
                "supported (a deeper pipeline would need k-step patch "
                "composition)")
        self.model = model
        self.emb = model.embedding
        self.lookahead = int(lookahead)
        self.stale_ok = bool(stale_ok)
        self.patch_capacity = patch_capacity
        self.stats = {"steps": 0, "cold_fills": 0, "patch_overflows": 0,
                      "patched_steps": 0, "patched_samples": 0,
                      "patched_samples_max": 0}
        # registry mirror of self.stats (ISSUE 11): counters bumped from
        # THIS host-side driver body only — never inside a traced fn —
        # plus the per-stage compile-count gauges the "must stay 1" SLO
        # rule reads (tools/slo_tier1.json)
        from distributed_embeddings_tpu.obs.registry import MetricRegistry
        self._metrics = (registry if registry is not None
                         else MetricRegistry())
        emb = self.emb
        # ONE optimizer construction (training._sparse_optimizer_setup)
        # shared with the monolithic step — the bit-exactness contract
        # between the two step forms depends on it
        scheduled, sopt_for, dense_optimizer = _sparse_optimizer_setup(
            optimizer, lr, strategy, dense_optimizer,
            widths=emb.plan_widths())
        # lookahead=0 path AND the shared init_fn: the monolithic step
        # itself — delegation is what makes depth 0 bit-identical
        self._init_fn, self._base_step = make_sparse_train_step(
            model, optimizer, lr=lr, dense_optimizer=dense_optimizer,
            strategy=strategy, donate=donate, fold_sort=fold_sort)
        if self.lookahead == 0:
            self._prefetch = self._fused = None
            self._slots = None
            self._prev_touched = None
            return

        # ---- refusals: every composition the patch cannot cover -----
        if jax.process_count() > 1:
            raise NotImplementedError(
                "lookahead>0 is single-process only: per-process patch "
                "bookkeeping must stay in SPMD lockstep across hosts, "
                "which this engine does not coordinate yet")
        if emb._hot_buckets:
            raise NotImplementedError(
                "lookahead>0 does not support hot-row replicated buckets "
                "(the replicated hot shard updates densely every step — "
                "under adam even rows absent from the batch — so the "
                "touched-row patch cannot make prefetched activations "
                "exact)")
        if emb._offload_enabled:
            raise NotImplementedError(
                "lookahead>0 does not support host-offloaded buckets: "
                "their lookups run outside the jitted stage and cannot "
                "be carried or patched")
        if getattr(emb, "_dp_custom_layers", None):
            raise NotImplementedError(
                "lookahead>0 does not support custom embedding layer "
                "classes on dp tables (staged forwards run them outside "
                "shard_map)")
        if getattr(emb, "quantized_buckets", []):
            raise NotImplementedError(
                "lookahead>0 does not support quantized (int8/fp8) "
                "bucket storage: the drain applies f32 row rules and "
                "the touched-row patch carries f32 activations — "
                "neither decodes or re-encodes the per-row "
                "payload+scale leaves an HBM-resident quantized bucket "
                "trains through")
        if (not emb.strategy.input_groups[1]
                and not emb.strategy.input_groups[2]):
            raise ValueError(
                "lookahead>0 has nothing to prefetch: every table in "
                "this plan is data-parallel (no exchange collectives on "
                "the critical path — run with lookahead=0)")

        sort_spec = (optimizer, strategy) if fold_sort else None
        sort_arg = sort_spec if sort_spec is not None else False
        if donate is None:
            donate = default_donate()

        def constrain_carry(ex, row, res):
            """Pin the carry's shardings to the canonical layout (ex
            [world_src, B@axis, ...], everything else leading-axis
            sharded). Both carry producers — the warmup/fallback
            prefetch executable and the fused step — emit the same
            layout, so the fused step compiles ONCE per (plan,
            batch-shape) instead of re-specializing on whichever
            GSPMD-inferred output sharding fed it first."""
            if emb.mesh is None or emb.world_size == 1:
                return {"ex": ex, "row": row, "res": res}
            from jax.sharding import NamedSharding, PartitionSpec as P

            def con(tree, spec):
                sh = NamedSharding(emb.mesh, spec)
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, sh),
                    tree)

            res = type(res)(res.key, con(res.tp_ids, P(emb.axis)),
                            con(res.tp_w, P(emb.axis)),
                            con(res.row_ids, P(emb.axis)),
                            con(res.row_w, P(emb.axis)),
                            con(res.tp_sort, P(emb.axis)),
                            con(res.row_sort, P(emb.axis)),
                            res.hot_pos, res.hot_w)
            return {"ex": con(ex, P(None, emb.axis)),
                    "row": con(row, P(emb.axis)), "res": res}

        def prefetch_fn(emb_params, cats):
            ex, row, res = emb.apply(emb_params, list(cats),
                                     return_residuals=True,
                                     residual_sort=sort_arg,
                                     _want_exchange=True)
            return constrain_carry(ex, row, res)

        def run_stages(params, opt_state, ex, row, res, numerical, cats,
                       labels, next_cats):
            # ---- prefetch stage (batch N+1): traced FIRST and reading
            # only params + next_cats — no data dependency on the dense
            # stage below, which is the whole point (the overlap arm of
            # tools/hlo_audit.py asserts it on the lowered module)
            nex, nrow, nres = emb.apply(params["embedding"],
                                        list(next_cats),
                                        return_residuals=True,
                                        residual_sort=sort_arg,
                                        _want_exchange=True)

            # ---- dense stage (batch N) over the carried activations
            def loss_staged(dense0, ex_in, row_in):
                p = _merge_dense(dense0, params)
                with emb.staged_exchange_scope(ex_in, row_in):
                    return model.loss_fn(p, numerical, list(cats), labels)

            dense0 = _dense_part(params)
            loss, (g_dense, g_ex, g_row) = jax.value_and_grad(
                loss_staged, argnums=(0, 1, 2))(dense0, ex, row)

            # ---- drain stage: explicit dp->mp gradient transpose (the
            # monolithic step's bwd collectives) + row-sparse update
            g_taps = emb.exchange_transpose(g_ex, g_row, res.key)
            sopt_t = sopt_for(opt_state)
            new_emb, new_emb_state, _ = drain_sparse_apply(
                emb, params["embedding"], opt_state["emb"], g_taps, res,
                sopt_t)
            updates, new_dense_state = dense_optimizer.update(
                g_dense, opt_state["dense"], dense0)
            new_dense = apply_updates(dense0, updates)
            new_params = _merge_dense(
                new_dense, {**params, "embedding": new_emb})
            new_state = {"emb": new_emb_state, "dense": new_dense_state}
            if scheduled:
                new_state["count"] = opt_state["count"] + 1
            return (new_params, new_state, loss,
                    constrain_carry(nex, nrow, nres))

        if self.stale_ok:
            def fused_fn(params, opt_state, carry, numerical, cats,
                         labels, next_cats):
                return run_stages(params, opt_state, carry["ex"],
                                  carry["row"], carry["res"], numerical,
                                  cats, labels, next_cats)
        else:
            def fused_fn(params, opt_state, carry, patch_cats, patch_idx,
                         numerical, cats, labels, next_cats):
                # ---- patch stage: re-exchange the stale samples against
                # the CURRENT tables (they carry the previous batch's
                # update) and overwrite their carried activations — the
                # bit-exactness seam. residual_sort=False: the patch is a
                # plain activation recompute, zero extra sort ops.
                ex, row, res = carry["ex"], carry["row"], carry["res"]
                pex, prow, _ = emb.apply(params["embedding"],
                                         list(patch_cats),
                                         return_residuals=True,
                                         residual_sort=False,
                                         _want_exchange=True)
                batch = (ex[0].shape[1] if ex else row[0].shape[0])
                ex, row = emb.patch_staged_carry(ex, row, pex, prow,
                                                 patch_idx, batch)
                return run_stages(params, opt_state, ex, row, res,
                                  numerical, cats, labels, next_cats)

        self._prefetch = jax.jit(prefetch_fn)
        self._fused = jax.jit(fused_fn,
                              donate_argnums=(0, 1, 2) if donate else ())
        self._slots = DoubleBufferSlots()
        self._prev_touched = None

    # ------------------------------------------------------------ state
    def init(self, params):
        """Sparse+dense optimizer state (same pytree as
        `make_sparse_train_step`'s init_fn — states are interchangeable
        between lookahead depths)."""
        return self._init_fn(params)

    def reset(self):
        """Flush the pipeline: drop the carried prefetch and touched-row
        memory. Call after mutating params/tables OUTSIDE the engine
        (checkpoint restore, store.apply_published, manual edits) — the
        next step re-fills the carry from the new tables."""
        if self._slots is not None:
            self._slots.clear()
        self._prev_touched = None

    def compile_counts(self) -> dict:
        """Executable-cache sizes per stage — the compile-count
        stability gate reads these (one entry per (plan, batch-shape),
        regardless of how many steps ran)."""
        if self.lookahead == 0:
            return {}
        return {"prefetch": self._prefetch._cache_size(),
                "fused": self._fused._cache_size()}

    # ------------------------------------------------------------ step
    @staticmethod
    def _canon(c):
        if isinstance(c, (RaggedIds, SparseIds)):
            raise NotImplementedError(
                "lookahead>0 supports dense id inputs (and (ids, "
                "weights) tuples) only: ragged/sparse per-sample patch "
                "selection would be shape-dynamic and recompile the "
                "fused step every batch")
        if isinstance(c, tuple):
            return tuple(jnp.asarray(e) for e in c)
        return jnp.asarray(c)

    def _capacity(self, batch: int) -> int:
        cap = (self.patch_capacity if self.patch_capacity is not None
               else max(1, batch // 8))
        world = self.emb.world_size
        cap = max(cap, world)
        return -(-cap // world) * world      # round up to a world multiple

    @staticmethod
    def _host_cats(cats):
        """ONE device->host materialization of the id inputs per step,
        shared by the stale mask, the patch gather and the touched-row
        accounting (each would otherwise fetch the same tensors again —
        real host-path time at DLRM id volumes)."""
        def h(x):
            return np.asarray(jax.device_get(x))
        return [tuple(h(e) for e in c) if isinstance(c, tuple) else h(c)
                for c in cats]

    def _build_patch(self, host_cats, idx_np, cap: int, batch: int):
        """Fixed-shape patch sub-batch: rows `idx_np` of every
        (host-materialized) input, padded to `cap` with sample 0
        (scatter index `batch` => padding lanes drop device-side)."""
        idx = np.full((cap,), batch, np.int64)
        idx[:len(idx_np)] = idx_np
        safe = np.zeros((cap,), np.int64)
        safe[:len(idx_np)] = idx_np
        pcats = []
        for x in host_cats:
            if isinstance(x, tuple):
                pcats.append(tuple(jnp.asarray(a[safe]) for a in x))
            else:
                pcats.append(jnp.asarray(x[safe]))
        return pcats, jnp.asarray(idx, jnp.int32)

    def step(self, params, opt_state, batch, next_batch=None):
        """One optimizer step over `batch`; `next_batch` is the batch
        the engine prefetches for (None at the tail — the step then
        feeds the current cats as a throwaway prefetch operand so the
        compiled executable never re-specializes).

        The pipeline contract: the object passed as `next_batch` here
        must be the object passed as `batch` on the NEXT call — the
        carry is tagged with its identity and a mismatch (or a cold
        start) falls back to a fresh, bit-exact prefetch on the current
        tables.

        Returns (params, opt_state, loss)."""
        num, cats, labels = batch
        if self.lookahead == 0:
            return self._base_step(params, opt_state, jnp.asarray(num),
                                   [self._canon(c) for c in cats],
                                   jnp.asarray(labels))
        cats = [self._canon(c) for c in cats]
        first = cats[0][0] if isinstance(cats[0], tuple) else cats[0]
        batch_n = int(first.shape[0])
        cap = self._capacity(batch_n)
        emb = self.emb

        host_cats = None if self.stale_ok else self._host_cats(cats)
        idx_np = np.zeros((0,), np.int64)
        cold = None
        if self._slots.current is None or self._slots.tag is not batch:
            cold = "cold_fills"
        elif not self.stale_ok and self._prev_touched is not None:
            mask = emb.prefetch_stale_mask(host_cats, self._prev_touched)
            idx_np = np.nonzero(mask)[0]
            if len(idx_np) > cap:
                cold = "patch_overflows"
        if cold is not None:
            # fresh prefetch on the CURRENT tables — bit-exact by
            # definition (it is the sequential computation), and it
            # reuses the already-compiled warmup executable
            self._slots.clear()
            carry = self._prefetch(params["embedding"], cats)
            idx_np = np.zeros((0,), np.int64)
            self.stats[cold] += 1
            self._metrics.counter(f"lookahead/{cold}").inc()
        else:
            carry = self._slots.take()

        nb_cats = (cats if next_batch is None
                   else [self._canon(c) for c in next_batch[1]])
        if self.stale_ok:
            params, opt_state, loss, new_carry = self._fused(
                params, opt_state, carry, jnp.asarray(num), cats,
                jnp.asarray(labels), nb_cats)
        else:
            patch_cats, patch_idx = self._build_patch(host_cats, idx_np,
                                                      cap, batch_n)
            params, opt_state, loss, new_carry = self._fused(
                params, opt_state, carry, patch_cats, patch_idx,
                jnp.asarray(num), cats, jnp.asarray(labels), nb_cats)
        self._slots.stage(new_carry,
                          tag=next_batch if next_batch is not None else None)
        if not self.stale_ok:
            # host-side id accounting for the NEXT step's patch (on the
            # already-materialized host arrays); runs while the device
            # chews on the dispatched step
            self._prev_touched = emb.touched_row_keys(host_cats)
        self.stats["steps"] += 1
        n_patched = int(len(idx_np))
        if n_patched:
            self.stats["patched_steps"] += 1
            self.stats["patched_samples"] += n_patched
            self.stats["patched_samples_max"] = max(
                self.stats["patched_samples_max"], n_patched)
        m = self._metrics
        m.counter("lookahead/steps").inc()
        if n_patched:
            m.counter("lookahead/patched_steps").inc()
            m.counter("lookahead/patched_samples").inc(n_patched)
            m.gauge("lookahead/patched_samples_max").set(
                self.stats["patched_samples_max"])
        # executable-cache sizes as gauges — the compile-count SLO
        # ("must stay 1 per (plan, batch-shape)") reads these
        m.gauge("lookahead/compiles", stage="prefetch").set(
            self._prefetch._cache_size())
        m.gauge("lookahead/compiles", stage="fused").set(
            self._fused._cache_size())
        return params, opt_state, loss

    # ------------------------------------------------------- lowering
    def lower_prefetch(self, params, cats):
        """`jax.jit(...).lower` of the prefetch stage (audit/bench)."""
        return self._prefetch.lower(params["embedding"],
                                    [self._canon(c) for c in cats])

    def lower_fused(self, params, opt_state, batch, next_batch=None):
        """Lower (don't compile) the fused staged step for one batch —
        the module tools/hlo_audit.py's overlap arm analyzes."""
        num, cats, labels = batch
        cats = [self._canon(c) for c in cats]
        first = cats[0][0] if isinstance(cats[0], tuple) else cats[0]
        batch_n = int(first.shape[0])
        carry = jax.eval_shape(self._prefetch, params["embedding"], cats)
        nb_cats = (cats if next_batch is None
                   else [self._canon(c) for c in next_batch[1]])
        if self.stale_ok:
            return self._fused.lower(params, opt_state, carry,
                                     jnp.asarray(num), cats,
                                     jnp.asarray(labels), nb_cats)
        cap = self._capacity(batch_n)
        patch_cats, patch_idx = self._build_patch(
            self._host_cats(cats), np.zeros((0,), np.int64), cap, batch_n)
        return self._fused.lower(params, opt_state, carry, patch_cats,
                                 patch_idx, jnp.asarray(num), cats,
                                 jnp.asarray(labels), nb_cats)
