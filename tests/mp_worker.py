"""Multi-process SPMD worker for tests/test_multiprocess.py.

Each invocation is ONE process of an N-process run over a shared 8-device
CPU mesh (4 local virtual devices per process when N=2) — the TPU-native
equivalent of the reference's `horovodrun -np N` test harness
(reference dist_model_parallel_test.py launches every case under real
multiprocess Horovod; SURVEY.md §4). The run is world-size-generic: the
SAME script with --nproc 1 is the single-process reference, and the parent
test asserts bit-identical checksums across launch shapes.

Covers, under real cross-process gloo collectives:
  * DistributedEmbedding planning + set_weights (per-process shard staging),
  * dp-input forward with dp/col-slice/row-slice groups active,
  * per-process input staging (stage_dp_batch / make_array_from_process_local_data),
  * a dense SGD train step through the sharded autodiff path,
  * get_weights reassembly (process 0 checksums the global tables).

Writes a JSON line of checksums to --out.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..")))  # repo root


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--nproc", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--local_devices", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--ckpt", default=None,
                    help="shared dir for the orbax checkpoint phase")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.local_devices}")
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distributed_embeddings_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    if args.nproc > 1:
        from distributed_embeddings_tpu.parallel.mesh import (
            initialize_distributed)
        initialize_distributed(
            coordinator_address=f"127.0.0.1:{args.port}",
            num_processes=args.nproc, process_id=args.pid)

    import numpy as np
    import jax.numpy as jnp
    import optax
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    from distributed_embeddings_tpu.parallel.staging import stage_dp_batch

    world = args.nproc * args.local_devices
    devs = jax.devices()
    assert len(devs) == world, (len(devs), world)
    mesh = create_mesh(devs)

    # mixed groups: 40 -> dp, 300..1000 -> table-parallel (largest ones
    # column-sliced by threshold), 4000 -> row-sliced
    sizes = ([(40, 8)] + [(300 + 100 * i, 8) for i in range(8)] + [(4000, 8)])
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner=None) for v, w in sizes], mesh=mesh,
        strategy="memory_balanced",
        data_parallel_threshold=512,
        column_slice_threshold=6000,
        row_slice_threshold=20000)

    rng = np.random.RandomState(7)
    weights = [rng.randn(v, w).astype(np.float32) * 0.05 for v, w in sizes]
    params = dist.set_weights(weights)

    batch = 16
    ids_global = [rng.randint(0, v, size=batch).astype(np.int32)
                  for v, _ in sizes]
    lo = args.pid * (batch // args.nproc)
    hi = lo + batch // args.nproc
    inputs = stage_dp_batch(mesh, [g[lo:hi] for g in ids_global])

    # checksums computed INSIDE jit: eager ops on non-fully-addressable
    # global arrays are illegal under multi-process, replicated jit outputs
    # are readable everywhere
    fwd = jax.jit(
        lambda p, xs: [jnp.sum(o * o) for o in dist.apply(p, xs)])
    checks = {"fwd": [round(float(s), 4) for s in fwd(params, inputs)]}

    # dense SGD step through sharded autodiff (grads follow param shardings
    # across processes), then a second forward
    opt = optax.sgd(0.5)
    opt_state = opt.init(params)

    def loss_fn(p, xs):
        outs = dist.apply(p, xs)
        return sum(jnp.sum(o * o) for o in outs) / batch

    @jax.jit
    def step(p, s, xs):
        loss, g = jax.value_and_grad(loss_fn)(p, xs)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss

    params, opt_state, loss = step(params, opt_state, inputs)
    checks["loss"] = round(float(loss), 5)
    checks["fwd2"] = [round(float(s), 4) for s in fwd(params, inputs)]

    # global weight reassembly after the update (collective under
    # multi-process — every process calls it together)
    got = dist.get_weights(params)
    checks["weights"] = [round(float(np.sum(np.abs(w))), 3) for w in got]

    # distributed orbax checkpoint: every process writes its own shards,
    # restore honors the plan shardings (multi-host checkpoint/resume)
    if args.ckpt:
        from distributed_embeddings_tpu.utils import checkpoint as ckpt
        ckpt.save_checkpoint(args.ckpt, params, force=True)
        restored = ckpt.restore_checkpoint(
            args.ckpt, params, shardings=dist.param_shardings())
        checks["ckpt_fwd"] = [round(float(s), 4)
                              for s in fwd(restored, inputs)]
        assert checks["ckpt_fwd"] == checks["fwd2"], (
            checks["ckpt_fwd"], checks["fwd2"])

    # sparse tapped train step (the production path): row-wise adagrad
    # updates flowing through shard_map across processes
    from distributed_embeddings_tpu.ops.sparse_update import (
        make_sparse_optimizer)
    sopt = make_sparse_optimizer("adagrad", 0.1)
    sstate = dist.init_sparse_state(params, sopt)

    def tap_loss(taps, p, xs):
        outs, res = dist.apply(p, xs, taps=taps, return_residuals=True)
        return sum(jnp.sum(o * o) for o in outs) / batch, res

    @jax.jit
    def sparse_step(p, s, xs):
        taps = dist.make_taps(xs)
        (loss, res), g_taps = jax.value_and_grad(
            tap_loss, has_aux=True)(taps, p, xs)
        new_p, new_s, _pending = dist.sparse_update(p, s, g_taps, res, sopt)
        return new_p, new_s, loss

    sparams, sstate, sloss = sparse_step(params, sstate, inputs)
    checks["sparse_loss"] = round(float(sloss), 5)
    checks["sparse_fwd"] = [round(float(s), 4)
                            for s in fwd(sparams, inputs)]

    # dp_input=False: each process supplies only its own ranks' features
    # (remote ranks are None), global batch everywhere
    dist_mp = DistributedEmbedding(
        [Embedding(v, w, combiner=None) for v, w in sizes[1:-1]], mesh=mesh,
        strategy="memory_balanced", dp_input=False,
        input_max_hotness=[1] * len(sizes[1:-1]))
    mp_params = dist_mp.set_weights(weights[1:-1])
    local_ranks = {r for r, _ in dist_mp._rank_of_device()}
    mp_inputs = []
    for r, rank_ids in enumerate(dist_mp.strategy.input_ids_list):
        if r not in local_ranks:
            mp_inputs.append(None)
            continue
        rr = np.random.RandomState(100 + r)
        mp_inputs.append([
            jnp.asarray(rr.randint(
                0, sizes[1:-1][dist_mp.strategy.input_groups[1][pos]][0],
                size=batch).astype(np.int32))
            for pos in rank_ids])
    mp_outs = dist_mp.apply_mp(mp_params, mp_inputs)
    sums = jax.jit(lambda *os: [jnp.sum(o * o) for o in os])(*mp_outs)
    checks["mp_fwd"] = [round(float(s), 4) for s in sums]

    # true-splits exchange under REAL cross-process collectives: the
    # ragged-exchange emulation (all_gather + masked gather) must produce
    # the same forward as the padded path over gloo, not just on the
    # single-process virtual mesh
    prev_rg = os.environ.get("DET_RAGGED_EXCHANGE")
    os.environ["DET_RAGGED_EXCHANGE"] = "1"
    try:
        dist_rg = DistributedEmbedding(
            [Embedding(v, w, combiner="sum") for v, w in sizes[1:-1]],
            mesh=mesh, strategy="comm_balanced",
            input_max_hotness=[3] * len(sizes[1:-1]))
        rg_params = dist_rg.set_weights(weights[1:-1])
        rg_rng = np.random.RandomState(31)
        rg_global = [rg_rng.randint(0, v, size=(batch, 3)).astype(np.int32)
                     for v, _ in sizes[1:-1]]
        rg_inputs = stage_dp_batch(mesh, [g[lo:hi] for g in rg_global])
        rg_fwd = jax.jit(
            lambda p, xs: [jnp.sum(o * o) for o in dist_rg.apply(p, xs)])
        rg_sums = [float(s) for s in rg_fwd(rg_params, rg_inputs)]
        checks["ragged_exchange_fwd"] = [round(s, 4) for s in rg_sums]
    finally:
        if prev_rg is None:
            os.environ.pop("DET_RAGGED_EXCHANGE", None)
        else:
            os.environ["DET_RAGGED_EXCHANGE"] = prev_rg
    # and the padded path on the same model/inputs must agree in-process
    # (tolerance, not bit equality: the two paths reduce in different
    # orders — same contract as test_exchange's allclose). Force the flag
    # OFF here — if the caller exported DET_RAGGED_EXCHANGE=1 the restore
    # above would otherwise make this a vacuous ragged-vs-ragged compare.
    os.environ["DET_RAGGED_EXCHANGE"] = "0"
    try:
        dist_pd = DistributedEmbedding(
            [Embedding(v, w, combiner="sum") for v, w in sizes[1:-1]],
            mesh=mesh, strategy="comm_balanced",
            input_max_hotness=[3] * len(sizes[1:-1]))
    finally:
        if prev_rg is None:
            os.environ.pop("DET_RAGGED_EXCHANGE", None)
        else:
            os.environ["DET_RAGGED_EXCHANGE"] = prev_rg
    pd_fwd = jax.jit(
        lambda p, xs: [jnp.sum(o * o) for o in dist_pd.apply(p, xs)])
    pd_sums = [float(s)
               for s in pd_fwd(dist_pd.set_weights(weights[1:-1]),
                               rg_inputs)]
    np.testing.assert_allclose(rg_sums, pd_sums, rtol=1e-5, atol=1e-5)

    # fit loop with ITERABLE per-process data: exercises fit's default
    # mesh-aware staging (stage_dp_batch / make_array_from_process_local_
    # data) — a committed single-device device_put cannot be resharded
    # onto a non-addressable global mesh, so this path only works if the
    # default stage is mesh-aware (round-3 fix), and the sync_every=1
    # lockstep default keeps the processes' collectives aligned
    class _FitModel:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, p, numerical, cats, labels, taps=None,
                    return_residuals=False):
            del numerical
            out = self.embedding(p["embedding"], list(cats), taps=taps,
                                 return_residuals=return_residuals)
            outs, res = out if return_residuals else (out, None)
            x = jnp.concatenate(
                [o.reshape(o.shape[0], -1) for o in outs],
                axis=1).astype(jnp.float32)
            loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    from distributed_embeddings_tpu import training

    b_local = batch // args.nproc
    rngf = np.random.RandomState(21)          # same stream on every process
    fit_batches = []
    for _ in range(6):
        cats_g = [rngf.randint(0, v, size=batch).astype(np.int32)
                  for v, _ in sizes]
        labs_g = rngf.randn(batch).astype(np.float32)
        fit_batches.append(
            (np.zeros((b_local, 1), np.float32),
             [c[lo:lo + b_local] for c in cats_g],
             labs_g[lo:lo + b_local]))
    fit_params, _, fit_hist = training.fit(
        _FitModel(dist), {"embedding": dist.set_weights(weights)},
        iter(fit_batches), steps=6, optimizer="adagrad", lr=0.1,
        sparse=True, log_every=0, log_fn=lambda *_: None)
    checks["fit_loss"] = [round(l, 5) for l in fit_hist["loss"]]
    checks["fit_fwd"] = [round(float(s), 4)
                         for s in fwd(fit_params["embedding"], inputs)]

    # offloaded-bucket sparse training under TRUE multi-process: the
    # pershard host apply must assemble non-fully-addressable pinned-host
    # buckets from each process's LOCAL shards only, with no device
    # round-trip (VERDICT r4 item 3 at world > 1; single-process coverage
    # is tests/test_offload.py)
    off_sizes = [(5000, 8), (40, 8), (5000, 8), (64, 8),
                 (128, 8), (96, 8), (80, 8), (72, 8)]
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore", RuntimeWarning)  # no-host-mem case
        dist_off = DistributedEmbedding(
            [Embedding(v, w, combiner="sum") for v, w in off_sizes],
            mesh=mesh, gpu_embedding_size=2500 * 8)
    # the layer's own capability probe decides (no duplicated memory-kind
    # probe here); skip the phase only where the backend has no host memory
    if dist_off._offload_enabled:
        assert any(b.offload for b in dist_off.plan.tp_buckets)
        rngo = np.random.RandomState(40)
        off_w = [rngo.randn(v, w).astype(np.float32) * 0.1
                 for v, w in off_sizes]
        off_model = _FitModel(dist_off)
        off_init, off_step = training.make_sparse_train_step(
            off_model, "adam", lr=0.05)
        off_p = {"embedding": dist_off.set_weights(off_w)}
        off_s = off_init(off_p)
        rngb = np.random.RandomState(41)       # same stream on every process
        for _ in range(2):
            cats_g = [rngb.randint(0, v, size=batch).astype(np.int32)
                      for v, _ in off_sizes]
            labs_g = rngb.randn(batch).astype(np.float32)
            off_cats = stage_dp_batch(mesh, [c[lo:hi] for c in cats_g])
            off_labs = stage_dp_batch(mesh, [labs_g[lo:hi]])[0]
            off_p, off_s, off_loss = off_step(
                off_p, off_s, np.zeros((batch // args.nproc, 1), np.float32),
                off_cats, off_labs)
            off_loss = float(off_loss)
        checks["offload_loss"] = round(off_loss, 5)
        modes = dist_off.host_apply_modes()
        assert modes and all(m in ("native", "pershard")
                             for m in modes.values()), (
            f"multi-process offloaded apply took a round-trip: {modes}")
        off_got = dist_off.get_weights(off_p["embedding"])
        checks["offload_weights"] = [round(float(np.sum(np.abs(w))), 3)
                                     for w in off_got]

    if args.pid == 0:
        with open(args.out, "w") as f:
            json.dump(checks, f)
    print(f"proc {args.pid}/{args.nproc}: {json.dumps(checks)[:200]}",
          flush=True)


if __name__ == "__main__":
    main()
