"""DLRM training on TPU — the flagship acceptance workload.

TPU-native re-design of the reference DLRM example
(reference: examples/dlrm/main.py): bottom MLP -> 26 embeddings via
DistributedEmbedding -> dot interaction -> top MLP, trained with a single
jit-compiled SPMD step over a device mesh (no Horovod choreography, no
broadcast bootstrapping — same program + seed everywhere).

Datasets:
  * --data_path pointing at the Criteo-1TB split-binary layout
    (label.bin / numerical.bin / cat_*.bin, see models/data.py) — read with
    native pread prefetch.
  * --synthetic (default): random ids at the MLPerf DLRM shapes.

Examples:
  python examples/dlrm/main.py --synthetic --steps 64 --batch_size 2048 \
      --devices 8 --force_cpu          # 8 virtual CPU devices, smoke run
  python examples/dlrm/main.py --data_path /data/criteo --amp
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))  # repo root

import argparse
import time
from contextlib import nullcontext

# Criteo-1TB MLPerf vocab sizes (reference examples/dlrm/main.py:47)
CRITEO_TABLE_SIZES = [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771, 25641295,
    39664984, 585935, 12972, 108, 36,
]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data_path", default=None,
                   help="Criteo split-binary dir (train/ + test/)")
    p.add_argument("--synthetic", action="store_true", default=False)
    p.add_argument("--batch_size", type=int, default=65536)
    p.add_argument("--steps", type=int, default=0,
                   help="0 = one epoch (or 512 synthetic steps)")
    p.add_argument("--eval_steps", type=int, default=64)
    p.add_argument("--embedding_dim", type=int, default=128)
    p.add_argument("--num_numerical", type=int, default=13)
    p.add_argument("--top_mlp", default="1024,1024,512,256,1")
    p.add_argument("--bottom_mlp", default="512,256,128")
    p.add_argument("--lr", type=float, default=24.0)
    p.add_argument("--warmup_steps", type=int, default=8000)
    p.add_argument("--decay_start_step", type=int, default=48000)
    p.add_argument("--decay_steps", type=int, default=24000)
    p.add_argument("--sparse_strategy", default="auto",
                   choices=["auto", "sort", "dense", "tiled"],
                   help="sparse aggregation strategy: tiled = the Pallas "
                        "one-hot-matmul kernels (hardware-validated)")
    p.add_argument("--dense_grads", action="store_true",
                   help="dense table grads + optax instead of the default "
                        "sparse row-wise update path")
    p.add_argument("--amp", action="store_true",
                   help="bfloat16 compute (reference AMP analogue)")
    p.add_argument("--dist_strategy", default="memory_balanced",
                   choices=["basic", "memory_balanced", "memory_optimized",
                            "comm_balanced", "auto"],
                   help="table placement: the three reference strategies "
                        "plus comm_balanced (exchange-padding-aware) and "
                        "auto (the library default)")
    p.add_argument("--column_slice_threshold", type=int, default=None)
    p.add_argument("--row_slice_threshold", type=int, default=None)
    p.add_argument("--data_parallel_threshold", type=int, default=None)
    p.add_argument("--table_scale", type=float, default=1.0,
                   help="scale Criteo vocab sizes (CPU smoke runs)")
    p.add_argument("--serial_ingest", action="store_true",
                   help="run read/decode/stage inline in the consumer "
                        "thread instead of the background ingestion "
                        "pipeline (A/B baseline)")
    p.add_argument("--pipeline_depth", type=int, default=2,
                   help="bound of each ingestion-pipeline queue")
    p.add_argument("--devices", type=int, default=0, help="0 = all")
    p.add_argument("--force_cpu", action="store_true",
                   help="run on virtual CPU devices (testing)")
    p.add_argument("--save_weights", default=None,
                   help="save global embedding weights npz here at the end")
    p.add_argument("--checkpoint_dir", default=None)
    p.add_argument("--log_every", type=int, default=32)
    p.add_argument("--seed", type=int, default=12345)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.synthetic and args.data_path:
        raise SystemExit("--synthetic and --data_path are mutually exclusive")
    if args.force_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        n = args.devices or 8
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}").strip()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")

    from distributed_embeddings_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    from distributed_embeddings_tpu.models.dlrm import DLRM, make_lr_schedule
    from distributed_embeddings_tpu.models.data import (DummyDataset,
                                                        RawBinaryDataset)
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    from distributed_embeddings_tpu.training import make_train_step
    from distributed_embeddings_tpu.utils.metrics import StreamingAUC
    from distributed_embeddings_tpu.utils import checkpoint as ckpt_lib

    devices = jax.devices()
    if args.devices:
        devices = devices[:args.devices]
    mesh = create_mesh(devices) if len(devices) > 1 else None
    print(f"devices: {len(devices)} x {devices[0].platform}", flush=True)

    table_sizes = [max(4, int(v * args.table_scale))
                   for v in CRITEO_TABLE_SIZES]
    model = DLRM(
        table_sizes=table_sizes,
        embedding_dim=args.embedding_dim,
        bottom_mlp_dims=[int(x) for x in args.bottom_mlp.split(",")],
        top_mlp_dims=[int(x) for x in args.top_mlp.split(",")],
        num_numerical_features=args.num_numerical,
        mesh=mesh,
        dist_strategy=args.dist_strategy,
        column_slice_threshold=args.column_slice_threshold,
        row_slice_threshold=args.row_slice_threshold,
        data_parallel_threshold=args.data_parallel_threshold,
        compute_dtype=jnp.bfloat16 if args.amp else jnp.float32)

    params = model.init(jax.random.PRNGKey(args.seed))
    schedule = make_lr_schedule(args.lr, args.warmup_steps,
                                args.decay_start_step, args.decay_steps)

    if args.data_path:
        train_data = RawBinaryDataset(
            args.data_path, batch_size=args.batch_size,
            numerical_features=args.num_numerical,
            categorical_features=list(range(len(table_sizes))),
            categorical_feature_sizes=table_sizes, dp_input=True,
            offset=0, local_batch_size=args.batch_size)
        steps = args.steps or len(train_data)
    else:
        rng = np.random.RandomState(args.seed)
        batches = []
        for _ in range(8):
            numerical = rng.rand(args.batch_size,
                                 args.num_numerical).astype(np.float32)
            cats = [rng.randint(0, v, args.batch_size).astype(np.int32)
                    for v in table_sizes]
            labels = rng.randint(0, 2, (args.batch_size, 1)).astype(np.float32)
            batches.append((numerical, cats, labels))
        train_data = batches
        steps = args.steps or 512

    if args.dense_grads:
        opt = optax.sgd(schedule)
        opt_state = opt.init(params)

        def loss_fn(p, numerical, cats, labels):
            return model.loss_fn(p, numerical, cats, labels)

        step_fn = make_train_step(loss_fn, opt)
    else:
        # production path: row-wise sparse embedding updates
        from distributed_embeddings_tpu.training import make_sparse_train_step
        init_fn, step_fn = make_sparse_train_step(
            model, "sgd", lr=schedule, strategy=args.sparse_strategy)
        opt_state = init_fn(params)

    # resume: restore params + optimizer state from the newest step under
    # --checkpoint_dir (orbax keeps the saved shardings; same-topology
    # resume, reference-parity mechanism is save/load_global_weights)
    start_step = 0
    if args.checkpoint_dir:
        last = ckpt_lib.latest_step(args.checkpoint_dir)
        if last is not None:
            saved = ckpt_lib.checkpoint_keys(args.checkpoint_dir, step=last)
            # unreadable metadata (saved is None) -> attempt the full
            # restore and let orbax surface the real error; only a
            # positively-identified params-only save skips opt_state
            if saved is None or "opt_state" in saved:
                restored = ckpt_lib.restore_checkpoint(
                    args.checkpoint_dir,
                    {"params": params, "opt_state": opt_state}, step=last)
                params, opt_state = restored["params"], restored["opt_state"]
            else:
                # params-only checkpoint (written before opt_state was
                # saved): restore params, keep the fresh opt_state
                restored = ckpt_lib.restore_checkpoint(
                    args.checkpoint_dir, {"params": params}, step=last)
                params = restored["params"]
                print("params-only checkpoint: optimizer state reset",
                      flush=True)
            start_step = last
            print(f"resumed from step {last}", flush=True)

    # ingestion pipeline: read (pread) -> preprocess (decode) -> stage
    # (device_put) in persistent background workers so host batch prep
    # hides under the device step (utils/pipeline.py; --serial_ingest
    # keeps the old inline form — identical batch order)
    from distributed_embeddings_tpu.utils.pipeline import (IngestPipeline,
                                                           SerialPipeline)

    def stage_batch(batch):
        # per-leaf jnp.asarray, NOT jax.device_put: uncommitted placement
        # preserves the pre-pipeline loop's behavior under a mesh (jit
        # places inputs; a committed device-0 array would force a reshard)
        numerical, cats, labels = batch
        return (jnp.asarray(numerical),
                [jnp.asarray(c) for c in cats],
                jnp.asarray(labels))

    if args.data_path:
        source = train_data.raw_batches(steps)
        stages = [("preprocess", train_data.preprocess),
                  ("stage", stage_batch)]
    else:
        source = (train_data[i % len(train_data)] for i in range(steps))
        stages = [("stage", stage_batch)]
    if args.serial_ingest:
        pipe = SerialPipeline(source, stages)
    else:
        pipe = IngestPipeline(source, stages, depth=args.pipeline_depth)

    ctx = mesh or nullcontext()
    t_start = time.perf_counter()
    samples = 0
    with ctx:
        it = iter(pipe)
        # warmup/compile on batch 0
        numerical, cats, labels = next(it)
        params, opt_state, loss = step_fn(params, opt_state, numerical, cats,
                                          labels)
        float(loss)   # a host fetch is the sync
        print(f"compiled in {time.perf_counter() - t_start:.1f}s", flush=True)

        t0 = time.perf_counter()
        for i in range(1, steps):
            numerical, cats, labels = next(it)
            params, opt_state, loss = step_fn(params, opt_state, numerical,
                                              cats, labels)
            samples += args.batch_size
            if i % args.log_every == 0 or i == steps - 1:
                lv = float(loss)
                dt = time.perf_counter() - t0
                print(f"step {i}/{steps} loss={lv:.5f} "
                      f"throughput={samples / dt:,.0f} samples/s", flush=True)
        float(loss)   # sync before the throughput is computed
        dt = time.perf_counter() - t0
        pipe.close()
        if samples:
            print(f"TRAIN DONE: {samples / dt:,.0f} samples/sec "
                  f"({dt / max(steps - 1, 1) * 1e3:.2f} ms/step)", flush=True)
        stage_ms = {k: v["mean_ms"]
                    for k, v in pipe.stage_summaries().items()}
        print(f"ingest stages mean ms "
              f"({'serial' if args.serial_ingest else 'pipelined'}): "
              f"{stage_ms}", flush=True)

        # ---- eval: streaming AUC over held-out batches -------------------
        metric = StreamingAUC()
        state = metric.init()

        @jax.jit
        def eval_step(p, state, numerical, cats, labels):
            logits = model.apply(p, numerical, cats)
            return metric.update(state, labels, logits[:, 0])

        if args.data_path:
            valid = RawBinaryDataset(
                args.data_path, batch_size=args.batch_size,
                numerical_features=args.num_numerical,
                categorical_features=list(range(len(table_sizes))),
                categorical_feature_sizes=table_sizes, dp_input=True,
                valid=True, offset=0, local_batch_size=args.batch_size)
            n_eval = min(args.eval_steps, len(valid))
            eval_src = valid
        else:
            n_eval = min(args.eval_steps, len(train_data))
            eval_src = train_data
        for i in range(n_eval):
            numerical, cats, labels = eval_src[i]
            state = eval_step(params, state, jnp.asarray(numerical),
                              [jnp.asarray(c) for c in cats],
                              jnp.asarray(labels))
        print(f"eval AUC = {metric.result(state):.5f}", flush=True)

    if args.save_weights:
        weights = model.embedding.get_weights(params["embedding"])
        out = ckpt_lib.save_global_weights(args.save_weights, weights)
        print(f"saved global embedding weights to {out}", flush=True)
    if args.checkpoint_dir:
        out = ckpt_lib.save_checkpoint(
            args.checkpoint_dir, {"params": params, "opt_state": opt_state},
            step=start_step + steps, force=True)
        print(f"saved checkpoint to {out}", flush=True)



if __name__ == "__main__":
    main()
