"""Synthetic model benchmark driver.

Mirror of the reference benchmark driver
(reference: examples/benchmarks/synthetic_models/main.py): picks one of the
7 model scales (tiny ... colossal), generates power-law ids, and times the
jit-compiled hybrid-parallel train step. The step-time numbers are directly
comparable to BASELINE.md's tables (same table configs, same global batch,
same optimizer).

  python examples/benchmarks/synthetic_models/main.py --model tiny \
      --batch_size 65536 --optimizer adagrad
  python examples/benchmarks/synthetic_models/main.py --model tiny \
      --force_cpu --batch_size 1024 --steps 8 --table_scale 0.01  # smoke

CPU smoke note: pass --table_scale on few-core hosts. XLA:CPU's collective
rendezvous aborts the process (F-level check, 40s budget) if any virtual
device's partition cannot reach the all_to_all in time — full-size tables
on a 1-core container starve it. Scaled tables keep per-device work far
under the budget; real TPU backends have no such limit.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..")))  # repo root

import argparse
from contextlib import nullcontext


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="tiny",
                   choices=["criteo", "tiny", "small", "medium", "large",
                            "jumbo", "colossal"])
    p.add_argument("--batch_size", type=int, default=65536)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--warmup_steps", type=int, default=4)
    p.add_argument("--optimizer", default="adagrad",
                   choices=["sgd", "adagrad", "adam"])
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--alpha", type=float, default=1.05,
                   help="power-law exponent for ids (0 = uniform)")
    p.add_argument("--num_data_batches", type=int, default=4)
    p.add_argument("--dist_strategy", default="memory_balanced")
    p.add_argument("--column_slice_threshold", type=int, default=None)
    p.add_argument("--dp_input", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="--no-dp_input benchmarks the model-parallel input "
                        "path (feature-sharded data, no id exchange)")
    p.add_argument("--amp", action="store_true")
    p.add_argument("--sparse_strategy", default="auto",
                   choices=["auto", "sort", "dense", "tiled"])
    p.add_argument("--dense_grads", action="store_true",
                   help="use dense table gradients + optax instead of the "
                        "default sparse row-wise update path")
    p.add_argument("--devices", type=int, default=0)
    p.add_argument("--force_cpu", action="store_true")
    p.add_argument("--table_scale", type=float, default=1.0,
                   help="scale vocab sizes down for small-memory smoke runs")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.force_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        n = args.devices or 8
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}").strip()

    import jax
    import jax.numpy as jnp
    import optax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")

    from distributed_embeddings_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    from distributed_embeddings_tpu.models.synthetic import (
        SYNTHETIC_MODELS, SyntheticModel, InputGenerator)
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    from distributed_embeddings_tpu.training import make_train_step
    from distributed_embeddings_tpu.utils import profiling

    cfg = SYNTHETIC_MODELS[args.model]
    if args.table_scale != 1.0:
        cfg = cfg._replace(embedding_configs=[
            c._replace(num_rows=max(4, int(c.num_rows * args.table_scale)))
            for c in cfg.embedding_configs])

    devices = jax.devices()
    if args.devices:
        devices = devices[:args.devices]
    mesh = create_mesh(devices) if len(devices) > 1 else None
    print(f"model={cfg.name} devices={len(devices)} "
          f"batch={args.batch_size} opt={args.optimizer}", flush=True)

    model = SyntheticModel(
        cfg, mesh=mesh, distributed=True, strategy=args.dist_strategy,
        column_slice_threshold=args.column_slice_threshold,
        dp_input=args.dp_input,
        compute_dtype=jnp.bfloat16 if args.amp else jnp.float32)
    params = model.init(jax.random.PRNGKey(args.seed))

    def to_model_inputs(cats):
        if args.dp_input:
            return cats
        # feature-sharded (mp) input: nested per-rank lists in
        # strategy.input_ids_list order
        strat = model.embedding.strategy
        return [[cats[strat.input_groups[1][pos]] for pos in rank_ids]
                for rank_ids in strat.input_ids_list]

    use_sparse = args.dp_input and not args.dense_grads
    if use_sparse:
        # production path: row-wise sparse embedding updates (no dense
        # [V, w] grads, no full-table optimizer pass)
        from distributed_embeddings_tpu.training import make_sparse_train_step
        init_fn, step_fn = make_sparse_train_step(
            model, args.optimizer, lr=args.lr,
            strategy=args.sparse_strategy)
        opt_state = init_fn(params)
    else:
        opt = {"sgd": optax.sgd, "adagrad": optax.adagrad,
               "adam": optax.adam}[args.optimizer](args.lr)
        opt_state = opt.init(params)
        step_fn = make_train_step(model.loss_fn, opt)

    gen = InputGenerator(cfg, args.batch_size, alpha=args.alpha,
                         num_batches=args.num_data_batches, seed=args.seed)

    batches = [(gen[i][0], to_model_inputs(gen[i][1]), gen[i][2])
               for i in range(len(gen))]

    # the state is threaded through the timed calls and donated (the
    # library default): out of place, a step holds two copies of the
    # tables and accumulators — 16.8 GiB for tiny on a 16 GB chip
    ctx = mesh if mesh is not None else nullcontext()
    with ctx:
        res, params, opt_state = profiling.benchmark_train_steps(
            step_fn, params, opt_state, batches, iters=args.steps,
            warmup=args.warmup_steps)
    print(f"step time: {res}", flush=True)
    print(f"throughput: {args.batch_size / res.mean_s:,.0f} samples/sec",
          flush=True)



if __name__ == "__main__":
    main()
