"""Configuration file -> MLPerf DLRM on the repo's public training path,
as ``examples/dlrm/main.py`` builds it: ``DLRM`` + ``make_lr_schedule`` +
``make_sparse_train_step(model, "sgd", lr=schedule)``."""

from benchmark.harness.built import Built, mlp_train_flops, scaled_rows


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.dlrm import DLRM, make_lr_schedule
    from distributed_embeddings_tpu.training import make_sparse_train_step

    cut = config["rehearse"] if rehearse else {}
    rows = [scaled_rows(v, cut.get("table_scale"))
            for v in config["table_rows"]]
    dim = config["embedding_dim"]
    bottom, top = config["bottom_mlp_dims"], config["top_mlp_dims"]
    model = DLRM(table_sizes=rows, embedding_dim=dim, bottom_mlp_dims=bottom,
                 top_mlp_dims=top,
                 num_numerical_features=config["num_numerical_features"],
                 mesh=mesh, dist_strategy=config["placement"])
    opt = config["optimizer"]
    sched = opt["lr_schedule"]
    schedule = make_lr_schedule(sched["base_lr"], sched["warmup_steps"],
                                sched["decay_start_step"],
                                sched["decay_steps"])
    n = len(rows) + 1
    interact = n * (n - 1) // 2 + bottom[-1]
    flops = (mlp_train_flops([config["num_numerical_features"]] + bottom)
             + mlp_train_flops([interact] + top)
             + 3 * 2 * n * n * dim)               # the Gram matrix
    return Built(
        model=model,
        make_step=lambda: make_sparse_train_step(model, opt["kind"],
                                                 lr=schedule),
        tables=[(v, dim) for v in rows], table_map=list(range(len(rows))),
        hotness=[1] * len(rows),
        num_numerical=config["num_numerical_features"],
        numerical_scale=config["numerical_scale"],
        global_batch=cut.get("global_batch", config["global_batch"]),
        optimizer=opt, reference="dlrm",
        dense_params=lambda params: {"bottom": params["bottom_mlp"],
                                     "top": params["top_mlp"]},
        mlp_flops_per_sample=flops, ids_1d=True, mesh=mesh)
