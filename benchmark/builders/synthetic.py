"""Configuration file -> the reference's synthetic model on the repo's
public training path: ``SyntheticModel(distributed=True)`` +
``make_sparse_train_step``, the calls of
``examples/benchmarks/synthetic_models/main.py``."""

from benchmark.harness.built import Built, mlp_train_flops, scaled_rows


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.synthetic import (
        EmbeddingConfig, ModelConfig, SyntheticModel,
        expand_embedding_configs)
    from distributed_embeddings_tpu.training import make_sparse_train_step

    cut = config["rehearse"] if rehearse else {}
    cfg = ModelConfig(
        config["name"],
        [EmbeddingConfig(c["num_tables"], list(c["nnz"]),
                         scaled_rows(c["num_rows"], cut.get("table_scale")),
                         c["width"], c["shared"])
         for c in config["embedding_configs"]],
        list(config["mlp_sizes"]), config["num_numerical_features"],
        config["interact_stride"])
    if cfg.interact_stride is not None:
        raise NotImplementedError(
            "benchmark.reference has no strided-pooling interaction yet")
    model = SyntheticModel(cfg, mesh=mesh, distributed=True,
                           strategy=config["placement"])
    tables, table_map, hotness = expand_embedding_configs(cfg)
    opt = config["optimizer"]
    return Built(
        model=model,
        make_step=lambda: make_sparse_train_step(model, opt["kind"],
                                                 lr=opt["lr"]),
        tables=tables, table_map=table_map, hotness=hotness,
        num_numerical=cfg.num_numerical_features,
        numerical_scale=config["numerical_scale"],
        global_batch=cut.get("global_batch", config["global_batch"]),
        optimizer=opt, reference="synthetic",
        dense_params=lambda params: {"mlp": params["mlp"]},
        mlp_flops_per_sample=mlp_train_flops([model.mlp_in]
                                             + model.mlp_sizes),
        mesh=mesh)
