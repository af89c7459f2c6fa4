"""Configuration file -> one chip's share of Mellum2-12B-A2.5B-Instruct on
the repo's public training path: ``models.mellum.Mellum`` +
``make_sparse_train_step(model, "adam", lr=schedule)``. The first
``num_hidden_layers`` entries of ``layer_types`` run; ``num_experts`` is the
experts held, from ``deployment.first_expert_held`` on, and
``num_experts_published`` the router's width; ``vocab_size`` the rows of the
table and the columns of the head."""

from benchmark.harness.built import Built


def train_flops_per_token(config) -> int:
    """Matmul flops a token that no implementation of the step can avoid,
    forward and twice that backward: the projections, the router, the
    products of the pairs a uniform router sends to the held experts
    (``num_experts_per_tok * num_experts / num_experts_published`` a token)
    and the head. Attention's score and value products are left out: they
    depend on the batch's documents (a token sees its document's earlier
    tokens, at most the window's on a window layer). So the step's roofline
    share is a floor, and recomputed work does not count."""
    h, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads"] + 2 * config["num_key_value_heads"]
    pairs = (config["num_experts_per_tok"] * config["num_experts"]
             / config["num_experts_published"])
    layer = (2 * h * d * heads                         # q, k, v
             + 2 * d * config["num_attention_heads"] * h      # o
             + 2 * h * config["num_experts_published"]        # router
             + pairs * 3 * 2 * h * config["moe_intermediate_size"])
    return int(3 * (config["num_hidden_layers"] * layer
                    + 2 * h * config["vocab_size"]))


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.dlrm import make_lr_schedule
    from distributed_embeddings_tpu.models.mellum import Mellum
    from distributed_embeddings_tpu.training import make_sparse_train_step

    if rehearse:                  # shapes cut for the CPU; what the plain
        config = {**config, **config["rehearse"]}   # reference reads stays
    first = config["deployment"]["first_expert_held"]
    model = Mellum(
        vocab_rows=config["vocab_size"], hidden=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        num_experts_total=config["num_experts_published"],
        held_experts=range(first, first + config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rms_eps=config["rms_norm_eps"],
        num_layers_total=config["num_hidden_layers_published"], mesh=mesh)
    opt = config["optimizer"]
    sched = opt["lr_schedule"]
    schedule = make_lr_schedule(sched["base_lr"], sched["warmup_steps"],
                                sched["decay_start_step"],
                                sched["decay_steps"])
    return Built(
        model=model,
        make_step=lambda: make_sparse_train_step(model, opt["kind"],
                                                 lr=schedule),
        tables=[(config["vocab_size"], config["hidden_size"])],
        table_map=[0], hotness=[1],
        # the generator's two parameters: the sequence length, and nothing
        num_numerical=config["sequence_length"], numerical_scale=0.0,
        global_batch=config["tokens_per_step"], optimizer=opt,
        reference="mellum2",
        dense_params=lambda params: {k: v for k, v in params.items()
                                     if k != "embedding"},
        mlp_flops_per_sample=train_flops_per_token(config),
        ids_1d=True, mesh=mesh)
