"""Configuration file -> one chip's share of LFM2-24B-A2B on the repo's
public training path: ``models.lfm2.Lfm2`` +
``make_sparse_train_step(model, "adam", lr=schedule)``. ``num_hidden_layers``
entries of ``layer_types`` run, from ``deployment.first_layer_held`` on, the
first ``num_dense_layers`` of them with the dense MLP and the rest with the
sparse one; ``num_experts`` is the experts held, from
``deployment.first_expert_held`` on, and ``num_experts_published`` the
router's width; ``vocab_size`` the rows of the table and the columns of the
head."""

from benchmark.harness.built import Built


def held_layers(config):
    """[(mixer, mlp)] of the layers this chip holds, in order."""
    first = config["deployment"]["first_layer_held"]
    mixers = config["layer_types"][first:first + config["num_hidden_layers"]]
    return [(mixer, "dense" if i < config["num_dense_layers"] else "sparse")
            for i, mixer in enumerate(mixers)]


def train_flops_per_token(config) -> int:
    """Matmul flops a token that no implementation of the step can avoid,
    forward and twice that backward: a convolution's two projections (its
    taps are no matrix product), attention's four, the dense MLP's three
    where a layer has it, elsewhere the router and the products of the pairs
    a uniform router sends to the held experts (``num_experts_per_tok *
    num_experts / num_experts_published`` a token), and the head.
    Attention's score and value products are left out: they depend on the
    batch's documents. So the step's roofline share is a floor, and
    recomputed work does not count."""
    h, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads"] + 2 * config["num_key_value_heads"]
    pairs = (config["num_experts_per_tok"] * config["num_experts"]
             / config["num_experts_published"])
    mixer = {"conv": 2 * h * 3 * h + 2 * h * h,                # in, out
             "full_attention": (2 * h * d * heads              # q, k, v
                                + 2 * d * config["num_attention_heads"] * h)}
    mlp = {"dense": 3 * 2 * h * config["intermediate_size"],
           "sparse": (2 * h * config["num_experts_published"]  # router
                      + pairs * 3 * 2 * h * config["moe_intermediate_size"])}
    layers = sum(mixer[m] + mlp[f] for m, f in held_layers(config))
    return int(3 * (layers + 2 * h * config["vocab_size"]))


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.dlrm import make_lr_schedule
    from distributed_embeddings_tpu.models.lfm2 import Lfm2
    from distributed_embeddings_tpu.training import make_sparse_train_step

    if rehearse:                  # shapes cut for the CPU; what the plain
        config = {**config, **config["rehearse"]}   # reference reads stays
    if (config["routed_scaling_factor"] != 1 or config["conv_bias"]
            or not config["use_expert_bias"]):
        raise ValueError("the program has no routed scaling factor other "
                         "than 1, no convolution bias and no sigmoid router "
                         "without its selection bias")
    first = config["deployment"]["first_expert_held"]
    model = Lfm2(
        vocab_rows=config["vocab_size"], hidden=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], layers=held_layers(config),
        rope=config["rope_parameters"], conv_taps=config["conv_L_cache"],
        dense_width=config["intermediate_size"],
        num_experts_total=config["num_experts_published"],
        held_experts=range(first, first + config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        bias_range=config["expert_bias_range"], norm_eps=config["norm_eps"],
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
        reference="lfm2",
        dense_params=lambda params: {k: v for k, v in params.items()
                                     if k != "embedding"},
        mlp_flops_per_sample=train_flops_per_token(config),
        ids_1d=True, mesh=mesh)
