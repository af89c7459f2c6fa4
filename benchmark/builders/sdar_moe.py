"""Configuration file -> one chip's share of SDAR-30B-A3B-Chat trained as a
block-diffusion model on the repo's public training path:
``models.sdar.Sdar`` + ``make_sparse_train_step(model, "adam",
lr=schedule)``. ``num_hidden_layers`` layers run, from
``deployment.first_layer_held`` on, every one attention and experts;
``num_experts`` is the experts held, from ``deployment.first_expert_held``
on, and ``num_experts_published`` the router's width; ``vocab_size`` the
rows of the table and the columns of the head, the last of them the mask
id. A sample is a clean token: ``tokens_per_step`` of them, each read in two
streams."""

from benchmark.harness.blockdiff_attn_flops import projection_flops
from benchmark.harness.built import Built


def train_flops_per_token(config) -> int:
    """Matmul flops a clean token that no implementation of the step can
    avoid, forward and twice that backward: attention's key and value
    projections at both of its positions in every layer, its query and
    output projections, the router and the products of the pairs a uniform
    router sends to the held experts (``num_experts_per_tok * num_experts
    / num_experts_published`` a position) at both positions but in the
    last layer, where at the noisy one alone (the loss reads no clean
    position there), and the head at the noisy position. Attention's score
    and value products are left out: they depend on the batch's documents
    (``harness/blockdiff_attn_flops.py`` counts them). So the step's
    roofline share is a floor, and recomputed work does not count."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    per = projection_flops(config)
    pairs = (config["num_experts_per_tok"] * config["num_experts"]
             / config["num_experts_published"])
    mlp = (2 * h * config["num_experts_published"]
           + pairs * 3 * 2 * h * config["moe_intermediate_size"])
    return int(3 * (2 * layers * per["kv"] + (2 * layers - 1)
                    * (per["qo"] + mlp) + 2 * h * config["vocab_size"]))


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.dlrm import make_lr_schedule
    from distributed_embeddings_tpu.models.sdar import Sdar
    from distributed_embeddings_tpu.training import make_sparse_train_step

    if rehearse:                  # shapes cut for the CPU; what the plain
        config = {**config, **config["rehearse"]}   # reference reads stays
    if (config["attention_bias"] or config["rope_scaling"] is not None
            or config["use_sliding_window"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1
            or not config["norm_topk_prob"]):
        raise ValueError("the program has no attention bias, no rope "
                         "scaling, no sliding window, no dense layer between "
                         "the sparse ones and no router without "
                         "renormalisation")
    first = config["deployment"]["first_expert_held"]
    model = Sdar(
        vocab_rows=config["vocab_size"], hidden=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_layers=config["num_hidden_layers"],
        rope={"rope_type": "default", "rope_theta": config["rope_theta"]},
        block=config["block_length"],
        num_experts_total=config["num_experts_published"],
        held_experts=range(first, first + config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        norm_eps=config["rms_norm_eps"],
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
        # the generator's two parameters: the sequence length and the block
        num_numerical=config["sequence_length"],
        numerical_scale=float(config["block_length"]),
        global_batch=config["tokens_per_step"], optimizer=opt,
        reference="sdar_moe",
        dense_params=lambda params: {k: v for k, v in params.items()
                                     if k != "embedding"},
        mlp_flops_per_sample=train_flops_per_token(config),
        ids_1d=True, mesh=mesh)
