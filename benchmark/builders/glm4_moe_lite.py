"""Configuration file -> one chip's share of GLM-4.7-Flash on the repo's
public training path: ``models.glm4_moe_lite.Glm4MoeLite`` +
``make_sparse_train_step(model, "adam", lr=schedule)``. ``num_hidden_layers``
layers run, from ``deployment.first_layer_held`` on, the first
``num_dense_layers`` of them with the dense MLP and the rest with the sparse
one (routed experts beside the shared expert); ``num_experts`` is the routed
experts held, from ``deployment.first_expert_held`` on, and
``num_experts_published`` the router's width; ``vocab_size`` the rows of the
table and the columns of the head."""

from benchmark.harness.built import Built
from benchmark.harness.latent_stage_flops import latent_weights

LATENT_SIZES = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")


def held_layers(config):
    """[(mixer, mlp)] of the layers this chip holds, in order."""
    return [("mla", "dense" if i < config["num_dense_layers"] else "sparse")
            for i in range(config["num_hidden_layers"])]


def train_flops_per_token(config) -> int:
    """Matmul flops a token that no implementation of the step can avoid,
    forward and twice that backward: latent attention's four projections and
    its output projection, the dense MLP's three where a layer has it,
    elsewhere the router, the shared expert's three and the products of the
    pairs a uniform router sends to the held experts (``num_experts_per_tok
    * num_experts / num_experts_published`` a token), and the head.
    Attention's score and value products are left out: they depend on the
    batch's documents. So the step's roofline share is a floor, and
    recomputed work does not count."""
    h = config["hidden_size"]
    expert = 3 * 2 * h * config["moe_intermediate_size"]
    pairs = (config["num_experts_per_tok"] * config["num_experts"]
             / config["num_experts_published"])
    attention = 2 * latent_weights(config) + (
        2 * config["num_attention_heads"] * config["v_head_dim"] * h)
    mlp = {"dense": 3 * 2 * h * config["intermediate_size"],
           "sparse": (2 * h * config["num_experts_published"]      # router
                      + config["n_shared_experts"] * expert + pairs * expert)}
    layers = sum(attention + mlp[f] for _, f in held_layers(config))
    return int(3 * (layers + 2 * h * config["vocab_size"]))


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.models.dlrm import make_lr_schedule
    from distributed_embeddings_tpu.models.glm4_moe_lite import Glm4MoeLite
    from distributed_embeddings_tpu.training import make_sparse_train_step

    if rehearse:                  # shapes cut for the CPU; what the plain
        config = {**config, **config["rehearse"]}   # reference reads stays
    if (config["n_group"] != 1 or config["topk_group"] != 1
            or config["attention_bias"] or config["rope_scaling"] is not None
            or config["n_shared_experts"] != 1):
        raise ValueError("the program has no group-limited choice of experts "
                         "(n_group and topk_group other than 1), no attention "
                         "bias, no rope scaling and not another number of "
                         "shared experts than 1")
    first = config["deployment"]["first_expert_held"]
    model = Glm4MoeLite(
        vocab_rows=config["vocab_size"], hidden=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        **{size: config[size] for size in LATENT_SIZES},
        layers=held_layers(config),
        rope={"rope_type": "default", "rope_theta": config["rope_theta"]},
        dense_width=config["intermediate_size"],
        num_experts_total=config["num_experts_published"],
        held_experts=range(first, first + config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        shared_width=(config["n_shared_experts"]
                      * config["moe_intermediate_size"]),
        bias_range=config["expert_bias_range"],
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
        # the generator's two parameters: the sequence length, and nothing
        num_numerical=config["sequence_length"], numerical_scale=0.0,
        global_batch=config["tokens_per_step"], optimizer=opt,
        reference="glm4_moe_lite",
        dense_params=lambda params: {k: v for k, v in params.items()
                                     if k != "embedding"},
        mlp_flops_per_sample=train_flops_per_token(config),
        ids_1d=True, mesh=mesh)
