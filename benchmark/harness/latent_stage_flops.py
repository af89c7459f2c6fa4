"""`stage_flops.py`'s counts for the stages a latent-attention model with a
shared expert adds: the numerators of ``latent_roofline`` and
``shared_roofline``. A file of its own because ``stage_flops.py`` is a
yardstick that stays as it is. Counted from the configuration's published
shapes, so the same work is read whatever implements it; what a recomputed
forward pass spends again does not count.
"""


def latent_weights(config) -> int:
    """Entries of a layer's four latent projections: ``hidden x
    q_lora_rank``, ``q_lora_rank x heads x (nope + rope)``, ``hidden x
    (kv_lora_rank + rope)`` and ``kv_lora_rank x heads x (nope +
    v_head_dim)``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return (h * config["q_lora_rank"]
            + config["q_lora_rank"] * heads * (nope + rope)
            + h * (config["kv_lora_rank"] + rope)
            + config["kv_lora_rank"] * heads * (nope + config["v_head_dim"]))


def latent_flops_per_step(config, tokens_per_chip) -> float:
    """Stage ``latent``: the four projections in front of the scores, 2
    flops a multiply-add, forward and twice that backward, in each of the
    ``num_hidden_layers`` layers here (`latent_weights`). The norms, the
    rotary and the assembly of q, k and v are no matrix products."""
    return (3 * 2 * latent_weights(config) * tokens_per_chip
            * config["num_hidden_layers"])


def shared_flops_per_step(config, tokens_per_chip) -> float:
    """Stage ``shared``: the shared expert's three products of ``hidden x
    (n_shared_experts x moe_intermediate_size)`` for every token, forward
    and twice that backward, in the layers here that have experts (the
    ``num_hidden_layers`` held less the ``num_dense_layers`` of them that
    carry a dense MLP)."""
    width = config["n_shared_experts"] * config["moe_intermediate_size"]
    return (3 * 2 * 3 * config["hidden_size"] * width * tokens_per_chip
            * (config["num_hidden_layers"] - config["num_dense_layers"]))
