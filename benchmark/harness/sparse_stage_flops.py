"""`stage_flops.py`'s counts for a model whose layers do not all carry
experts: the numerator of ``sparse_experts_roofline``. A file of its own
because ``stage_flops.py`` is a yardstick that stays as it is.
"""


def sparse_expert_flops_per_step(config, tokens_per_chip) -> float:
    """The held experts' products, as `stage_flops.expert_flops_per_step`
    counts them (three products of ``hidden x moe_intermediate_size`` a
    pair, 2 flops a multiply-add, forward and twice that backward; a uniform
    router's share of the ``num_experts_per_tok`` pairs a token), in the
    layers here that HAVE experts: the ``num_hidden_layers`` held less the
    ``num_dense_layers`` of them that carry a dense MLP."""
    pairs = (tokens_per_chip * config["num_experts_per_tok"]
             * config["num_experts"] / config["num_experts_published"])
    return (3 * 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]
            * pairs * (config["num_hidden_layers"]
                       - config["num_dense_layers"]))
