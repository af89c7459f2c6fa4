"""Matmul flops one training step must spend inside one of the program's
stage scopes, from the configuration's published shapes: the numerator of
a ``<stage>_roofline``. Kept here, beside ``roofline.py`` and with the
benchmark, so that no later PR can move the yardstick; counted from shapes,
so the same work is read whatever implements it.
"""


def expert_flops_per_step(config, tokens_per_chip) -> float:
    """The held experts' products: a SwiGLU expert is three products of
    ``hidden x moe_intermediate_size`` (gate, up, down), 2 flops a
    multiply-add, forward and twice that backward; a token makes
    ``num_experts_per_tok`` pairs, of which a uniform router sends
    ``num_experts / num_experts_published`` to the experts held here; in
    each of the ``num_hidden_layers`` layers here. What a recomputed forward
    pass spends again does not count."""
    pairs = (tokens_per_chip * config["num_experts_per_tok"]
             * config["num_experts"] / config["num_experts_published"])
    return (3 * 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]
            * pairs * config["num_hidden_layers"])

