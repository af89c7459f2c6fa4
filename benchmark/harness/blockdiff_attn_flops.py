"""The matrix products a block-diffusion model's attention must take in one
training step: the numerator of ``blockdiff_attn_roofline``. A file of its
own because ``stage_flops.py`` is a yardstick that stays as it is. Counted
from the configuration's published shapes and from the pairs the mask keeps
(`benchmark/generators/block_diffusion.visible_pairs`, from a batch's
positions and the block length, never from the program's tiles), so the
same work is read whatever implements it; what a recomputed forward pass
spends again does not count, and neither does what the loss does not read:
the clean stream's queries, scores and output projection in the last
layer.
"""


def projection_flops(config) -> dict:
    """Per position and layer, forward: ``{"qo"}`` the query and output
    projections, ``{"kv"}`` the key and value projections, 2 flops a
    multiply-add."""
    h, d = config["hidden_size"], config["head_dim"]
    return {"qo": 2 * 2 * h * config["num_attention_heads"] * d,
            "kv": 2 * 2 * h * config["num_key_value_heads"] * d}


def attn_flops_per_step(config, tokens_per_chip, pairs) -> float:
    """Stage ``attn``'s products in a step over ``tokens_per_chip`` clean
    tokens, forward and twice that backward, over the
    ``num_hidden_layers`` layers here: the key and value projections of
    both streams in every layer; the query and output projections of both
    streams but in the last layer, where the noisy stream's alone; and 2 x
    2 x ``head_dim`` flops per query head and visible (query, key) pair
    (scores and values), `pairs` being one stream's queries' in one layer
    (a noisy query keeps as many keys as a clean one), of both streams but
    in the last layer."""
    layers = config["num_hidden_layers"]
    per = projection_flops(config)
    queries = tokens_per_chip * (2 * layers - 1)    # positions that query
    projections = (per["kv"] * 2 * tokens_per_chip * layers
                   + per["qo"] * queries)
    pair = 2 * 2 * config["head_dim"] * config["num_attention_heads"]
    scores = pair * pairs * (2 * layers - 1)
    return 3.0 * (projections + scores)
