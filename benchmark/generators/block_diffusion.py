"""Packed documents read as a block-diffusion model's two streams, seeded.

The clean stream is `packed_documents`' batch over every row of the
vocabulary's slice but its last, the mask id: lognormal documents packed end
to end into sequences, positions restarting at 0 with each document, ids by
the repo's power law (``alpha``). Blocks of ``block`` tokens are counted
from each document's start (the builder hands the model's block length as
the generator's ``numerical_scale``); per block ``t ~ U(0, 1)`` and ``p =
(1 - mask_eps) t + mask_eps``, and each token of the block is replaced by
the mask id with probability ``p`` in the noisy stream. Draws come from
generators of their own, seeded by ``seed``; host arrays are returned.

`PAIRS` keeps, for each batch of the newest call, the (query, key) pairs the
block-diffusion mask keeps for one stream's queries in one layer
(`visible_pairs`), counted from the positions and the block length alone:
what ``readers/blockdiff_attn_roofline.py`` reads, since a reader's context
holds no batch.
"""

import numpy as np

from benchmark.generators import packed_documents

PAIRS = []      # visible pairs of one stream's queries, per batch


def visible_pairs(positions, block):
    """The (query, key) pairs of one stream's queries that the mask keeps
    in one layer, over ``positions [sequences, length]``. A noisy query
    sees the noisy keys of its own block and the clean keys of its
    document's earlier blocks, a clean query the clean keys of its document
    up to its own block's end: as many keys either way, the keys of its
    document up to its block's end."""
    pairs = 0
    for row in positions:
        starts = np.flatnonzero(row == 0)
        for n in np.diff(np.append(starts, len(row))):
            first = np.arange(n) // block * block    # a token's block start
            pairs += int(np.sum(np.minimum(n, first + block)))
    return pairs


def generate(traffic, inputs, batch, num_numerical, numerical_scale, seed):
    """`inputs` is [(vocabulary rows, 1)], `batch` the clean tokens of a
    step, `num_numerical` the sequence length, `numerical_scale` the block
    length. Returns per batch (``[sequences, 2, length]`` int32: each
    token's position inside its document, its clean id; ``[ids [2T, 1]
    int32]``, a sequence's noisy stream then its clean one; ``[T]`` f32
    loss weights, ``1 / p`` of its block at a masked token and 0
    elsewhere)."""
    ((rows, hotness),) = inputs
    block = int(numerical_scale)
    if block < 1 or block != numerical_scale:
        raise ValueError(f"a block of {numerical_scale} tokens")
    mask_id = rows - 1
    length = int(num_numerical)
    clean = packed_documents.generate(traffic, [(mask_id, hotness)], batch,
                                      length, 0.0, seed)
    rng = np.random.default_rng([seed, block])
    eps = float(traffic["mask_eps"])
    batches = []
    PAIRS.clear()
    for positions, (ids,), _ in clean:
        x0 = ids.reshape(positions.shape)
        # a block starts where its document's position is a multiple of B
        index = np.cumsum(positions.reshape(-1) % block == 0) - 1
        p = (1.0 - eps) * rng.random(index[-1] + 1) + eps
        masked = (rng.random(index.size) < p[index]).reshape(positions.shape)
        xt = np.where(masked, mask_id, x0)
        weights = np.where(masked.reshape(-1), 1.0 / p[index], 0.0)
        batches.append((np.stack([positions, x0], axis=1).astype(np.int32),
                        [np.stack([xt, x0], axis=1).reshape(-1, 1).astype(
                            np.int32)],
                        weights.astype(np.float32)))
        PAIRS.append(visible_pairs(positions, block))
    return batches
