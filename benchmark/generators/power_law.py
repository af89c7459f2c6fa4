"""The reference's synthetic input generator, seeded.

A copy of ``distributed_embeddings_tpu.models.synthetic`` (``power_law``,
``gen_power_law_data``, ``InputGenerator``), itself the mirror of the
reference's ``synthetic_models.py:31-113``. The copy draws from a
``RandomState(seed)`` of its own, never from the global ``np.random``, and
returns host arrays: staging is the harness's business.

Traffic file keys: ``alpha`` (0 = ids uniform over each vocabulary, > 0 =
power law with that exponent, the low ids the hot ones) and ``num_batches``
(distinct batches, rotated by the loop).
"""

import numpy as np


def power_law(k_min, k_max, alpha, r):
    """Map U(0,1) samples to a power law on [k_min, k_max)."""
    gamma = 1 - alpha
    return ((r * (k_max ** gamma - k_min ** gamma) + k_min ** gamma)
            ** (1.0 / gamma)).astype(np.int64)


def generate(traffic, inputs, batch, num_numerical, numerical_scale, seed):
    """`inputs` is [(vocabulary rows, hotness)] per model input. Returns
    [(numerical [B, n] f32, [ids [B, hotness] int32 per input],
    labels [B, 1] f32)], one per batch."""
    rng = np.random.RandomState(seed)
    alpha = float(traffic["alpha"])
    batches = []
    for _ in range(int(traffic["num_batches"])):
        cats = []
        for rows, hotness in inputs:
            if alpha == 0.0:
                ids = rng.randint(0, rows, size=(batch, hotness))
            else:
                ids = power_law(1, rows + 1, alpha,
                                rng.rand(batch * hotness)) - 1
                ids = ids.reshape(batch, hotness)
            cats.append(ids.astype(np.int32))
        numerical = (rng.rand(batch, num_numerical) * numerical_scale
                     ).astype(np.float32)
        labels = rng.randint(0, 2, size=(batch, 1)).astype(np.float32)
        batches.append((numerical, cats, labels))
    return batches
