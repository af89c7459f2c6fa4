"""Packed documents of skewed token ids, seeded: a language model's batch.

Documents of lognormal length (``document_median``, ``document_sigma``,
clipped to ``document_min`` .. ``document_max``) are packed end to end into
sequences, the last of a sequence cut at its end, as a pre-training loader
packs source files; a token's position restarts at 0 with each document.
Token ids follow the repo's power law (``alpha``, the low ids the hot ones;
0 = uniform) over the vocabulary's rows, which for a sliced vocabulary are
the slice's. Draws come from a ``RandomState(seed)`` of its own; host arrays
are returned.

The harness hands a generator ``(traffic, inputs, batch, num_numerical,
numerical_scale, seed)``: `batch` is the tokens of a step and `num_numerical`
carries the builder's sequence length (`Built`: "parameters the builder
hands the cell's generator"), which must divide it.
"""

import numpy as np

from benchmark.generators.power_law import power_law


def document_lengths(traffic, rng, tokens):
    """Lengths drawn until they cover `tokens`."""
    lengths = []
    while sum(lengths) < tokens:
        drawn = rng.lognormal(np.log(float(traffic["document_median"])),
                              float(traffic["document_sigma"]),
                              size=max(8, tokens // 256))
        lengths.extend(np.clip(np.rint(drawn), int(traffic["document_min"]),
                               int(traffic["document_max"])).astype(np.int64))
    return lengths


def generate(traffic, inputs, batch, num_numerical, numerical_scale, seed):
    """`inputs` is [(vocabulary rows, 1)]. Returns per batch (positions
    inside the document [sequences, length] int32, [ids [T, 1] int32], next
    ids [T] int32); the successor of a sequence's last token is drawn like
    any other and belongs to no document of the batch."""
    ((rows, hotness),) = inputs
    length = int(num_numerical)
    if hotness != 1 or length <= 0 or batch % length:
        raise ValueError(f"{batch} tokens a step do not pack into sequences "
                         f"of {length} (one one-hot input)")
    rng = np.random.RandomState(seed)
    alpha = float(traffic["alpha"])
    batches = []
    for _ in range(int(traffic["num_batches"])):
        positions = np.empty((batch // length, length), np.int32)
        for row in positions:
            at = 0
            for n in document_lengths(traffic, rng, length):
                n = min(int(n), length - at)
                row[at:at + n] = np.arange(n)
                at += n
                if at == length:
                    break
        draws = batch + batch // length          # a successor per sequence
        if alpha == 0.0:
            ids = rng.randint(0, rows, size=draws)
        else:
            ids = power_law(1, rows + 1, alpha, rng.rand(draws)) - 1
        ids = ids.astype(np.int32).reshape(-1, length + 1)
        batches.append((positions, [ids[:, :-1].reshape(-1, 1).copy()],
                        ids[:, 1:].reshape(-1).copy()))
    return batches
