"""Plain reference of the synthetic models (NVIDIA-Merlin/distributed-embeddings,
``examples/benchmarks/synthetic_models/synthetic_models.py``): embeddings
with the ``sum`` combiner, concatenated in input order with the numerical
features appended, an MLP with ReLU between layers, one logit, sigmoid
binary cross-entropy averaged over the global batch.

``inputs`` is the numerical features ``[B, n]`` f32, ``labels`` the clicks
``[B, 1]`` f32, ``dense`` ``{"mlp": [{"w", "b"}, ...]}``."""

import jax.numpy as jnp

from benchmark.reference import bce_with_logits, mlp


def synthetic_logits(dense, embs, numerical):
    x = jnp.concatenate(list(embs) + [numerical], axis=1)
    return mlp(dense["mlp"], x)[:, 0]


def loss(dense, embs, inputs, labels):
    return bce_with_logits(synthetic_logits(dense, embs, inputs), labels)
