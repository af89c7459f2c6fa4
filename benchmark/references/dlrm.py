"""Plain reference of DLRM (Naumov et al., arXiv:1906.00091, as MLPerf and
the reference's ``examples/dlrm`` run it): bottom MLP with ReLU after every
layer, the pairwise dot products of the bottom output and the 26 embeddings
(strictly lower triangle, row-major) concatenated in front of the bottom
output, top MLP with ReLU between layers, one logit, sigmoid binary
cross-entropy averaged over the global batch.

``inputs`` is the numerical features ``[B, 13]`` f32, ``labels`` the clicks
``[B, 1]`` f32, ``dense`` ``{"bottom": [...], "top": [...]}`` of
``{"w", "b"}`` layers."""

import jax.numpy as jnp
import numpy as np

from benchmark.reference import bce_with_logits, mlp


def dlrm_logits(dense, embs, numerical):
    bottom = mlp(dense["bottom"], numerical, final_activation=True)
    feats = jnp.stack([bottom] + list(embs), axis=1)          # [B, F+1, d]
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    rows, cols = np.tril_indices(feats.shape[1], k=-1)
    pairwise = gram[:, rows, cols]
    return mlp(dense["top"], jnp.concatenate([pairwise, bottom], axis=1))[:, 0]


def loss(dense, embs, inputs, labels):
    return bce_with_logits(dlrm_logits(dense, embs, inputs), labels)
