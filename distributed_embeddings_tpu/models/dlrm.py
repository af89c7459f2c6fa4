"""DLRM (Deep Learning Recommendation Model) on TPU.

Functional re-design of the reference DLRM example
(reference: examples/dlrm/main.py:77-140, examples/dlrm/utils.py:27-113):
bottom MLP over dense features -> 26 embedding lookups via
DistributedEmbedding -> pairwise dot-interaction -> top MLP -> logit.

TPU-first details:
  * MLPs run in bfloat16-friendly sizes and map onto the MXU; the whole train
    step is one jit-compiled SPMD program (dense part data-parallel via batch
    sharding, embeddings hybrid-parallel via DistributedEmbedding).
  * dot_interact extracts the strictly-lower-triangular pairwise dots with a
    static boolean mask — a gather with a trace-time-constant index vector,
    not tf.boolean_mask's dynamic shapes.
"""

import math
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.obs.spans import spanned


def dlrm_initializer():
    """Uniform(+-1/sqrt(rows)) embedding init (reference utils.py:27-41)."""
    def init(key, shape, dtype=jnp.float32):
        maxval = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, dtype, -maxval, maxval)
    return init


def dot_interact(emb_outs: Sequence[jax.Array],
                 bottom_mlp_out: jax.Array) -> jax.Array:
    """Pairwise-dot feature interaction (reference utils.py:92-113).

    Stacks [bottom_mlp_out] + emb_outs into [B, F+1, d], computes the Gram
    matrix on the MXU, gathers the strictly-lower-triangular entries with a
    static index, and re-concats the bottom MLP output.
    """
    feats = jnp.stack([bottom_mlp_out] + list(emb_outs), axis=1)  # [B, F+1, d]
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats,
                      preferred_element_type=jnp.float32)
    n = feats.shape[1]
    rows, cols = np.tril_indices(n, k=-1)
    flat = gram.reshape(gram.shape[0], n * n)
    pairwise = flat[:, rows * n + cols]                            # [B, n(n-1)/2]
    return jnp.concatenate([pairwise, bottom_mlp_out], axis=1)


def _mlp_init(key, dims: List[int], in_dim: int):
    params = []
    for i, out_dim in enumerate(dims):
        kw, kb, key = jax.random.split(key, 3)
        # glorot-normal kernel, bias ~ N(0, 1/out) (reference main.py:127-139)
        std = math.sqrt(2.0 / (in_dim + out_dim))
        params.append({
            "w": jax.random.normal(kw, (in_dim, out_dim)) * std,
            "b": jax.random.normal(kb, (out_dim,)) * math.sqrt(1.0 / out_dim),
        })
        in_dim = out_dim
    return params


def _mlp_apply(params, x, final_activation=False):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1 or final_activation:
            x = jax.nn.relu(x)
    return x


class DLRM:
    """DLRM with hybrid-parallel embeddings.

    Args:
      table_sizes: vocab size per categorical feature (26 for Criteo).
      embedding_dim: embedding width (128 for the MLPerf config).
      bottom_mlp_dims / top_mlp_dims: layer sizes; top ends at 1 logit.
      num_numerical_features: dense feature count (13 for Criteo).
      mesh: device mesh (None = single device).
      dist_strategy / column_slice_threshold / row_slice_threshold /
      data_parallel_threshold: forwarded to DistributedEmbedding.
      compute_dtype: activations dtype (bfloat16 recommended on TPU).
    """

    def __init__(self,
                 table_sizes: Sequence[int],
                 embedding_dim: int = 128,
                 bottom_mlp_dims: Sequence[int] = (512, 256, 128),
                 top_mlp_dims: Sequence[int] = (1024, 1024, 512, 256, 1),
                 num_numerical_features: int = 13,
                 mesh=None,
                 dist_strategy: str = "memory_balanced",
                 column_slice_threshold: Optional[int] = None,
                 row_slice_threshold: Optional[int] = None,
                 data_parallel_threshold: Optional[int] = None,
                 dp_input: bool = True,
                 compute_dtype=jnp.float32):
        self.table_sizes = list(table_sizes)
        self.embedding_dim = embedding_dim
        self.bottom_mlp_dims = list(bottom_mlp_dims)
        self.top_mlp_dims = list(top_mlp_dims)
        self.num_numerical_features = num_numerical_features
        self.compute_dtype = compute_dtype

        embeddings = [
            Embedding(v, embedding_dim, embeddings_initializer=dlrm_initializer())
            for v in self.table_sizes
        ]
        self.embedding = DistributedEmbedding(
            embeddings,
            strategy=dist_strategy,
            column_slice_threshold=column_slice_threshold,
            row_slice_threshold=row_slice_threshold,
            data_parallel_threshold=data_parallel_threshold,
            dp_input=dp_input,
            mesh=mesh,
            # bf16 inside the embedding halves the mp->dp all_to_all bytes
            compute_dtype=(compute_dtype
                           if compute_dtype != jnp.float32 else None))
        self.mesh = mesh

    @spanned("model/init")
    def init(self, key) -> dict:
        ke, kb, kt = jax.random.split(key, 3)
        n_feats = len(self.table_sizes) + 1
        interact_dim = n_feats * (n_feats - 1) // 2 + self.bottom_mlp_dims[-1]
        return {
            "embedding": self.embedding.init(ke),
            "bottom_mlp": _mlp_init(kb, self.bottom_mlp_dims,
                                    self.num_numerical_features),
            "top_mlp": _mlp_init(kt, self.top_mlp_dims, interact_dim),
        }

    def apply(self, params: dict, numerical: jax.Array,
              categorical: Sequence[jax.Array], taps=None,
              return_residuals: bool = False):
        """Forward: [B, num_numerical] + categorical ids -> [B, 1] logit.

        With dp_input=True `categorical` is one global-batch id array per
        feature; with dp_input=False it is the nested per-rank form expected
        by DistributedEmbedding.apply_mp (reference dp_input semantics,
        dist_model_parallel.py:729-731). taps/return_residuals: sparse
        training hooks (see DistributedEmbedding.apply).
        """
        x = numerical.astype(self.compute_dtype)
        bottom = _mlp_apply(params["bottom_mlp"], x, final_activation=True)
        res = None
        if taps is not None or return_residuals:
            emb_outs, res = self.embedding(
                params["embedding"], list(categorical), taps=taps,
                return_residuals=True)
        else:
            emb_outs = self.embedding(params["embedding"], list(categorical))
        emb_outs = [e.astype(self.compute_dtype) for e in emb_outs]
        interact = dot_interact(emb_outs, bottom).astype(self.compute_dtype)
        out = _mlp_apply(params["top_mlp"], interact)
        return (out, res) if return_residuals else out

    def loss_fn(self, params, numerical, categorical, labels, taps=None,
                return_residuals: bool = False):
        out = self.apply(params, numerical, categorical, taps=taps,
                         return_residuals=return_residuals)
        logits, res = out if return_residuals else (out, None)
        logits = logits[:, 0]
        labels = labels.reshape(-1).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        # sigmoid binary cross-entropy, mean over the global batch
        loss = jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return (loss, res) if return_residuals else loss

    def make_train_step(self, optimizer):
        """Build a jittable train step: (opt_state, params, batch) -> updated."""
        def step(params, opt_state, numerical, categorical, labels):
            loss, grads = jax.value_and_grad(self.loss_fn)(
                params, numerical, categorical, labels)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss
        return step


def make_lr_schedule(base_lr: float, warmup_steps: int, decay_start_step: int,
                     decay_steps: int, poly_power: int = 2):
    """Warmup -> constant -> polynomial decay LR schedule
    (reference utils.py:45-88), as a pure optax-style schedule function."""
    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warmup = 1.0 - (warmup_steps - step) / warmup_steps
        decay_end = decay_start_step + decay_steps
        decay = jnp.clip((decay_end - step) / decay_steps, 0.0, 1.0) ** poly_power
        factor = jnp.where(step < warmup_steps, warmup,
                           jnp.where(step < decay_start_step, 1.0, decay))
        return base_lr * factor
    return schedule
