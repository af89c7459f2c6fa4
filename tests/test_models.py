"""Model-level smoke + equivalence tests (DLRM, synthetic zoo)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.models.dlrm import DLRM, dot_interact
from distributed_embeddings_tpu.models.synthetic import (
    SYNTHETIC_MODELS, SyntheticModel, InputGenerator)
from distributed_embeddings_tpu.parallel.mesh import create_mesh

SIZES = [50, 60, 200, 300, 400, 500, 600, 700]


def _mesh(n=8):
    return create_mesh(jax.devices()[:n])


def test_dlrm_dp_input_forward_and_grad():
    mesh = _mesh()
    model = DLRM(table_sizes=SIZES, embedding_dim=8, bottom_mlp_dims=(16, 8),
                 top_mlp_dims=(16, 1), num_numerical_features=4, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    B = 32
    numerical = jnp.asarray(rng.rand(B, 4).astype(np.float32))
    cats = [jnp.asarray(rng.randint(0, v, (B,)).astype(np.int32))
            for v in SIZES]
    labels = jnp.asarray(rng.randint(0, 2, (B, 1)).astype(np.float32))
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
        params, numerical, cats, labels)
    assert np.isfinite(float(loss))
    assert jnp.all(jnp.isfinite(grads["top_mlp"][0]["w"]))


def test_dlrm_mp_input_forward():
    # dp_input=False: the model takes nested per-rank categorical inputs
    mesh = _mesh()
    model = DLRM(table_sizes=SIZES, embedding_dim=8, bottom_mlp_dims=(16, 8),
                 top_mlp_dims=(16, 1), num_numerical_features=4, mesh=mesh,
                 dp_input=False)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    B = 32
    numerical = jnp.asarray(rng.rand(B, 4).astype(np.float32))
    global_cats = [jnp.asarray(rng.randint(0, v, (B,)).astype(np.int32))
                   for v in SIZES]
    strat = model.embedding.strategy
    mp_cats = [[global_cats[strat.input_groups[1][pos]] for pos in rank_ids]
               for rank_ids in strat.input_ids_list]
    out = model.apply(params, numerical, mp_cats)
    assert out.shape == (B, 1)
    assert np.all(np.isfinite(np.asarray(out)))

    # must equal the dp_input model's output with identical weights
    model_dp = DLRM(table_sizes=SIZES, embedding_dim=8,
                    bottom_mlp_dims=(16, 8), top_mlp_dims=(16, 1),
                    num_numerical_features=4, mesh=mesh)
    weights = model.embedding.get_weights(params["embedding"])
    params_dp = dict(params)
    params_dp["embedding"] = model_dp.embedding.set_weights(weights)
    out_dp = model_dp.apply(params_dp, numerical, global_cats)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_dp),
                               rtol=1e-5, atol=1e-5)


def test_dot_interact_shape():
    B, F, d = 8, 5, 16
    rng = np.random.RandomState(0)
    embs = [jnp.asarray(rng.randn(B, d).astype(np.float32))
            for _ in range(F)]
    bottom = jnp.asarray(rng.randn(B, d).astype(np.float32))
    out = dot_interact(embs, bottom)
    n = F + 1
    assert out.shape == (B, n * (n - 1) // 2 + d)


def test_synthetic_tiny_step():
    cfg = SYNTHETIC_MODELS["tiny"]
    # shrink vocabs so this runs fast on CPU: replace configs with tiny rows
    small = cfg._replace(embedding_configs=[
        c._replace(num_rows=min(c.num_rows, 1000))
        for c in cfg.embedding_configs])
    mesh = _mesh()
    model = SyntheticModel(small, mesh=mesh, distributed=True)
    params = model.init(jax.random.PRNGKey(0))
    gen = InputGenerator(small, 32, alpha=1.05, num_batches=1, seed=0)
    numerical, cats, labels = gen[0]
    loss = jax.jit(model.loss_fn)(params, numerical, cats, labels)
    assert np.isfinite(float(loss))
