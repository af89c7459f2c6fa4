"""Coverage for parity-surface pieces not exercised elsewhere:
ConcatOneHotEmbedding (reference embedding.py:173-198), the training API
shims, staging helpers, initializers, and the DLRM LR schedule."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import ConcatOneHotEmbedding
from distributed_embeddings_tpu.models.dlrm import (dlrm_initializer,
                                                    make_lr_schedule)
from distributed_embeddings_tpu.ops.embedding_ops import read_var_no_copy
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.parallel.staging import stage_replicated
from distributed_embeddings_tpu.training import (
    BroadcastGlobalVariablesCallback, DistributedGradientTape,
    broadcast_variables)
from distributed_embeddings_tpu.utils.initializers import get_initializer


def test_concat_one_hot_embedding_matches_separate_tables():
    sizes = [7, 13, 5]
    width = 4
    layer = ConcatOneHotEmbedding(sizes, width)
    params = layer.init(jax.random.PRNGKey(0))
    assert params["params"].shape == (sum(sizes), width)

    rng = np.random.RandomState(0)
    ids = np.stack([rng.randint(0, v, size=6) for v in sizes], axis=1)
    out = layer(params, jnp.asarray(ids))
    assert out.shape == (6, len(sizes), width)

    # manual per-table lookup against the fused table's offset ranges
    offs = np.concatenate([[0], np.cumsum(sizes)])
    table = np.asarray(params["params"])
    for f, v in enumerate(sizes):
        sub = table[offs[f]:offs[f + 1]]
        np.testing.assert_allclose(np.asarray(out[:, f, :]), sub[ids[:, f]])

    # single fused gather is differentiable end to end
    g = jax.grad(lambda p: jnp.sum(layer(p, jnp.asarray(ids)) ** 2))(params)
    assert g["params"].shape == table.shape


def test_concat_one_hot_grad_routes_to_correct_rows():
    layer = ConcatOneHotEmbedding([3, 3], 2)
    params = {"params": jnp.ones((6, 2))}
    ids = jnp.asarray([[1, 2]])
    g = jax.grad(lambda p: jnp.sum(layer(p, ids)))(params)["params"]
    expect = np.zeros((6, 2))
    expect[1] = 1.0       # table 0 row 1
    expect[3 + 2] = 1.0   # table 1 row 2 at offset 3
    np.testing.assert_allclose(np.asarray(g), expect)


def test_training_shims_single_process():
    params = {"w": jnp.arange(4.0)}
    assert broadcast_variables(params) is params
    cb = BroadcastGlobalVariablesCallback()
    assert cb.on_train_begin(params) is params
    # second call is a no-op too
    assert cb.on_train_begin(params) is params
    with pytest.raises(NotImplementedError):
        BroadcastGlobalVariablesCallback(root_rank=1)

    tape = DistributedGradientTape()
    loss, grads = tape.gradient(lambda p: jnp.sum(p["w"] ** 2), params)
    assert float(loss) == float(jnp.sum(params["w"] ** 2))
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               2 * np.arange(4.0))


def test_read_var_no_copy_identity():
    x = jnp.ones((3, 2))
    assert read_var_no_copy(x) is x


def test_stage_replicated():
    mesh = create_mesh(jax.devices()[:8])
    tree = {"a": np.arange(6.0).reshape(2, 3)}
    out = stage_replicated(mesh, tree)
    assert out["a"].sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(out["a"]), tree["a"])


def test_dlrm_initializer_range():
    init = dlrm_initializer()
    w = init(jax.random.PRNGKey(0), (100, 8))
    bound = 1.0 / np.sqrt(100)
    assert float(jnp.max(jnp.abs(w))) <= bound
    assert float(jnp.std(w)) > 0.3 * bound  # actually uniform, not zeros


def test_make_lr_schedule_phases():
    sched = make_lr_schedule(2.0, warmup_steps=10, decay_start_step=20,
                             decay_steps=10, poly_power=2)
    # warmup is linear from 1/10 to 1
    np.testing.assert_allclose(float(sched(0)), 2.0 * (1 - 10 / 10), atol=1e-6)
    np.testing.assert_allclose(float(sched(5)), 2.0 * 0.5, atol=1e-6)
    # constant plateau
    np.testing.assert_allclose(float(sched(15)), 2.0, atol=1e-6)
    # poly-2 decay hits zero at decay end and stays there
    np.testing.assert_allclose(float(sched(25)), 2.0 * 0.25, atol=1e-6)
    np.testing.assert_allclose(float(sched(30)), 0.0, atol=1e-6)
    np.testing.assert_allclose(float(sched(40)), 0.0, atol=1e-6)


@pytest.mark.parametrize("spec", ["uniform", "zeros",
                                  {"class_name": "RandomUniform",
                                   "config": {"minval": -0.5,
                                              "maxval": 0.5}}])
def test_get_initializer_specs(spec):
    init = get_initializer(spec)
    w = init(jax.random.PRNGKey(1), (16, 4), jnp.float32)
    assert w.shape == (16, 4)
    if spec == "zeros":
        np.testing.assert_allclose(np.asarray(w), 0.0)


def test_set_weights_error_paths():
    """Analogue of the reference's set_weight error test (:461): wrong
    weight count / shape fail loudly, not silently."""
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding

    dist = DistributedEmbedding([Embedding(10, 4), Embedding(20, 4)],
                                mesh=create_mesh(jax.devices()[:8]))
    with pytest.raises(ValueError, match="Expected 2 weights"):
        dist.set_weights([np.zeros((10, 4), np.float32)])
    with pytest.raises(ValueError, match="shape"):
        dist.set_weights([np.zeros((10, 4), np.float32),
                          np.zeros((21, 4), np.float32)])


def test_prefetch_to_device_order_and_content():
    from distributed_embeddings_tpu.utils.prefetch import prefetch_to_device

    batches = [{"x": np.full((2, 2), i, np.float32)} for i in range(5)]
    out = list(prefetch_to_device(iter(batches), size=2))
    assert len(out) == 5
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_allclose(np.asarray(b["x"]), i)
    # fewer batches than queue depth
    out = list(prefetch_to_device(iter(batches[:1]), size=3))
    assert len(out) == 1


def test_top_level_api_matches_reference():
    """Every name the reference exports at package top level
    (reference distributed_embeddings/__init__.py:17-27) must exist here."""
    import distributed_embeddings_tpu as d

    for name in ["embedding_lookup", "Embedding", "IntegerLookup",
                 "dist_model_parallel", "DistEmbeddingStrategy",
                 "DistributedEmbedding", "broadcast_variables",
                 "DistributedGradientTape", "DistributedOptimizer",
                 "BroadcastGlobalVariablesCallback", "__version__"]:
        assert hasattr(d, name), name


def test_gather_global_chunked_device_bucket(monkeypatch):
    """ADVICE r5: the chunked gather must take the jit-sliced path on a
    DEVICE bucket too (eager indexing of non-fully-addressable arrays is
    backend-dependent). Force chunk < rows and check exact reassembly."""
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding

    mesh = create_mesh(jax.devices()[:8])
    dist = DistributedEmbedding([Embedding(640, 8), Embedding(320, 8)],
                                mesh=mesh)
    params = dist.init(jax.random.PRNGKey(0))
    arr = params["tp"][0]                       # [8, rows, 8] device bucket
    world, rows, tail = arr.shape
    assert rows > 3
    # chunk = GATHER_CHUNK_ELEMS // (world * tail) -> rows // 3 (< rows)
    monkeypatch.setattr(DistributedEmbedding, "GATHER_CHUNK_ELEMS",
                        world * tail * (rows // 3))
    out = dist._gather_global_chunked(arr)
    np.testing.assert_array_equal(out, np.asarray(arr))


def test_compile_cache_dir_comes_from_outside_or_the_checkout(monkeypatch):
    """`enable_compile_cache`: a directory given through
    JAX_COMPILATION_CACHE_DIR is returned untouched and NO directory is
    set in code; without it the cache is <checkout>/.jax_cache."""
    import os
    from distributed_embeddings_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/given/from/outside")
    assert compile_cache.enable_compile_cache() == "/given/from/outside"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
