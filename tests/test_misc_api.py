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


# ---- every DET_* default is read where it is used: unset gives the
# fallback beside the read, set gives the value, and an explicit argument
# (where the consumer takes one) still wins
def _knob_emb(**kw):
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding
    return DistributedEmbedding(
        [Embedding(40, 8, combiner="sum") for _ in range(2)], mesh=None,
        vocab_slack=4, **kw)


def _knob_store(**kw):
    from distributed_embeddings_tpu.store import TableStore
    emb = _knob_emb()
    return TableStore(emb, emb.init(jax.random.PRNGKey(0)), **kw)


def _knob_fit(**kw):
    """`fit` over no batches: it resolves its knobs, builds its pipeline
    and takes no step. Returns what it handed the ingest pipeline."""
    from distributed_embeddings_tpu import training
    from distributed_embeddings_tpu.utils import pipeline
    from test_sparse_train import TinyModel

    model = TinyModel([(40, 8, "sum")] * 2, None)
    params = {"embedding": model.embedding.init(jax.random.PRNGKey(0)),
              "head": {"w": jnp.zeros((16, 1), jnp.float32)}}
    seen = {}
    real = pipeline.staged_batches

    def spy(source, **kwargs):
        seen["depth"] = kwargs["depth"]
        return real(source, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pipeline, "staged_batches", spy)
        training.fit(model, params, iter(()), steps=0, log_every=0, **kw)
    return seen


def _read_id_wire(explicit):
    from distributed_embeddings_tpu.ops import wire
    return wire.default_id_wire()


def _read_lookahead(explicit):
    # a dense-path fit refuses any lookahead but 0
    try:
        _knob_fit(sparse=False, lookahead=explicit)
    except ValueError as e:
        assert "lookahead requires the sparse tapped path" in str(e)
        return 1
    return 0


def _read_publish_every(explicit):
    # a publishing fit refuses to run without a directory
    try:
        _knob_fit(sparse=True, store=object(), publish_every=explicit)
    except ValueError as e:
        assert "publish_every requires publish_dir" in str(e)
        return "publishing"
    return "off"


def _read_vocab_admit(explicit):
    from distributed_embeddings_tpu.vocab import VocabManager
    return VocabManager(_knob_emb(), admit_threshold=explicit,
                        use_native=False).admit_threshold


def _read_fleet(attr):
    from distributed_embeddings_tpu.fleet import AdmissionController
    return lambda explicit: getattr(
        AdmissionController(**{attr: explicit}), attr)


# (variable, reader(explicit argument or None), reading when unset,
#  a value to set, its reading, an explicit argument, its reading)
_KNOBS = [
    ("DET_EXCHANGE_WIRE",
     lambda x: _knob_emb(exchange_wire=x).strategy.exchange_wire,
     "f32", "bf16", "bf16", "f32", "f32"),
    ("DET_ID_WIRE", _read_id_wire, "auto", "int32", "int32", None, None),
    ("DET_STORE_DTYPE",
     lambda x: _knob_emb(storage_dtype=x).strategy.storage_dtype,
     "f32", "int8", "int8", "fp8", "fp8"),
    ("DET_DELTA_DTYPE", lambda x: _knob_store(delta_dtype=x).delta_dtype,
     "f32", "int8", "int8", "fp8", "fp8"),
    ("DET_HOT_ROWS", lambda x: _knob_emb(hot_rows=x).strategy.hot_rows,
     0, "16", 16, 4, 4),
    ("DET_LOOKAHEAD", _read_lookahead, 0, "1", 1, 0, 0),
    ("DET_PIPELINE_DEPTH",
     lambda x: _knob_fit(pipeline_depth=x)["depth"], 2, "5", 5, 3, 3),
    ("DET_PUBLISH_EVERY", _read_publish_every,
     "off", "3", "publishing", 0, "off"),
    ("DET_STORE_SNAPSHOT_EVERY",
     lambda x: _knob_store(snapshot_every=x).snapshot_every,
     0, "4", 4, 2, 2),
    ("DET_VOCAB_ADMIT", _read_vocab_admit, 2, "5", 5, 3, 3),
    ("DET_FLEET_MAX_QUEUE_DEPTH", _read_fleet("max_queue_depth"),
     64, "7", 7, 9, 9),
    ("DET_FLEET_MAX_QUEUE_ROWS", _read_fleet("max_queue_rows"),
     None, "100", 100, 50, 50),
]


@pytest.mark.parametrize("env,read,unset,value,set_reading,explicit,"
                         "explicit_reading", _KNOBS,
                         ids=[k[0] for k in _KNOBS])
def test_det_default_is_read_at_its_site(monkeypatch, env, read, unset,
                                         value, set_reading, explicit,
                                         explicit_reading):
    monkeypatch.delenv(env, raising=False)
    assert read(None) == unset
    monkeypatch.setenv(env, value)
    assert read(None) == set_reading
    if explicit is not None:
        assert read(explicit) == explicit_reading
