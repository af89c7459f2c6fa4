"""Distributed equivalence tests.

Mirrors the reference's core test pattern (dist_model_parallel_test.py:
reference-model equivalence): an unsharded pure-JAX model and the sharded
DistributedEmbedding get identical weights, run the same batch, and must
produce identical outputs AND identical post-SGD-update weights — exercising
forward collectives and sharded autodiff in one go. Runs on an 8-virtual-CPU
device mesh (conftest.py)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.ops.embedding_ops import (
    RaggedIds, SparseIds, embedding_lookup)
from distributed_embeddings_tpu.parallel.mesh import create_mesh

BATCH = 16
LR = 0.5


def make_mesh(n=8):
    return create_mesh(jax.devices()[:n])


def ref_apply(weights, inputs, table_map, combiners):
    outs = []
    for i, t in enumerate(table_map):
        x = inputs[i]
        if isinstance(x, (RaggedIds, SparseIds)):
            out = embedding_lookup(weights[t], x, combiners[t])
        elif isinstance(x, tuple) and len(x) == 2:
            ids, w = x
            emb = jnp.take(weights[t], jnp.asarray(ids), axis=0)
            w = jnp.asarray(w).astype(emb.dtype)
            out = jnp.einsum("bk,bkw->bw", w, emb)
            if combiners[t] == "mean":
                denom = jnp.maximum(jnp.sum(w, axis=-1), 1.0)
                out = out / denom[:, None]
        else:
            x = jnp.asarray(x)
            if x.ndim == 1:
                out = jnp.take(weights[t], x, axis=0)
            else:
                out = embedding_lookup(weights[t], x, combiners[t])
        outs.append(out)
    return outs


# one program per shape and placement, shared by every case of the worker
_sgd = jax.jit(lambda p, g: p - LR * g)


def _sgd_respecting_placement(p, g):
    """p - LR*g, keeping offloaded (pinned-host) params in their memory
    space: the update runs in device space, the result is placed back."""
    def dev_sharding(x):
        import jax.sharding as shd
        s = x.sharding
        if isinstance(s, shd.NamedSharding):
            return shd.NamedSharding(s.mesh, s.spec)
        return shd.SingleDeviceSharding(list(x.devices())[0])

    if getattr(getattr(p, "sharding", None), "memory_kind", None) == \
            "pinned_host":
        pd = jax.device_put(p, dev_sharding(p))
        gd = g
        if getattr(getattr(g, "sharding", None), "memory_kind", None) == \
                "pinned_host":
            gd = jax.device_put(g, dev_sharding(g))
        return jax.device_put(_sgd(pd, gd), p.sharding)
    return _sgd(p, g)


def check_equivalence(specs, world=8, input_table_map=None, inputs=None,
                      seed=0, check_train=True, input_max_hotness=None,
                      rtol=1e-5, atol=1e-5, train_rtol=1e-4, train_atol=1e-5,
                      store_roundtrip=False, vocab_axis=False,
                      lookahead_axis=False, eager=False,
                      **dist_kwargs):
    """specs: list of (vocab, width) or (vocab, width, combiner).

    store_roundtrip (ISSUE 6): materialize the params through the
    versioned table store's publish/consume path (snapshot file ->
    consumer apply) before running the checks, so every equivalence
    property also holds for store-backed parameters.

    lookahead_axis (ISSUE 9): additionally train this exact plan for a
    few steps through the `schedule.LookaheadEngine` staged pipeline
    and require BIT-exact agreement with the monolithic sparse step
    (losses and final tables) — the prefetch/patch/drain restructuring
    must be invisible across the whole random config space. Configs the
    engine refuses by design (host-offloaded buckets, all-dp plans) are
    skipped for this axis only.

    vocab_axis (ISSUE 7): run the batch as RAW int64 keys through a
    `vocab.VocabManager` over a slack-inflated plan — inputs reach the
    forward as manager-translated physical rows, so every equivalence
    property also holds for dynamically-bound vocabularies (the
    reference model sees the same translated rows over zero-padded
    tables; what this axis exercises is the slack plan + binding
    composition, per-table and shared-table alike).

    eager: run the forward and gradient comparisons op by op, as a caller
    who never jits the layer does, instead of as one program each."""
    rng = np.random.RandomState(seed)
    embeddings = []
    combiners = []
    for spec in specs:
        v, w = spec[0], spec[1]
        c = spec[2] if len(spec) > 2 else None
        embeddings.append(Embedding(v, w, combiner=c))
        combiners.append(c)
    table_map = (list(input_table_map) if input_table_map
                 else list(range(len(specs))))

    if inputs is None:
        inputs = []
        for i, t in enumerate(table_map):
            v = specs[t][0]
            c = combiners[t]
            if c is None:
                inputs.append(jnp.asarray(rng.randint(0, v, size=(BATCH,))))
            else:
                inputs.append(jnp.asarray(
                    rng.randint(0, v, size=(BATCH, 2 + (i % 3)))))

    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]

    mesh = make_mesh(world) if world > 1 else None
    if vocab_axis:
        dist_kwargs.setdefault("vocab_slack", 16)
    dist = DistributedEmbedding(embeddings, mesh=mesh,
                                input_table_map=input_table_map,
                                input_max_hotness=input_max_hotness,
                                **dist_kwargs)
    if check_train and getattr(dist, "quantized_buckets", []):
        # quantized (int8/fp8) offloaded buckets have non-differentiable
        # table leaves: the dense-grad SGD comparison below cannot run,
        # and the SUPPORTED training path for them is the tapped sparse
        # step — its per-optimizer parity matrix lives in
        # test_store_dtype.py. Forward equivalence still checks here.
        check_train = False
    if vocab_axis:
        from distributed_embeddings_tpu.vocab import VocabManager

        # physical shapes are slack-inflated: pad the reference weights
        # with zero growth rows (both sides read the same padded tables)
        weights = [
            np.pad(np.asarray(w, np.float32),
                   ((0, dist.strategy.global_configs[t]["input_dim"]
                     - np.asarray(w).shape[0]), (0, 0)))
            for t, w in enumerate(weights)]
        mgr = VocabManager(dist, admit_threshold=1, use_native=False)

        def to_raw(vals):
            # injective map into a far-away int64 raw-key space
            return np.asarray(jax.device_get(vals),
                              np.int64) * 97 + 3_000_000_017

        raw_inputs, per_table_raw = [], {}
        for i, x in enumerate(inputs):
            t = (list(input_table_map) if input_table_map
                 else list(range(len(specs))))[i]
            if t not in mgr.vocabs:
                raw_inputs.append(x)
                continue
            if isinstance(x, RaggedIds):
                raw = to_raw(x.values)
                raw_inputs.append(RaggedIds(raw, x.row_splits))
            elif isinstance(x, SparseIds):
                raw = to_raw(x.values)
                raw_inputs.append(SparseIds(x.indices, raw, x.dense_shape))
            elif isinstance(x, tuple) and len(x) == 2:
                raw = to_raw(x[0])
                raw_inputs.append((raw, x[1]))
            else:
                raw = to_raw(x)
                raw_inputs.append(raw)
            per_table_raw.setdefault(t, []).append(raw.reshape(-1))
        for t, chunks in per_table_raw.items():
            mgr.vocabs[t].bind(np.unique(np.concatenate(chunks)))
        inputs = mgr.translate(raw_inputs)
    params = dist.set_weights(weights)
    if store_roundtrip:
        import tempfile
        from distributed_embeddings_tpu.store import (TableStore,
                                                      restore_from_published)
        with tempfile.TemporaryDirectory() as stream_dir:
            st = TableStore(dist, params)
            st.commit(params)
            st.publish(stream_dir)
            params = restore_from_published(dist, stream_dir).params

    # each side is one program (the batch is a constant of it), not one
    # program per operation; `eager` keeps the op-by-op public mode
    compiled = (lambda f: f) if eager else jax.jit
    ref_w = [jnp.asarray(w) for w in weights]
    ref_outs = compiled(
        lambda ws: ref_apply(ws, inputs, table_map, combiners))(ref_w)
    dist_outs = compiled(lambda p: dist.apply(p, inputs))(params)

    assert len(ref_outs) == len(dist_outs)
    for i, (a, b) in enumerate(zip(ref_outs, dist_outs)):
        np.testing.assert_allclose(np.asarray(b, np.float32), np.asarray(a),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"output {i}")

    if not check_train:
        return dist, params

    # training equivalence: same loss, compare post-SGD weights
    cots = [jnp.asarray(rng.randn(*o.shape).astype(np.float32))
            for o in ref_outs]

    def dist_loss(p):
        outs = dist.apply(p, inputs)
        return sum(jnp.vdot(o.astype(jnp.float32), c)
                   for o, c in zip(outs, cots))

    def ref_loss(ws):
        outs = ref_apply(ws, inputs, table_map, combiners)
        return sum(jnp.vdot(o, c) for o, c in zip(outs, cots))

    dist_grads = compiled(jax.grad(dist_loss))(params)
    new_params = jax.tree.map(_sgd_respecting_placement, params, dist_grads)

    ref_grads = compiled(jax.grad(ref_loss))(ref_w)
    new_ref = [_sgd(w, g) for w, g in zip(ref_w, ref_grads)]

    got = dist.get_weights(new_params)
    for t, (a, b) in enumerate(zip(new_ref, got)):
        np.testing.assert_allclose(b, np.asarray(a), rtol=train_rtol,
                                   atol=train_atol,
                                   err_msg=f"updated table {t}")
    if lookahead_axis:
        _check_lookahead_parity(dist, params, inputs, rng)
    return dist, params


def _check_lookahead_parity(dist, params, inputs, rng, steps=3):
    """Lookahead axis (ISSUE 9): the staged pipeline must be bit-exact
    against the monolithic sparse step on THIS plan — same weights,
    same batches (labels vary per step; ids repeat, which maximizes the
    touched-row/prefetch intersection the patch has to fix)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from distributed_embeddings_tpu.schedule import LookaheadEngine
    from distributed_embeddings_tpu.training import make_sparse_train_step

    class _Head:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, p, numerical, cats, labels, taps=None,
                    return_residuals=False):
            if taps is not None or return_residuals:
                outs, res = self.embedding(p["embedding"], list(cats),
                                           taps=taps, return_residuals=True)
            else:
                outs = self.embedding(p["embedding"], list(cats))
                res = None
            x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                axis=1).astype(jnp.float32)
            loss = jnp.mean(((x @ p["head"])[:, 0]
                             - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    model = _Head(dist)
    outs = jax.eval_shape(lambda p: dist.apply(p, inputs), params)
    feat = sum(int(np.prod(o.shape[1:])) for o in outs)
    batch = int(outs[0].shape[0])
    head = jnp.asarray(rng.randn(feat, 1).astype(np.float32)) * 0.1
    if dist.mesh is not None:
        head = jax.device_put(head,
                              NamedSharding(dist.mesh, PartitionSpec()))
    full = {"embedding": params, "head": head}
    num = jnp.zeros((batch, 1), jnp.float32)
    labels = [jnp.asarray(rng.randn(batch).astype(np.float32))
              for _ in range(steps)]

    try:
        eng = LookaheadEngine(model, "adagrad", lr=0.05, donate=False,
                              patch_capacity=batch)
    except (NotImplementedError, ValueError):
        return      # engine refuses this config by design (offload/all-dp)
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.05,
                                              donate=False)
    p, s = full, init_fn(full)
    mono = []
    for i in range(steps):
        p, s, loss = step_fn(p, s, num, list(inputs), labels[i])
        mono.append(float(loss))
    p2, s2 = full, eng.init(full)
    batches = [(num, list(inputs), labels[i]) for i in range(steps)]
    got = []
    for i in range(steps):
        nxt = batches[i + 1] if i + 1 < steps else None
        p2, s2, loss = eng.step(p2, s2, batches[i], nxt)
        got.append(float(loss))
    assert mono == got, f"lookahead axis: loss trace diverged {mono} {got}"
    w1 = dist.get_weights(p["embedding"])
    w2 = dist.get_weights(p2["embedding"])
    for t, (a, b) in enumerate(zip(w1, w2)):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"lookahead axis table {t}")


ONE_HOT_8 = [(96, 8), (50, 8), (100, 16), (120, 8), (40, 16), (70, 8),
             (60, 8), (81, 8)]


def test_basic():
    check_equivalence(ONE_HOT_8, strategy="basic", eager=True)


def test_memory_balanced():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced")


def test_memory_optimized():
    check_equivalence(ONE_HOT_8, strategy="memory_optimized")


def test_column_slice():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                      column_slice_threshold=400)


def test_row_slice():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                      row_slice_threshold=1600)


def test_data_parallel():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                      data_parallel_threshold=500)


def test_all_parallelism_modes():
    specs = [(10, 4), (96, 8), (50, 8), (1000, 16), (2000, 16), (30, 4),
             (800, 8), (64, 8)]
    check_equivalence(specs, strategy="memory_balanced",
                      column_slice_threshold=400,
                      row_slice_threshold=12800,
                      data_parallel_threshold=200)


class _ScaledEmbedding(Embedding):
    """Custom forward: 2x-scaled gather (non-gather semantics marker)."""

    SCALE = 2.0

    def __call__(self, params, inputs):
        return self.SCALE * jnp.take(params["embeddings"],
                                     jnp.asarray(inputs), axis=0)


class _GatherOkEmbedding(Embedding):
    """Overrides __call__ but asserts plain gather semantics."""

    det_gather_semantics = True

    def __call__(self, params, inputs):
        return jnp.take(params["embeddings"], jnp.asarray(inputs), axis=0)


def test_custom_layer_class_dp_runs_real_forward():
    """VERDICT r4 item 6: a custom layer_class placed data-parallel must run
    ITS forward (reference :820-834), not a plain gather."""
    rng = np.random.RandomState(0)
    mesh = make_mesh(8)
    specs = [(40, 8), (48, 8), (56, 8), (64, 8),
             (3000, 8), (3200, 8), (3400, 8), (3600, 8)]
    embs = [( _ScaledEmbedding if v < 100 else Embedding)(v, w)
            for v, w in specs]
    dist = DistributedEmbedding(embs, mesh=mesh,
                                strategy="memory_balanced",
                                data_parallel_threshold=600)
    assert dist._dp_custom_layers, "small tables should have placed DP"
    weights = [rng.randn(v, w).astype(np.float32) for v, w in specs]
    params = dist.set_weights(weights)
    inputs = [jnp.asarray(rng.randint(0, v, size=(BATCH,))) for v, _ in specs]
    outs = dist.apply(params, inputs)
    for i, (v, w) in enumerate(specs):
        want = np.asarray(weights[i])[np.asarray(inputs[i])]
        if v < 100:
            want = _ScaledEmbedding.SCALE * want      # the REAL forward
        np.testing.assert_allclose(np.asarray(outs[i]), want, rtol=1e-5,
                                   atol=1e-5, err_msg=f"table {i}")


def test_custom_layer_class_mp_rejected_loudly():
    """A custom-forward layer in a fused model-parallel group must fail at
    construction, not silently run gather semantics."""
    mesh = make_mesh(8)
    embs = [_ScaledEmbedding(3000, 8)] + [Embedding(v, 8)
                                          for v in (3200, 3400, 3600,
                                                    3800, 4000, 4200, 4400)]
    with pytest.raises(ValueError, match="custom embedding layer class"):
        DistributedEmbedding(embs, mesh=mesh, strategy="memory_balanced")


def test_custom_layer_class_gather_optout_allowed():
    """det_gather_semantics=True asserts gather equivalence: the subclass
    may place model-parallel and the fused executor's result is correct."""
    rng = np.random.RandomState(1)
    mesh = make_mesh(8)
    specs = [(3000, 8), (3200, 8), (3400, 8), (3600, 8),
             (3800, 8), (4000, 8), (4200, 8), (4400, 8)]
    embs = [_GatherOkEmbedding(v, w) for v, w in specs]
    dist = DistributedEmbedding(embs, mesh=mesh, strategy="memory_balanced")
    weights = [rng.randn(v, w).astype(np.float32) for v, w in specs]
    params = dist.set_weights(weights)
    inputs = [jnp.asarray(rng.randint(0, v, size=(BATCH,))) for v, _ in specs]
    outs = dist.apply(params, inputs)
    for i, _ in enumerate(specs):
        want = np.asarray(weights[i])[np.asarray(inputs[i])]
        np.testing.assert_allclose(np.asarray(outs[i]), want, rtol=1e-5,
                                   atol=1e-5, err_msg=f"table {i}")


def test_shared_tables_mp():
    check_equivalence([(96, 8), (50, 16)], input_table_map=[0, 1, 0, 1, 0])


def test_shared_tables_all_modes():
    specs = [(10, 4), (1000, 8), (4000, 16)]
    check_equivalence(specs, input_table_map=[0, 1, 2, 1, 0],
                      data_parallel_threshold=100,
                      row_slice_threshold=60000,
                      column_slice_threshold=1000,
                      strategy="memory_balanced")


def test_fewer_tables_than_workers():
    check_equivalence([(64, 16), (80, 16)], strategy="basic")


def test_multihot_sum():
    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"),
             (120, 8, "sum")]
    check_equivalence(specs, strategy="memory_balanced")


def test_multihot_mean():
    specs = [(96, 8, "mean"), (50, 8, "mean"), (100, 16, "mean"),
             (120, 8, "mean")]
    check_equivalence(specs, strategy="memory_balanced")


def test_multihot_mixed_combiners():
    specs = [(96, 8, "sum"), (50, 8, "mean"), (100, 16, None), (120, 8, None),
             (60, 8, "sum"), (70, 8, "mean"), (110, 16, "sum"), (90, 8, None)]
    check_equivalence(specs, strategy="memory_balanced")


def test_multihot_row_slice():
    specs = [(2000, 8, "sum"), (96, 8, "sum"), (50, 8, "sum"), (80, 8, "sum")]
    check_equivalence(specs, strategy="memory_balanced",
                      row_slice_threshold=8000)


def test_ragged_input():
    rng = np.random.RandomState(3)
    specs = [(96, 8, "sum"), (50, 8, "mean"), (70, 8, "sum"), (60, 8, "sum")]
    inputs = []
    for t, (v, w, c) in enumerate(specs):
        lengths = rng.randint(1, 5, size=BATCH)
        values = rng.randint(0, v, size=int(lengths.sum())).astype(np.int32)
        splits = np.cumsum([0] + list(lengths)).astype(np.int32)
        inputs.append(RaggedIds(jnp.asarray(values), jnp.asarray(splits)))
    check_equivalence(specs, inputs=inputs, input_max_hotness=[8] * 4,
                      strategy="memory_balanced")


def test_single_device_fallback():
    check_equivalence(ONE_HOT_8[:4], world=1)


def test_get_set_weights_roundtrip():
    rng = np.random.RandomState(7)
    specs = [(96, 8), (50, 8), (1000, 16), (2000, 16)]
    dist, params = check_equivalence(
        specs, strategy="memory_balanced", check_train=False,
        column_slice_threshold=2000, row_slice_threshold=30000)
    weights = [rng.randn(v, w).astype(np.float32) for v, w in specs]
    params = dist.set_weights(weights)
    got = dist.get_weights(params)
    for a, b in zip(weights, got):
        np.testing.assert_allclose(b, a, rtol=1e-6)


def test_gather_global_chunked_bounded(monkeypatch):
    """VERDICT r4 item 5: the multi-process get_weights gather must move at
    most GATHER_CHUNK_ELEMS elements per collective (reference _split_1d,
    :1024-1089), and chunked == unchunked bit-for-bit."""
    from jax.experimental import multihost_utils

    rng = np.random.RandomState(3)
    specs = [(96, 8), (50, 8), (1000, 8), (2000, 8)]
    mesh = make_mesh(8)
    dist = DistributedEmbedding([Embedding(v, w) for v, w in specs],
                                mesh=mesh, strategy="memory_balanced")
    params = dist.set_weights(
        [rng.randn(v, w).astype(np.float32) for v, w in specs])
    arr = max(params["tp"], key=lambda a: a.size)   # multi-shard bucket
    bound = 4096                    # elements; forces many chunks
    monkeypatch.setattr(DistributedEmbedding, "GATHER_CHUNK_ELEMS", bound)

    calls = []
    real = multihost_utils.process_allgather

    def spy(x, *a, **kw):
        calls.append(int(np.prod(x.shape)))
        return real(x, *a, **kw)

    monkeypatch.setattr(multihost_utils, "process_allgather", spy)
    got = dist._gather_global_chunked(arr)
    np.testing.assert_array_equal(got, np.asarray(arr))
    assert len(calls) > 1, "bound should have forced chunking"
    world, tail = arr.shape[0], int(np.prod(arr.shape[2:]))
    per_row = world * tail
    assert max(calls) <= max(bound, per_row), (max(calls), bound)


def test_indivisible_batch_raises():
    mesh = make_mesh(8)
    dist = DistributedEmbedding([Embedding(32, 8)], mesh=mesh)
    params = dist.set_weights([np.zeros((32, 8), np.float32)])
    with pytest.raises(ValueError, match="not divisible"):
        dist.apply(params, [jnp.zeros((12,), jnp.int32)])


def test_jit_apply():
    mesh = make_mesh(8)
    embeddings = [Embedding(v, w) for v, w in ONE_HOT_8]
    dist = DistributedEmbedding(embeddings, mesh=mesh,
                                strategy="memory_balanced")
    rng = np.random.RandomState(0)
    weights = [rng.randn(v, w).astype(np.float32) for v, w in ONE_HOT_8]
    params = dist.set_weights(weights)
    inputs = [jnp.asarray(rng.randint(0, v, size=(BATCH,)))
              for v, w in ONE_HOT_8]
    outs = jax.jit(lambda p: dist.apply(p, inputs))(params)
    ref = ref_apply([jnp.asarray(w) for w in weights], inputs,
                    list(range(8)), [None] * 8)
    for a, b in zip(ref, outs):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------- mp input
def check_mp_equivalence(specs, world=8, input_table_map=None, seed=0,
                         check_train=True, eager=False, **dist_kwargs):
    """Same equivalence check through the model-parallel input path
    (reference dp_input=False): each rank gets global-batch ids for the
    features it owns (strategy.input_ids_list order).

    eager: as `check_equivalence`'s. Called eagerly, `apply_mp` stages each
    rank's block on its own device; inside one program it stacks them."""
    rng = np.random.RandomState(seed)
    embeddings, combiners = [], []
    for spec in specs:
        v, w = spec[0], spec[1]
        c = spec[2] if len(spec) > 2 else None
        embeddings.append(Embedding(v, w, combiner=c))
        combiners.append(c)
    table_map = (list(input_table_map) if input_table_map
                 else list(range(len(specs))))

    inputs = []
    for i, t in enumerate(table_map):
        v, c = specs[t][0], combiners[t]
        if c is None:
            inputs.append(jnp.asarray(rng.randint(0, v, size=(BATCH,))))
        else:
            inputs.append(jnp.asarray(
                rng.randint(0, v, size=(BATCH, 2 + (i % 3)))))
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]

    mesh = make_mesh(world) if world > 1 else None
    dist = DistributedEmbedding(embeddings, mesh=mesh, dp_input=False,
                                input_table_map=input_table_map,
                                **dist_kwargs)
    params = dist.set_weights(weights)

    def to_mp(inps):
        return [[inps[dist.strategy.input_groups[1][pos]] for pos in rank_ids]
                for rank_ids in dist.strategy.input_ids_list]

    compiled = (lambda f: f) if eager else jax.jit
    ref_w = [jnp.asarray(w) for w in weights]
    ref_outs = compiled(
        lambda ws: ref_apply(ws, inputs, table_map, combiners))(ref_w)
    dist_outs = compiled(dist.apply_mp)(params, to_mp(inputs))

    assert len(ref_outs) == len(dist_outs)
    for i, (a, b) in enumerate(zip(ref_outs, dist_outs)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5, err_msg=f"mp output {i}")
    if not check_train:
        return dist, params

    cots = [jnp.asarray(rng.randn(*o.shape).astype(np.float32))
            for o in ref_outs]

    def dist_loss(p, mp_inputs):
        outs = dist.apply_mp(p, mp_inputs)
        return sum(jnp.vdot(o, c) for o, c in zip(outs, cots))

    def ref_loss(ws):
        outs = ref_apply(ws, inputs, table_map, combiners)
        return sum(jnp.vdot(o, c) for o, c in zip(outs, cots))

    dist_grads = compiled(jax.grad(dist_loss))(params, to_mp(inputs))
    new_params = jax.tree.map(_sgd_respecting_placement, params, dist_grads)
    ref_grads = compiled(jax.grad(ref_loss))(ref_w)
    new_ref = [_sgd(w, g) for w, g in zip(ref_w, ref_grads)]
    got = dist.get_weights(new_params)
    for t, (a, b) in enumerate(zip(new_ref, got)):
        np.testing.assert_allclose(b, np.asarray(a), rtol=1e-4, atol=1e-5,
                                   err_msg=f"mp updated table {t}")
    return dist, params


def test_mp_input_basic():
    check_mp_equivalence(ONE_HOT_8, strategy="basic", eager=True)


def test_mp_input_memory_balanced():
    check_mp_equivalence(ONE_HOT_8, strategy="memory_balanced")


def test_mp_input_column_slice():
    # slices of one table land on several ranks -> the same feature's ids are
    # fed on every owning rank (reference :846-851)
    check_mp_equivalence(ONE_HOT_8, strategy="memory_balanced",
                         column_slice_threshold=400)


def test_mp_input_multihot():
    specs = [(96, 8, "sum"), (50, 8, "mean"), (100, 16, "sum"),
             (120, 8, "sum"), (60, 8, "mean"), (70, 8, None)]
    check_mp_equivalence(specs, strategy="memory_balanced")


def test_mp_input_shared_tables():
    check_mp_equivalence([(96, 8), (50, 16)], input_table_map=[0, 1, 0, 1, 0])


def test_mp_input_single_device_flat():
    check_mp_equivalence(ONE_HOT_8[:4], world=1)


def test_mp_call_dispatch():
    mesh = make_mesh(8)
    dist = DistributedEmbedding([Embedding(64, 8) for _ in range(8)],
                                mesh=mesh, dp_input=False)
    params = dist.set_weights(
        [np.zeros((64, 8), np.float32) for _ in range(8)])
    with pytest.raises(ValueError, match="dp_input=False"):
        dist.apply(params, [jnp.zeros((BATCH,), jnp.int32)] * 8)
    mp_inputs = [[jnp.zeros((BATCH,), jnp.int32) for _ in rank_ids]
                 for rank_ids in dist.strategy.input_ids_list]
    outs = dist(params, mp_inputs)
    assert len(outs) == 8 and outs[0].shape == (BATCH, 8)


# ------------------------------------------------------- mixed precision
# reference parameterizes a mixed_precision_policy over its whole matrix
# (dist_model_parallel_test.py:30-34); params stay fp32, compute in bf16.
BF16_TOL = dict(rtol=4e-2, atol=4e-2, train_rtol=4e-2, train_atol=4e-2)


def test_bf16_basic():
    dist, _ = check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                                compute_dtype=jnp.bfloat16, **BF16_TOL)
    inputs = [jnp.zeros((BATCH,), jnp.int32)] * 8
    params = dist.set_weights(
        [np.zeros((v, w), np.float32) for v, w in ONE_HOT_8])
    outs = dist.apply(params, inputs)
    assert all(o.dtype == jnp.bfloat16 for o in outs)


def test_bf16_column_slice():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                      column_slice_threshold=400,
                      compute_dtype=jnp.bfloat16, **BF16_TOL)


def test_bf16_row_slice():
    check_equivalence(ONE_HOT_8, strategy="memory_balanced",
                      row_slice_threshold=1600,
                      compute_dtype=jnp.bfloat16, **BF16_TOL)


def test_bf16_multihot_all_modes():
    specs = [(10, 4, "sum"), (96, 8, "sum"), (1000, 16, "mean"),
             (2000, 16, "sum"), (800, 8, "sum")]
    check_equivalence(specs, strategy="memory_balanced",
                      column_slice_threshold=400, row_slice_threshold=12800,
                      data_parallel_threshold=200,
                      compute_dtype=jnp.bfloat16, **BF16_TOL)


def test_cpu_offload_equivalence():
    # gpu_embedding_size flags the largest tp tables for offload; they land
    # in separate buckets and stay numerically exact (reference :449-476)
    dist, params = check_equivalence(
        ONE_HOT_8, strategy="memory_balanced", gpu_embedding_size=800)
    assert any(b.offload for b in dist.plan.tp_buckets)
    assert any(not b.offload for b in dist.plan.tp_buckets)


def test_cpu_offload_bucket_separation():
    # offloaded tables must never be concat-fused with on-budget tables
    mesh = make_mesh(8)
    dist = DistributedEmbedding([Embedding(v, w) for v, w in ONE_HOT_8],
                                mesh=mesh, strategy="memory_balanced",
                                gpu_embedding_size=800)
    assert any(b.offload for b in dist.plan.tp_buckets)
    assert any(not b.offload for b in dist.plan.tp_buckets)


def test_cpu_offload_multihot():
    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 8, "mean"), (120, 8, "sum")]
    check_equivalence(specs, strategy="memory_balanced",
                      gpu_embedding_size=500)


class CustomEmbedding:
    """User-defined layer: anything exposing get_config() with
    input_dim/output_dim is distributable (reference CustomEmbedding
    dist_model_parallel_test.py:48-66 — gather semantics, config contract)."""

    def __init__(self, input_dim, output_dim):
        self.input_dim = input_dim
        self.output_dim = output_dim

    def get_config(self):
        return {"input_dim": self.input_dim, "output_dim": self.output_dim}


def test_custom_embedding_layer():
    rng = np.random.RandomState(11)
    specs = [(96, 8), (50, 8), (100, 16), (120, 8)]
    embeddings = [CustomEmbedding(v, w) for v, w in specs]
    mesh = make_mesh(8)
    dist = DistributedEmbedding(embeddings, mesh=mesh, strategy="basic")
    weights = [rng.randn(v, w).astype(np.float32) for v, w in specs]
    params = dist.set_weights(weights)
    inputs = [jnp.asarray(rng.randint(0, v, size=(BATCH,))) for v, w in specs]
    outs = dist.apply(params, inputs)
    for w, x, o in zip(weights, inputs, outs):
        np.testing.assert_allclose(np.asarray(o), w[np.asarray(x)],
                                   rtol=1e-6)
