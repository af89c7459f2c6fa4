"""Seeded randomized-config equivalence sweep.

The hand-written tests cover the reference's named cases; this sweep walks
random corners of the planner x forward configuration space (table
counts/sizes/widths, combiners, shared tables, thresholds, strategies) and
requires exact reference-model equivalence for each. Seeds are fixed —
failures reproduce.
"""

import numpy as np
import pytest

from test_dist_model_parallel import check_equivalence

STRATEGIES = ["basic", "memory_balanced", "memory_optimized",
              "comm_balanced", "auto"]


def gen_config(seed):
    rng = np.random.RandomState(1000 + seed)
    n = int(rng.randint(4, 11))
    specs = []
    for _ in range(n):
        vocab = int(rng.choice([8, 40, 120, 500, 1300, 5000]))
        width = int(rng.choice([4, 8, 16]))
        combiner = [None, "sum", "mean"][rng.randint(3)]
        specs.append((vocab, width, combiner))
    # occasionally share a table between two inputs
    table_map = list(range(n))
    if n >= 4 and rng.rand() < 0.5:
        table_map.append(int(rng.randint(n)))
    kw = {"strategy": STRATEGIES[rng.randint(len(STRATEGIES))]}
    if rng.rand() < 0.5:
        kw["data_parallel_threshold"] = int(rng.choice([64, 400]))
    if rng.rand() < 0.5:
        kw["column_slice_threshold"] = int(rng.choice([2000, 8000]))
    if rng.rand() < 0.5:
        kw["row_slice_threshold"] = int(rng.choice([8000, 40000]))
    if rng.rand() < 0.3:
        # host-offload the biggest buckets (pinned_host on the CPU backend)
        kw["gpu_embedding_size"] = int(rng.choice([3000, 12000]))
    if rng.rand() < 0.3:
        import jax.numpy as jnp
        kw["compute_dtype"] = jnp.bfloat16
        kw.update(rtol=4e-2, atol=4e-2, train_rtol=4e-2, train_atol=4e-2)
    if rng.rand() < 0.3:
        # wire-dtype axis (ISSUE 5): bf16 exchange wire, f32 local math —
        # one rounding per wire crossing, so the bf16 compute tolerance
        # covers it (combiner-None buckets keep f32 by the plan gate)
        kw["exchange_wire"] = "bf16"
        kw.update(rtol=4e-2, atol=4e-2, train_rtol=4e-2, train_atol=4e-2)
    if rng.rand() < 0.35:
        # store-backed axis (ISSUE 6): params materialize through the
        # table store's publish/consume path (snapshot file -> consumer
        # apply — bit-exact by contract), so every equivalence property
        # in this sweep also runs against store-backed parameters
        kw["store_roundtrip"] = True
    if rng.rand() < 0.3:
        # vocab axis (ISSUE 7): the batch arrives as RAW int64 keys and
        # reaches the forward through a VocabManager binding over a
        # slack-inflated plan — every equivalence property also holds
        # for dynamically-bound vocabularies
        kw["vocab_axis"] = True
    if rng.rand() < 0.3:
        # lookahead axis (ISSUE 9): train the same plan through the
        # staged prefetch/patch/drain pipeline and require BIT-exact
        # agreement with the monolithic sparse step (engine-refused
        # configs — offloaded buckets, all-dp plans — skip the axis)
        kw["lookahead_axis"] = True
    if rng.rand() < 0.3:
        # storage-dtype axis (ISSUE 15 + 17): quantized at-rest rows on
        # BOTH residencies. Half the draws force an offload budget
        # (cold buckets: decode in the host exchange path); the other
        # half leave whatever residency the config already drew — under
        # the ISSUE 17 lifted gate device-resident buckets ALSO
        # quantize, exercising the decode-at-gather branch inside the
        # jitted forward. One decode per gather either way: the
        # bf16-class tolerance covers it. (LookaheadEngine refuses
        # quantized buckets, so that axis self-skips here.)
        kw["storage_dtype"] = "int8"
        if rng.rand() < 0.5:
            kw.setdefault("gpu_embedding_size",
                          int(rng.choice([3000, 12000])))
        kw.update(rtol=4e-2, atol=4e-2, train_rtol=4e-2, train_atol=4e-2)
    return specs, table_map, kw


@pytest.mark.parametrize("seed", range(8))
def test_random_config_equivalence(seed):
    specs, table_map, kw = gen_config(seed)
    try:
        check_equivalence(specs, input_table_map=table_map, seed=seed,
                          check_train=(seed % 4 == 0), **kw)
    except ValueError as e:
        if "Not enough tables" in str(e):
            pytest.skip(f"seed {seed}: config unplaceable on 8 devices")
        raise


@pytest.mark.parametrize("seed", range(4))
def test_random_config_ragged_and_weighted(seed):
    """Same sweep but inputs arrive as RaggedIds / (ids, weights) tuples
    for combiner tables — the other two prepared-input forms."""
    import jax.numpy as jnp
    from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds
    from test_dist_model_parallel import BATCH

    specs, table_map, kw = gen_config(seed)
    rng = np.random.RandomState(2000 + seed)
    inputs, max_hot = [], []
    for i, t in enumerate(table_map):
        v, _, c = specs[t]
        if c is None:
            inputs.append(jnp.asarray(rng.randint(0, v, size=(BATCH,))))
            max_hot.append(1)
        elif rng.rand() < 0.5:
            k = int(rng.randint(2, 6))
            lengths = rng.randint(1, k + 1, size=BATCH)
            values = rng.randint(0, v, size=int(lengths.sum()))
            splits = np.cumsum([0] + list(lengths))
            inputs.append(RaggedIds(jnp.asarray(values.astype(np.int32)),
                                    jnp.asarray(splits.astype(np.int32))))
            max_hot.append(k)
        else:
            k = int(rng.randint(2, 5))
            ids = rng.randint(0, v, size=(BATCH, k))
            w = (rng.rand(BATCH, k) > 0.3).astype(np.float32)
            inputs.append((jnp.asarray(ids), jnp.asarray(w)))
            max_hot.append(k)
    try:
        check_equivalence(specs, input_table_map=table_map, inputs=inputs,
                          input_max_hotness=max_hot, seed=seed,
                          check_train=(seed == 0), **kw)
    except ValueError as e:
        if "Not enough tables" in str(e):
            pytest.skip(f"seed {seed}: config unplaceable on 8 devices")
        raise


@pytest.mark.parametrize("seed", range(6))
def test_storage_dtype_stream_and_stash_fuzz(seed, tmp_path):
    """Storage-dtype axis over the train-to-serve row stores (ISSUE 15):
    random configs through publish -> consume (random delta dtype) and
    admit -> evict -> re-admit (random stash dtype), asserting the
    documented per-row decode bounds — and BIT-exactness at f32."""
    import jax
    import jax.numpy as jnp
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.ops import wire as wire_ops
    from distributed_embeddings_tpu.store import TableStore, scan_published
    from distributed_embeddings_tpu.vocab import VocabManager
    from test_dist_model_parallel import make_mesh

    rng = np.random.RandomState(4000 + seed)
    dtypes = ["f32", "int8"] + (["fp8"] if wire_ops.fp8_supported()
                                else [])
    delta_dtype = dtypes[rng.randint(len(dtypes))]
    stash_dtype = dtypes[rng.randint(len(dtypes))]
    n = int(rng.randint(6, 10))
    specs = [(int(rng.choice([40, 120, 500, 1500])),
              int(rng.choice([8, 16, 32])), "sum") for _ in range(n)]
    kw = {}
    if rng.rand() < 0.5:
        # offload the big tables so the STORED-quantized read/apply
        # seam (not just the stream codec) is on the fuzzed path
        kw["gpu_embedding_size"] = 3000
        kw["storage_dtype"] = delta_dtype
    mesh = make_mesh(8)
    W = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]

    def build():
        return DistributedEmbedding(
            [Embedding(v, w, combiner=c) for v, w, c in specs],
            mesh=mesh, **kw)

    # ---- publish -> consume at the random delta dtype
    emb = build()
    store = TableStore(emb, emb.set_weights(W), delta_dtype=delta_dtype)
    d = str(tmp_path / "pub")
    store.publish(d)
    ins = [jnp.asarray(rng.randint(0, v, size=(16, 2)).astype(np.int32))
           for v, _, _ in specs]
    store.observe(ins)
    store.commit(store.params)
    info = store.publish(d)
    assert info["dtype"] == delta_dtype
    assert info["payload_bytes"] == info["model_payload_bytes"]
    c_emb = build()
    consumer = TableStore(c_emb, c_emb.init(jax.random.PRNGKey(seed)))
    for _, _, path in scan_published(d):
        consumer.apply_published(path)
    for a, b in zip(store.get_weights(), consumer.get_weights()):
        if delta_dtype == "f32" and not kw.get("storage_dtype"):
            np.testing.assert_array_equal(a, b)
        else:
            # one encode on publish + (for quantized-at-rest consumers)
            # one re-encode on apply: two quantization steps bound it
            bound = 2 * wire_ops.store_decode_bound(a, delta_dtype
                                                    if delta_dtype != "f32"
                                                    else kw.get(
                                                        "storage_dtype",
                                                        "f32"))
            assert (np.abs(a - b).max(axis=-1) <= bound + 1e-6).all()

    # ---- admit -> evict -> re-admit with the quantized stash
    v_emb = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in specs],
        mesh=mesh, vocab_slack=16)
    mgr = VocabManager(v_emb, use_native=False, stash_dtype=stash_dtype)
    gtid = min(mgr.vocabs)
    mv = mgr.vocabs[gtid]
    width = v_emb.strategy.global_configs[gtid]["output_dim"]
    kcount = int(rng.randint(3, 9))
    keys = rng.randint(10_000, 20_000, size=kcount).astype(np.int64)
    keys = np.unique(keys)
    rows = rng.randn(len(keys), width).astype(np.float32)
    mv.bind(keys)
    mv.unbind(keys, rows)
    for i, k in enumerate(keys):
        back = mv.stash_take(int(k))
        assert back is not None
        if stash_dtype == "f32":
            np.testing.assert_array_equal(back, rows[i])
        else:
            bound = float(wire_ops.store_decode_bound(
                rows[i], stash_dtype).max())
            assert np.abs(back - rows[i]).max() <= bound + 1e-6


def test_sparse_ids_through_distributed_forward():
    """COO SparseIds inputs through the full distributed forward — the one
    prepared-input form the named tests don't cover (reference sparse-input
    path, embedding_lookup_ops.py:90-96)."""
    import jax.numpy as jnp
    from distributed_embeddings_tpu.ops.embedding_ops import SparseIds
    from test_dist_model_parallel import BATCH

    specs = [(300, 8, "sum"), (500, 8, "mean"), (120, 8, "sum"),
             (800, 8, "sum"), (256, 8, "mean"), (640, 8, "sum"),
             (90, 8, "sum"), (410, 8, "sum")]
    rng = np.random.RandomState(11)
    inputs, max_hot = [], []
    for v, _, _ in specs:
        k = int(rng.randint(2, 5))
        rows, cols, vals = [], [], []
        for b in range(BATCH):
            nnz = int(rng.randint(1, k + 1))
            for j in range(nnz):
                rows.append(b)
                cols.append(j)
                vals.append(int(rng.randint(0, v)))
        idx = np.stack([rows, cols], axis=1).astype(np.int32)
        inputs.append(SparseIds(jnp.asarray(idx),
                                jnp.asarray(np.asarray(vals, np.int32)),
                                (BATCH, k)))
        max_hot.append(k)
    check_equivalence(specs, inputs=inputs, input_max_hotness=max_hot,
                      strategy="memory_balanced", check_train=False)


def test_comm_balanced_equivalence():
    """comm_balanced placement is numerically identical to the reference
    model, hotness hints and all (mixed one-hot + multi-hot + shared)."""
    specs = [(96, 8, "sum"), (50, 8), (300, 8, "sum"), (80, 8, "mean"),
             (120, 8), (700, 8, "sum"), (60, 8), (210, 8, "sum")]
    table_map = list(range(8)) + [0, 2]
    hot = []
    rng = np.random.RandomState(5)
    import jax.numpy as jnp
    inputs = []
    for i, t in enumerate(table_map):
        v = specs[t][0]
        c = specs[t][2] if len(specs[t]) > 2 else None
        if c is None:
            inputs.append(jnp.asarray(rng.randint(0, v, size=(16,))))
            hot.append(1)
        else:
            k = 2 + (i % 4)
            inputs.append(jnp.asarray(rng.randint(0, v, size=(16, k))))
            hot.append(k)
    check_equivalence(specs, input_table_map=table_map, inputs=inputs,
                      input_max_hotness=hot, strategy="comm_balanced")


def test_mp_input_mixed_forms_equivalence():
    """apply_mp (feature-sharded input) with mixed dense/ragged/weighted
    forms matches the unsharded reference — per-rank input routing plus
    every prepared-input form at once."""
    import jax
    import jax.numpy as jnp
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds
    from test_dist_model_parallel import make_mesh, ref_apply, BATCH

    specs = [(96, 8, "sum"), (50, 8, "mean"), (300, 8, "sum"), (80, 8, None),
             (120, 8, "sum"), (700, 8, "sum"), (60, 8, None), (210, 8, "sum")]
    hot = [5, 3, 4, 1, 2, 6, 1, 3]
    rng = np.random.RandomState(9)
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in specs],
        mesh=make_mesh(), strategy="comm_balanced", dp_input=False,
        input_max_hotness=hot)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    params = dist.set_weights(weights)

    # one global input per feature, then routed per owning rank
    flat_inputs = []
    for i, (v, w, c) in enumerate(specs):
        k = hot[i]
        if c is None:
            flat_inputs.append(jnp.asarray(
                rng.randint(0, v, size=(BATCH,)).astype(np.int32)))
        elif i % 3 == 0:
            lengths = rng.randint(1, k + 1, size=BATCH)
            values = rng.randint(0, v, size=int(lengths.sum()))
            splits = np.cumsum([0] + list(lengths))
            flat_inputs.append(RaggedIds(
                jnp.asarray(values.astype(np.int32)),
                jnp.asarray(splits.astype(np.int32))))
        else:
            ids = rng.randint(0, v, size=(BATCH, k))
            wts = (rng.rand(BATCH, k) > 0.3).astype(np.float32)
            flat_inputs.append((jnp.asarray(ids), jnp.asarray(wts)))

    mp_inputs = [
        [flat_inputs[dist.strategy.input_groups[1][pos]] for pos in rank_ids]
        for rank_ids in dist.strategy.input_ids_list]
    outs = dist.apply_mp(params, mp_inputs)

    refs = jax.jit(lambda ws: ref_apply(
        ws, flat_inputs, list(range(len(specs))), [c for _, _, c in specs]))(
            [jnp.asarray(w) for w in weights])
    for i, (a, b) in enumerate(zip(refs, outs)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5, err_msg=f"output {i}")
