"""Hot-row replication (ISSUE 4): frequency-based hybrid parallelism in
the training step.

Parity contract: a hot-sharded step must match the no-hot-shard step.
At hotness 1 (the DLRM shape) every (sample, slot) lane is entirely hit
or miss, and the observed deviation is at float-rounding scale; for
k > 1 the split reorders float summation (hit einsum + miss einsum vs
one fused combine, dense scatter-add + psum vs segment-sum), so the
documented tolerance is allclose at 1e-5 — see docs/perf_model.md
"Hot-row replication".
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import fit, make_sparse_train_step

BATCH = 16
SPECS = [(40, 4, "sum"), (60, 8, "sum"), (30, 4, "sum"), (50, 8, "mean")]


class _TapModel:
    def __init__(self, mesh, specs=SPECS, **kw):
        self.embedding = DistributedEmbedding(
            [Embedding(v, w, combiner=c) for v, w, c in specs],
            mesh=mesh, **kw)

    def loss_fn(self, params, numerical, cats, labels, taps=None,
                return_residuals=False):
        out = self.embedding(params["embedding"], list(cats), taps=taps,
                             return_residuals=return_residuals)
        outs, res = out if return_residuals else (out, None)
        x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                            axis=1).astype(jnp.float32)
        loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
        return (loss, res) if return_residuals else loss

    def apply(self, params, numerical, cats):
        outs = self.embedding(params["embedding"], list(cats))
        x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                            axis=1)
        return jnp.sum(x, axis=1)


def _zipf_cats(data, specs=SPECS, hotness=2, batch=BATCH, weighted=False):
    cats = [jnp.asarray(np.minimum(
        data.zipf(1.3, size=(batch, hotness)) - 1, v - 1).astype(np.int32))
        for v, _, _ in specs]
    if not weighted:
        return cats
    return [(c, jnp.asarray(
        data.rand(batch, hotness).astype(np.float32) + 0.5)) for c in cats]


def _run(hot_rows, optimizer="adagrad", steps=3, admit_at=1, specs=SPECS,
         hotness=2, seed=0, strategy="auto", weighted=False, **kw):
    rng = np.random.RandomState(seed)
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(mesh, specs=specs, hot_rows=hot_rows, **kw)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    params = {"embedding": model.embedding.set_weights(weights)}
    init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.05,
                                              strategy=strategy)
    state = init_fn(params)
    data = np.random.RandomState(7)
    losses = []
    for s in range(steps):
        cats = _zipf_cats(data, specs, hotness, weighted=weighted)
        labels = jnp.asarray(data.randn(BATCH).astype(np.float32))
        if hot_rows:
            model.embedding.observe_hot_ids(cats)
            if s == admit_at:
                p, st = model.embedding.sync_hot_rows(
                    params["embedding"], state["emb"], admit=True)
                params = {**params, "embedding": p}
                state = {**state, "emb": st}
                assert any(t.resident for t
                           in model.embedding._hot_trackers.values())
        params, state, loss = step_fn(params, state, jnp.zeros((BATCH, 1)),
                                      cats, labels)
        losses.append(float(loss))
    return losses, params, state, model


def _assert_parity(optimizer, strategy="auto", weighted=False, **env):
    import os
    for k, v in env.items():
        os.environ[k] = v
    try:
        l0, p0, _, m0 = _run(0, optimizer, strategy=strategy,
                             weighted=weighted)
        l1, p1, s1, m1 = _run(8, optimizer, strategy=strategy,
                              weighted=weighted)
    finally:
        for k in env:
            os.environ.pop(k, None)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
    w0 = m0.embedding.get_weights(p0["embedding"])
    w1 = m1.embedding.get_weights(p1["embedding"])
    for t, (a, b) in enumerate(zip(w0, w1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=f"table {t} ({optimizer})")
    # and the synced canonical params agree with the overlayed dump
    p_sync, _ = m1.embedding.sync_hot_rows(p1["embedding"], s1["emb"])
    for a, b in zip(w1, m1.embedding.get_weights(p_sync)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("exchange", ["padded", "ragged"])
def test_hot_parity_adagrad(exchange):
    """Hot-split vs no-hot-shard training parity, both exchange paths."""
    _assert_parity("adagrad", DET_RAGGED_EXCHANGE=(
        "1" if exchange == "ragged" else "0"))


def test_hot_parity_weighted_inputs():
    """(ids, weights) inputs take the EXPLICIT weight-exchange branch of
    the hot split — unweighted inputs skip that exchange and reconstruct
    the 0/scale effective weights receiver-side from the sentinel, so
    this is the only path that moves a weight block over the wire."""
    _assert_parity("adagrad", weighted=True)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("exchange", ["padded", "ragged"])
def test_hot_parity_optimizers(optimizer, exchange):
    _assert_parity(optimizer, DET_RAGGED_EXCHANGE=(
        "1" if exchange == "ragged" else "0"))


def test_hot_parity_tiled_forward():
    """Hot split x tiled forward gather (DET_LOOKUP_PATH=tiled, interpret
    mode off-TPU): the presorted artifact covers the sentinel-masked
    stream — the tiled gather clamps sid internally, the update drops the
    sentinel lanes. Fold still holds (sort-bound gate lives in
    test_hlo_hot_step_adds_zero_sorts / hlo_audit)."""
    _assert_parity("adagrad", strategy="tiled", DET_LOOKUP_PATH="tiled")


def test_empty_hot_set_is_identity():
    """Before any admission the hot shard is behaviorally inert: every
    lookup misses and the membership is all-sentinel."""
    mesh = create_mesh(jax.devices()[:8])
    rng = np.random.RandomState(1)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    m0 = _TapModel(mesh)
    m1 = _TapModel(mesh, hot_rows=8)
    p0 = m0.embedding.set_weights(weights)
    p1 = m1.embedding.set_weights(weights)
    assert "hot" not in p0 and "hot" in p1
    cats = _zipf_cats(np.random.RandomState(2))
    out0 = jax.jit(m0.embedding)(p0, cats)
    out1 = jax.jit(m1.embedding)(p1, cats)
    for a, b in zip(out0, out1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_hot_forward_hits_read_hot_shard():
    """Resident rows are served from the replicated hot param: perturbing
    the hot rows changes the output; perturbing the canonical rows of
    resident ids does NOT (the canonical table is out of the hit path)."""
    mesh = create_mesh(jax.devices()[:2])
    specs = [(32, 4, "sum")]
    rng = np.random.RandomState(3)
    m = _TapModel(mesh, specs=specs, hot_rows=4)
    weights = [rng.randn(32, 4).astype(np.float32)]
    params = m.embedding.set_weights(weights)
    emb = m.embedding
    b = emb._hot_buckets[0]
    # admit ids 0 and 1 of input 0 across EVERY slot the input feeds
    # (column slices live on several ranks, each with its own key space)
    keys = []
    for (rank, bb, slot_idx) in emb.plan.tp_input_slots[0]:
        off = emb.plan.tp_buckets[bb].slots[rank][slot_idx].row_offset
        rows_max = max(emb.plan.tp_buckets[bb].rows_max, 1)
        keys += [rank * rows_max + off + 0, rank * rows_max + off + 1]
    params, _ = emb.sync_hot_rows(params, None, new_keys={b: np.asarray(keys)})
    cats = [jnp.asarray(np.array([[0, 1], [2, 3]], np.int32))]
    base = np.asarray(emb(params, cats)[0])
    # 1. poke the hot rows -> row-0/1 outputs move
    poked = dict(params)
    poked["hot"] = list(params["hot"])
    poked["hot"][b] = {"ids": params["hot"][b]["ids"],
                       "rows": params["hot"][b]["rows"] + 1.0}
    out = np.asarray(emb(poked, cats)[0])
    assert np.abs(out[0] - base[0]).max() > 0.5
    np.testing.assert_allclose(out[1], base[1], atol=1e-6)
    # 2. poke the canonical table everywhere -> only MISS ids move
    poked2 = dict(params)
    poked2["tp"] = [t + 1.0 for t in params["tp"]]
    out2 = np.asarray(emb(poked2, cats)[0])
    np.testing.assert_allclose(out2[0], base[0], atol=1e-6)
    assert np.abs(out2[1] - base[1]).max() > 0.5


def test_hot_adam_does_not_touch_masked_rows():
    """Regression (review finding): hit lanes are SENTINEL-masked, not
    id-0-masked — a zero-contribution touch at a real row is NOT the
    identity for lazy adam (moment decay runs on every touched row). Train
    a row's moments, admit a DIFFERENT id, keep hitting it: the trained
    row must stay bit-identical to the hot-less baseline."""
    specs = [(32, 8, "sum")]

    def drive(hot):
        model = _TapModel(None, specs=specs, hot_rows=hot)
        rng = np.random.RandomState(4)
        weights = [rng.randn(32, 8).astype(np.float32) * 0.1]
        params = {"embedding": model.embedding.set_weights(weights)}
        init_fn, step_fn = make_sparse_train_step(model, "adam", lr=0.05)
        state = init_fn(params)
        emb = model.embedding
        # step 0 trains id 0's moments (so a later spurious touch would
        # visibly bleed its momentum into the table)
        cats0 = [jnp.asarray(np.array([[0], [0]], np.int32))]
        params, state, _ = step_fn(params, state, jnp.zeros((2, 1)),
                                   cats0, jnp.ones((2,)))
        if hot:
            b = emb._hot_buckets[0]
            (rank, bb, slot_idx) = emb.plan.tp_input_slots[0][0]
            off = emb.plan.tp_buckets[bb].slots[rank][slot_idx].row_offset
            rows_max = max(emb.plan.tp_buckets[bb].rows_max, 1)
            p, s = emb.sync_hot_rows(
                params["embedding"], state["emb"],
                new_keys={b: np.asarray([rank * rows_max + off + 5])})
            params = {**params, "embedding": p}
            state = {**state, "emb": s}
        # steps with id 5 (the hot hit) and id 7, never id 0
        cats = [jnp.asarray(np.array([[5], [7]], np.int32))]
        for _ in range(4):
            params, state, _ = step_fn(params, state, jnp.zeros((2, 1)),
                                       cats, jnp.ones((2,)))
        return model.embedding.get_weights(params["embedding"])[0]

    w_base = drive(0)
    w_hot = drive(4)
    np.testing.assert_array_equal(w_base[0], w_hot[0])   # untouched row
    np.testing.assert_allclose(w_base, w_hot, rtol=1e-5, atol=1e-6)


def test_checkpoint_roundtrip_merges_hot_rows():
    """The portable dump (get_weights) overlays resident hot rows, a
    set_weights round-trip restarts empty-hot with identical numerics,
    and sync_hot_rows writes the rows back into the canonical arrays."""
    losses, params, state, model = _run(8, "adagrad", steps=3)
    emb = model.embedding
    # resident hot rows diverge from the canonical (stale) rows pre-sync
    w_overlay = emb.get_weights(params["embedding"])
    stale = dict(params["embedding"])
    stale.pop("hot")
    w_stale = emb.get_weights({**stale})
    assert any(np.abs(a - b).max() > 1e-7
               for a, b in zip(w_overlay, w_stale)), \
        "hot rows never diverged; test admits nothing?"
    # sync writes them back: canonical-only dump now matches the overlay
    p_sync, _ = emb.sync_hot_rows(params["embedding"], state["emb"])
    no_hot = dict(p_sync)
    no_hot.pop("hot")
    for a, b in zip(w_overlay, emb.get_weights(no_hot)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # portable round-trip: reload into a fresh layer, outputs agree
    mesh = create_mesh(jax.devices()[:8])
    m2 = _TapModel(mesh, hot_rows=8)
    p2 = {"embedding": m2.embedding.set_weights(w_overlay)}
    cats = _zipf_cats(np.random.RandomState(11))
    out1 = jax.jit(model.embedding)(p_sync, cats)
    out2 = jax.jit(m2.embedding)(p2["embedding"], cats)
    for a, b in zip(out1, out2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_sync_admission_gathers_canonical_state():
    """Admission copies rows AND optimizer-state rows from the canonical
    arrays, so admitting is numerically a no-op for the next update."""
    losses, params, state, model = _run(8, "adagrad", steps=2, admit_at=1)
    emb = model.embedding
    for pos_h, b in enumerate(emb._hot_buckets):
        entry = params["embedding"]["hot"][b]
        ids = np.asarray(jax.device_get(entry["ids"])).astype(np.int64)
        rows = np.asarray(jax.device_get(entry["rows"]))
        sent = emb._hot_sentinel(b)
        valid = ids < sent
        if not valid.any():
            continue
        # hot acc rows must be >= the adagrad init fill (gathered, not
        # re-initialized) wherever resident
        acc = np.asarray(jax.device_get(state["emb"]["hot"][pos_h][0]))
        assert (acc[valid] >= 0.1 - 1e-6).all()
        # membership is sorted with sentinel padding at the tail
        assert (np.diff(ids) >= 0).all()
        assert rows.shape[0] == emb.plan.tp_buckets[b].hot_rows


def test_hot_keys_from_counts_ranks_by_frequency():
    specs = [(32, 4, "sum")]
    m = _TapModel(None, specs=specs, hot_rows=4)   # world 1: single slot
    emb = m.embedding
    # over-length counts (IntegerLookup.counts() is [capacity+1] with the
    # OOV slot): entries past the table's input_dim must be DROPPED, not
    # attributed to neighboring tables'/ranks' rows (review finding)
    counts = [np.zeros((40,), np.int64)]
    counts[0][[3, 7, 9]] = [50, 40, 30]
    counts[0][20] = 5
    counts[0][35] = 1000           # past input_dim 32: must not admit
    new_keys = emb.hot_keys_from_counts(counts)
    b = emb._hot_buckets[0]
    (rank, bb, slot_idx) = emb.plan.tp_input_slots[0][0]
    off = emb.plan.tp_buckets[bb].slots[rank][slot_idx].row_offset
    rows_max = max(emb.plan.tp_buckets[bb].rows_max, 1)
    got_rows = sorted(k % rows_max - off for k in new_keys[b].tolist())
    assert got_rows == [3, 7, 9, 20]


def test_negative_ids_never_hit():
    """Regression (review finding): a negative id folds onto a LOWER
    slot/rank's key range and could alias a resident hot key there — it
    must always MISS and take the baseline's deterministic invalid-id
    path instead of being served another table's hot row."""
    specs = [(32, 4, "sum")]
    m0 = _TapModel(None, specs=specs)
    m1 = _TapModel(None, specs=specs, hot_rows=4)
    rng = np.random.RandomState(9)
    weights = [rng.randn(32, 4).astype(np.float32)]
    p0 = m0.embedding.set_weights(weights)
    p1 = m1.embedding.set_weights(weights)
    emb = m1.embedding
    b = emb._hot_buckets[0]
    (rank, bb, slot_idx) = emb.plan.tp_input_slots[0][0]
    off = emb.plan.tp_buckets[bb].slots[rank][slot_idx].row_offset
    rows_max = max(emb.plan.tp_buckets[bb].rows_max, 1)
    # admit id 2; then query id -1 whose folded key is base+(-1) = key of
    # id 1... and id (2 - 32) whose folded key aliases resident id 2
    p1, _ = emb.sync_hot_rows(p1, None,
                              new_keys={b: np.asarray(
                                  [rank * rows_max + off + 2])})
    cats = [jnp.asarray(np.array([[2 - 32], [-1]], np.int32))]
    out0 = np.asarray(m0.embedding(p0, cats)[0])
    out1 = np.asarray(m1.embedding(p1, cats)[0])
    np.testing.assert_allclose(out1, out0, rtol=1e-6, atol=1e-7)


def test_padding_report_post_hot_accounting():
    _, params, state, model = _run(8, "adagrad", steps=2)
    rep = model.embedding.exchange_padding_report()
    assert "hot_hit_ids" in rep and "true_ids_post_hot" in rep
    assert rep["hot_hit_ids"] >= 0
    # residual USEFUL volume subtracts from true ids, never from the
    # (padded, unchanged) wire-slot count
    assert rep["true_ids_post_hot"] \
        == rep["true_ids"] - rep["hot_hit_ids"]
    hot_entries = [g for g in rep["groups"] if "hot_hit_ids" in g]
    assert hot_entries, rep
    for g in hot_entries:
        assert g["true_ids_post_hot"] == g["true_ids"] - g["hot_hit_ids"]
        assert 0 <= g["true_ids_post_hot"] <= g["true_ids"]
    # projection override
    rep2 = model.embedding.exchange_padding_report(hot_hit_rate=0.5)
    assert rep2["hot_hit_ids"] > 0


def test_hlo_hot_step_adds_zero_sorts():
    """Acceptance gate (ISSUE 4): the hot-split tapped step lowers with NO
    additional sort instructions per exchange group versus the folded
    baseline — membership is a searchsorted (binary search), the hot
    update a dense scatter."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "det_hlo_audit", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools",
            "hlo_audit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    base = mod.audit_tapped_step(strategy="sort", hot_rows=0)
    hot = mod.audit_tapped_step(strategy="sort", hot_rows=1024)
    assert hot["hlo_sort"] <= base["hlo_sort"], (base, hot)
    assert hot["hlo_sort"] <= hot["sort_bound"], hot


def test_fit_hot_sync_every_smoke():
    """fit()'s hot_sync_every cadence: observes, admits, returns
    canonical-consistent params + hot stats in the history."""
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(mesh, hot_rows=8)
    rng = np.random.RandomState(5)
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in SPECS]
    params = {"embedding": model.embedding.set_weights(weights)}
    data = np.random.RandomState(6)

    def batch(step):
        return (np.zeros((BATCH, 1), np.float32),
                [np.asarray(c) for c in _zipf_cats(data)],
                data.randn(BATCH).astype(np.float32))

    params, opt_state, hist = fit(model, params, batch, steps=4,
                                  optimizer="adagrad", lr=0.05,
                                  log_every=0, hot_sync_every=2)
    assert "hot_stats" in hist and hist["hot_stats"]
    assert any(s["resident"] for s in hist["hot_stats"].values())
    assert len(hist["loss"]) == 4
    assert np.isfinite(hist["loss"]).all()


def test_integer_lookup_counts_feed_admission():
    """IntegerLookup exposes per-index frequencies (native in-probe
    counting / numpy per-occurrence counting) in the shape
    hot_keys_from_counts consumes."""
    from distributed_embeddings_tpu.layers.embedding import IntegerLookup

    lk = IntegerLookup(16)
    lk(np.array([100, 100, 100, 200, 200, 300]))
    c = lk.counts()
    assert c.shape == (17,)
    # indices are assigned in first-appearance order: 100->1, 200->2, 300->3
    assert c[1] == 3 and c[2] == 2 and c[3] == 1


def test_tapped_forward_without_hot_taps_raises():
    """A hand-built tap pytree ({'tp', 'row'} — the pre-hot-shard
    contract) on an active hot split must be rejected: the split masks
    resident rows' canonical gradients to zero by design, so their
    updates flow ONLY through taps['hot'] — accepting such taps would
    silently freeze the hottest rows."""
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(mesh, hot_rows=8)
    params = {"embedding": model.embedding.init(jax.random.PRNGKey(0))}
    cats = _zipf_cats(np.random.RandomState(0))
    taps = model.embedding.make_taps(cats)
    assert "hot" in taps
    # tapless and make_taps-built forwards both work
    forward = jax.jit(model.embedding)
    forward(params["embedding"], list(cats))
    forward(params["embedding"], list(cats), taps=taps)
    with pytest.raises(ValueError, match=r"taps\['hot'\]"):
        forward(params["embedding"], list(cats),
                taps={"tp": taps["tp"], "row": taps["row"]})


def test_observe_hot_ids_ignores_out_of_range_ids():
    """The host-side observer mirrors the device split's lane_rows guard:
    ids outside [0, segment rows) neither count toward a NEIGHBORING
    segment's flat key (phantom admission) nor toward hit/miss stats the
    padding report folds in (the device split forces them to miss)."""
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(mesh, hot_rows=8)
    tr_before = dict(model.embedding.hot_stats())
    model.embedding.observe_hot_ids(
        [np.full((BATCH, 2), v + 1000, np.int32) for v, _, _ in SPECS])
    stats = model.embedding.hot_stats()
    assert all(s["tracked"] == 0 and s["hits"] == 0 and s["misses"] == 0
               for s in stats.values()), (tr_before, stats)
    # in-range ids still count
    model.embedding.observe_hot_ids(
        [np.zeros((BATCH, 2), np.int32) for _ in SPECS])
    assert all(s["tracked"] > 0 for s in model.embedding.hot_stats().values())
