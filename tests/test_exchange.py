"""Comm-design tests for the dp->mp exchange.

Round-2 guarantee (VERDICT round-1 items 1/3/4): table-parallel ids move via
fixed-shape `lax.all_to_all` exchange groups — per-device id traffic is
O(owned features x true hotness), like the reference's hvd.alltoall with
per-destination splits (reference dist_model_parallel.py:169-288), NOT an
all_gather of every feature's ids to every device; and one-hot inputs are
never padded to the model's global max hotness.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.parallel.mesh import create_mesh

BATCH = 16


def make_dist(specs, **kw):
    mesh = create_mesh(jax.devices()[:8])
    embeddings = []
    for spec in specs:
        v, w = spec[0], spec[1]
        c = spec[2] if len(spec) > 2 else None
        embeddings.append(Embedding(v, w, combiner=c))
    dist = DistributedEmbedding(embeddings, mesh=mesh, **kw)
    weights = [np.zeros((s[0], s[1]), np.float32) for s in specs]
    params = dist.set_weights(weights)
    return dist, params


def lowered_text(dist, params, inputs):
    return jax.jit(lambda p, i: dist.apply(p, i)).lower(params, inputs).as_text()


def test_tp_exchange_is_all_to_all_not_all_gather():
    specs = [(96, 8), (50, 8), (100, 16), (120, 8), (40, 16), (70, 8),
             (60, 8), (81, 8)]
    dist, params = make_dist(specs, strategy="memory_balanced")
    inputs = [jnp.zeros((BATCH,), jnp.int32) for _ in specs]
    txt = lowered_text(dist, params, inputs)
    assert len(re.findall(r"all_to_all", txt)) > 0
    # pure table-parallel model: no all_gather anywhere in the forward
    assert len(re.findall(r"all_gather", txt)) == 0


def test_row_slice_still_uses_all_gather():
    # row slicing legitimately all_gathers ids (reference grouped_allgather
    # :893); make sure the tp rewrite did not break that path's lowering
    specs = [(4000, 8), (96, 8), (50, 8), (80, 8)]
    dist, params = make_dist(specs, strategy="memory_balanced",
                             row_slice_threshold=16000)
    inputs = [jnp.zeros((BATCH,), jnp.int32) for _ in specs]
    txt = lowered_text(dist, params, inputs)
    assert len(re.findall(r"all_gather", txt)) > 0


def test_no_global_hotness_padding():
    # one hotness-64 input next to one-hot inputs: the one-hot ids must
    # exchange in their own k=1 group, not be padded 64x (round-1 Weak #3)
    specs = [(500, 8, "sum")] + [(100 + i, 8) for i in range(7)]
    dist, params = make_dist(specs, strategy="memory_balanced")
    prep = dist._prepare_inputs(
        [jnp.zeros((BATCH, 64), jnp.int32)]
        + [jnp.zeros((BATCH,), jnp.int32)] * 7)
    tp_prep = [prep[i] for i in dist.strategy.input_groups[1]]
    groups, assembly = dist._exchange_groups(tp_prep)
    ks = sorted(g.k for g in groups)
    assert ks[0] == 1 and ks[-1] == 64
    # total exchanged id elements per batch row = sum over groups of
    # world * f_max * k; must be far below the padded-K_max cost
    vol = sum(g.sel.size * g.k for g in groups)
    padded_vol = 8 * max(g.f_max for g in groups) * 64 * len(groups)
    n_tp = len(tp_prep)
    # old design: every input padded to k=64 and gathered to all 8 devices
    old_vol = 8 * n_tp * 64
    assert vol < old_vol / 4, (vol, old_vol)
    # every input appears exactly once per owning slot in the assembly
    assert sorted(i for g in groups for i in g.class_inputs) == sorted(
        set(range(n_tp)))
    assert all(len(a) >= 1 for a in assembly)


def test_group_cache_hit():
    specs = [(96, 8), (50, 8)]
    dist, params = make_dist(specs)
    prep = dist._prepare_inputs([jnp.zeros((BATCH,), jnp.int32)] * 2)
    tp_prep = [prep[i] for i in dist.strategy.input_groups[1]]
    g1 = dist._exchange_groups(tp_prep)
    g2 = dist._exchange_groups(tp_prep)
    assert g1 is g2


def test_multihot_mixed_hotness_equivalence():
    # inputs of different hotness to same-width tables: correctness of the
    # group split + reassembly (the old path padded these to a common K)
    rng = np.random.RandomState(0)
    specs = [(96, 8, "sum"), (50, 8, "sum"), (70, 8, "mean"), (60, 8, "sum")]
    dist, _ = make_dist(specs, strategy="memory_balanced")
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    params = dist.set_weights(weights)
    hot = [1, 7, 3, 7]
    inputs = [jnp.asarray(rng.randint(0, specs[t][0], size=(BATCH, hot[t])))
              for t in range(4)]
    outs = dist.apply(params, inputs)
    for t, (v, w, c) in enumerate(specs):
        emb = weights[t][np.asarray(inputs[t])]       # [B, k, w]
        ref = emb.sum(1) if c == "sum" else emb.mean(1)
        np.testing.assert_allclose(np.asarray(outs[t]), ref, rtol=1e-5,
                                   atol=1e-5, err_msg=f"table {t}")


def test_exchange_padding_report_and_auto_strategy():
    """VERDICT r2 item 4: with comm_balanced (the 'auto' default for
    multi-hot models) the fixed-shape exchange moves close to the
    reference's true-splits volume — within 1.2x of true nnz on the jumbo
    synthetic config — and strictly less padding than memory_balanced."""
    from distributed_embeddings_tpu.models.synthetic import (
        SYNTHETIC_MODELS, SyntheticModel)

    mesh = create_mesh(jax.devices()[:8])

    def build(strategy):
        return SyntheticModel(SYNTHETIC_MODELS["jumbo"], mesh=mesh,
                              strategy=strategy).embedding

    auto = build("auto")
    # the auto default resolved to comm_balanced (jumbo is multi-hot)
    assert auto.strategy.strategy == "comm_balanced"
    rep_auto = auto.exchange_padding_report()
    rep_mem = build("memory_balanced").exchange_padding_report()
    # same true volume (placement-independent), less padded volume
    assert rep_auto["true_ids"] == rep_mem["true_ids"]
    assert rep_auto["exchanged_ids"] <= rep_mem["exchanged_ids"]
    assert rep_auto["ratio"] <= 1.2, rep_auto
    # report internals are consistent
    assert rep_auto["exchanged_ids"] == sum(
        g["exchanged_ids"] for g in rep_auto["groups"])
    assert all(g["f_max"] == max(g["features_per_rank"])
               for g in rep_auto["groups"])
    # byte-level wire accounting (ISSUE 5): every group carries its wire
    # formats and the id+activation byte totals; top-level sums agree
    for g in rep_auto["groups"]:
        for k in ("wire_dtype", "id_wire_dtype", "act_width", "act_bytes",
                  "act_bytes_f32", "exchanged_bytes", "true_bytes",
                  "weight_bytes_if_weighted"):
            assert k in g, k
        assert g["exchanged_bytes"] >= g["true_bytes"]
    assert rep_auto["exchanged_bytes"] == sum(
        g["exchanged_bytes"] for g in rep_auto["groups"])
    assert rep_auto["true_bytes"] == sum(
        g["true_bytes"] for g in rep_auto["groups"])
    # default wire: no compression claimed, all-f32 buckets
    assert rep_auto["act_wire_reduction"] == 1.0
    assert set(rep_auto["wire_dtypes"].values()) <= {"f32"}
    assert isinstance(rep_auto["id_narrowed_groups"], list)


def test_exchange_report_bf16_wire_bytes():
    """A bf16-wire layer's report must show the >= 1.9x activation-byte
    reduction the acceptance gate audits, per bf16 bucket and in total."""
    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"), (120, 8, "sum")]
    dist, _ = make_dist(specs, exchange_wire="bf16",
                        input_max_hotness=[4, 4, 4, 4])
    rep = dist.exchange_padding_report()
    assert all(g["wire_dtype"] == "bf16" for g in rep["groups"])
    for g in rep["groups"]:
        assert g["act_bytes_f32"] / g["act_bytes"] == pytest.approx(2.0)
    assert rep["act_wire_reduction"] >= 1.9
    # small vocabs: the id wire narrowed too, and the narrowing is
    # visible per group
    assert all(g["id_wire_dtype"] == "int16" for g in rep["groups"])
    assert rep["id_narrowed_groups"] == list(range(len(rep["groups"])))


def test_touched_rows_per_step_schema():
    """Touched-row accounting (ISSUE 6): every report group carries
    `touched_rows_per_step` (the dedup'd post-sentinel-mask ids the
    sparse update writes per step — the number the row-delta size model
    is built on) and `delta_bytes_per_step` = touched * (8 id bytes +
    4 * width); batch scales it, the bucket's total rows bound it, and
    hot-hit lanes subtract (they skip the canonical scatter)."""
    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"),
             (120, 8, "sum")]
    dist, _ = make_dist(specs, input_max_hotness=[4, 4, 4, 4])
    rep = dist.exchange_padding_report()
    for g in rep["groups"]:
        bucket = dist.plan.tp_buckets[g["bucket"]]
        assert g["touched_rows_per_step"] == g["true_ids"]  # per-sample
        assert g["delta_bytes_per_step"] == (
            g["touched_rows_per_step"] * (8 + 4 * bucket.width))
    assert rep["touched_rows_per_step"] == sum(
        g["touched_rows_per_step"] for g in rep["groups"])
    assert rep["delta_bytes_per_step"] == sum(
        g["delta_bytes_per_step"] for g in rep["groups"])

    # batch scaling caps at the bucket's total row count (dedup bound)
    rep_b = dist.exchange_padding_report(batch=10 ** 6)
    for g in rep_b["groups"]:
        bucket = dist.plan.tp_buckets[g["bucket"]]
        cap = dist.world_size * max(bucket.rows_max, 1)
        assert g["touched_rows_per_step"] == cap
    assert (rep_b["touched_rows_per_step"]
            > rep["touched_rows_per_step"])

    # hot-hit lanes are sentinel-masked: they leave the canonical
    # touched set (the delta still republishes them via the merged
    # view, but the SPARSE UPDATE's write volume is post-hot)
    hot_specs = [(500, 8, "sum")] + [(100 + i, 8) for i in range(7)]
    hot_dist, _ = make_dist(hot_specs, hot_rows=64,
                            input_max_hotness=[4] + [1] * 7)
    assert hot_dist._hot_buckets
    r0 = hot_dist.exchange_padding_report()
    r1 = hot_dist.exchange_padding_report(hot_hit_rate=0.5)
    hot_g0 = [g for g in r0["groups"]
              if g["bucket"] in hot_dist._hot_buckets]
    hot_g1 = [g for g in r1["groups"]
              if g["bucket"] in hot_dist._hot_buckets]
    assert sum(g["touched_rows_per_step"] for g in hot_g1) < sum(
        g["touched_rows_per_step"] for g in hot_g0)
    for g in hot_g1:
        assert g["touched_rows_per_step"] == g["true_ids_post_hot"]
        # ... but the BYTE model re-adds the republished hot-hit rows
        # (the delta carries their merged values), so it exceeds the
        # canonical-write term alone
        bucket = hot_dist.plan.tp_buckets[g["bucket"]]
        assert g["delta_bytes_per_step"] == (
            (g["touched_rows_per_step"]
             + min(g["hot_hit_ids"], bucket.hot_rows))
            * (8 + 4 * bucket.width))
        assert g["delta_bytes_per_step"] > (
            g["touched_rows_per_step"] * (8 + 4 * bucket.width))


def test_delta_bytes_storage_dtype_aware():
    """ISSUE 15 satellite: `delta_bytes_per_step` charges the STREAM's
    storage dtype through the ONE shared formula
    (`wire.delta_row_bytes` — 8 key bytes + width x payload itemsize +
    per-row scale), not a hardcoded f32 row; every group also reports
    its bucket's at-rest `storage_dtype`, and `DET_DELTA_DTYPE` is the
    report's default."""
    from distributed_embeddings_tpu.ops import wire as wire_ops

    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"),
             (120, 8, "sum")]
    dist, _ = make_dist(specs, input_max_hotness=[4, 4, 4, 4])
    r32 = dist.exchange_padding_report()
    r8 = dist.exchange_padding_report(delta_dtype="int8")
    assert r32["delta_dtype"] == "f32" and r8["delta_dtype"] == "int8"
    for g32, g8 in zip(r32["groups"], r8["groups"]):
        bucket = dist.plan.tp_buckets[g32["bucket"]]
        # device-resident buckets: at-rest storage stays f32 by the gate
        assert g32["storage_dtype"] == "f32"
        assert g32["delta_bytes_per_step"] == (
            g32["touched_rows_per_step"]
            * wire_ops.delta_row_bytes(bucket.width, "f32"))
        assert g8["delta_bytes_per_step"] == (
            g8["touched_rows_per_step"]
            * wire_ops.delta_row_bytes(bucket.width, "int8"))
        assert g8["delta_bytes_per_step"] < g32["delta_bytes_per_step"]
    assert r8["delta_bytes_per_step"] == sum(
        g["delta_bytes_per_step"] for g in r8["groups"])
    assert set(r32["storage_dtypes"]) == set(
        range(len(dist.plan.tp_buckets)))

    # the env default drives the report like DET_EXCHANGE_WIRE drives
    # the wire (explicit argument wins)
    import os
    os.environ["DET_DELTA_DTYPE"] = "int8"
    try:
        assert dist.exchange_padding_report()["delta_dtype"] == "int8"
        assert dist.exchange_padding_report(
            delta_dtype="f32")["delta_dtype"] == "f32"
    finally:
        del os.environ["DET_DELTA_DTYPE"]


def test_lookahead_prefetch_report_schema():
    """Overlap-window accounting (ISSUE 9): with `lookahead > 0` every
    report group carries `prefetch_patch_rows_per_step` (worst case —
    the previous step's touched rows all reappearing in the prefetched
    batch, i.e. exactly `touched_rows_per_step` with its dedup bound)
    and `prefetch_patch_bytes_per_step` (id wire + one activation slot
    at the bucket's wire per patched row — the EXTRA exchange traffic
    the overlap window adds). lookahead=0 reports zeros: the sequential
    step has no patch."""
    from distributed_embeddings_tpu.ops import wire as wire_ops

    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"),
             (120, 8, "sum")]
    dist, _ = make_dist(specs, input_max_hotness=[4, 4, 4, 4])

    r0 = dist.exchange_padding_report()
    assert r0["lookahead"] == 0
    assert r0["prefetch_patch_rows_per_step"] == 0
    assert r0["prefetch_patch_bytes_per_step"] == 0
    for g in r0["groups"]:
        assert g["prefetch_patch_rows_per_step"] == 0
        assert g["prefetch_patch_bytes_per_step"] == 0

    r1 = dist.exchange_padding_report(lookahead=1, batch=64)
    assert r1["lookahead"] == 1
    for g in r1["groups"]:
        bucket = dist.plan.tp_buckets[g["bucket"]]
        assert (g["prefetch_patch_rows_per_step"]
                == g["touched_rows_per_step"])
        id_b = wire_ops.id_wire_itemsize(bucket.id_wire_dtype)
        wire_b = wire_ops.wire_itemsize(bucket.wire_dtype)
        assert g["prefetch_patch_bytes_per_step"] == (
            g["prefetch_patch_rows_per_step"]
            * (id_b + g["act_width"] * wire_b))
    assert r1["prefetch_patch_rows_per_step"] == sum(
        g["prefetch_patch_rows_per_step"] for g in r1["groups"])
    assert r1["prefetch_patch_bytes_per_step"] == sum(
        g["prefetch_patch_bytes_per_step"] for g in r1["groups"])
    # batch scales the window until the dedup bound caps it
    r_big = dist.exchange_padding_report(lookahead=1, batch=10 ** 6)
    assert (r_big["prefetch_patch_rows_per_step"]
            >= r1["prefetch_patch_rows_per_step"])
    for g in r_big["groups"]:
        bucket = dist.plan.tp_buckets[g["bucket"]]
        assert (g["prefetch_patch_rows_per_step"]
                <= dist.world_size * max(bucket.rows_max, 1))


def test_vocab_occupancy_report_schema():
    """Capacity accounting (ISSUE 7): every report group carries
    `occupancy` (live rows / capacity rows), `slack_rows` (pre-reserved
    growth rows in the bucket) and `evictions_per_step`; a static plan
    reads fully-bound/zero, a slack plan with a live VocabManager reads
    the measured binding state."""
    specs = [(96, 8, "sum"), (50, 8, "sum"), (100, 16, "sum"),
             (120, 8, "sum")]
    dist, _ = make_dist(specs, input_max_hotness=[4, 4, 4, 4])
    rep = dist.exchange_padding_report()
    for g in rep["groups"]:
        assert g["occupancy"] == 1.0          # static vocab: all rows live
        assert g["slack_rows"] == 0
        assert g["evictions_per_step"] == 0.0
    assert rep["occupancy"] == 1.0
    assert rep["slack_rows"] == 0
    assert rep["evictions_per_step"] == 0.0

    from distributed_embeddings_tpu.vocab import VocabManager
    dist_s = DistributedEmbedding(
        [Embedding(v, w, combiner=c) for v, w, c in specs],
        mesh=create_mesh(jax.devices()[:8]),
        input_max_hotness=[4, 4, 4, 4], vocab_slack=16)
    mgr = VocabManager(dist_s, admit_threshold=1, use_native=False)
    mgr.vocabs[0].bind([10**9, 10**9 + 1, 10**9 + 2])
    mgr.maintain_cycles = 2
    mgr.vocabs[0].evictions = 4
    rep_s = dist_s.exchange_padding_report(vocab=mgr)
    assert rep_s["slack_rows"] == sum(
        b.slack_rows for b in dist_s.plan.tp_buckets)
    assert rep_s["slack_rows"] > 0
    assert 0.0 < rep_s["occupancy"] < 1.0     # mostly-unbound manager
    assert rep_s["evictions_per_step"] == pytest.approx(2.0)
    for g in rep_s["groups"]:
        assert 0.0 < g["occupancy"] <= 1.0
        assert g["slack_rows"] >= 0
        assert g["evictions_per_step"] >= 0.0


def test_one_hot_auto_resolves_basic():
    specs = [(96, 8), (50, 8), (100, 16), (120, 8)]
    dist, _ = make_dist(specs, input_max_hotness=[1, 1, 1, 1])
    assert dist.strategy.strategy == "basic"


def test_ragged_exchange_auto_policy(monkeypatch):
    """DET_RAGGED_EXCHANGE=auto (the round-4 default): per-group policy
    picks the true-splits exchange on TPU iff padded volume > 1.5x true
    ids; CPU always takes padded; '1'/'0' force."""
    import types
    specs = [(96, 8, "sum"), (50, 8, "sum")]
    dist, _ = make_dist([(v, w) for v, w, _ in specs],
                        input_max_hotness=[4, 4])

    grp_pad = types.SimpleNamespace(rank_slots=[[0], [], [], [], [], [], [],
                                                []], k=4, f_max=1, bucket=0)
    grp_tight = types.SimpleNamespace(rank_slots=[[0]] * 8, k=4, f_max=1,
                                      bucket=1)
    monkeypatch.delenv("DET_RAGGED_EXCHANGE", raising=False)
    # CPU backend: auto never takes the ragged path
    assert not dist._use_ragged_exchange(grp_pad, 8)
    # force flags work regardless of backend
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1")
    assert dist._use_ragged_exchange(grp_pad, 8)
    assert not dist._use_ragged_exchange(grp_pad, 1)   # world 1: no exchange
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "0")
    assert not dist._use_ragged_exchange(grp_pad, 8)
    # auto on a (mocked) TPU backend: ratio decides
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dist._use_ragged_exchange(grp_pad, 8)       # 8x padding
    assert not dist._use_ragged_exchange(grp_tight, 8)  # 1.0x padding


# execution-bound on the single-core CPU test host (see
# .claude/skills/verify/SKILL.md): runs in the `-m slow` tier so the
# not-slow tier-1 sweep completes inside its time budget
@pytest.mark.slow
def test_ragged_exchange_equivalence(monkeypatch):
    """DET_RAGGED_EXCHANGE=1 (true-splits exchange, CPU emulation) must be
    numerically identical to the padded exchange across mixed hotness,
    shared tables, combiners AND input forms — dense, RaggedIds and
    explicit (ids, weights) all ride the exchange (ragged/sparse inputs
    synthesize mask weights, so the weight exchange is load-bearing for
    exactly the workloads the padding problem is about). Metadata, layout
    and reassembly are the parts the CPU can prove; the op itself runs
    on the chips in `chip_smoke.py --chips 4`."""
    from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds

    rng = np.random.RandomState(17)
    specs = [(96, 8, "sum"), (50, 8, "sum"), (70, 8, "mean"), (300, 8, "sum"),
             (64, 8, "sum"), (120, 8, "mean"), (80, 8, "sum"), (45, 8, "sum")]
    table_map = list(range(8)) + [1]
    hot = [1, 7, 3, 5, 1, 2, 4, 1, 7]
    inputs = []
    for i, t in enumerate(table_map):
        v, k = specs[t][0], hot[i]
        if i % 3 == 1 and k > 1:          # RaggedIds (synthesized weights)
            lengths = rng.randint(1, k + 1, size=BATCH)
            values = rng.randint(0, v, size=int(lengths.sum()))
            splits = np.cumsum([0] + list(lengths))
            inputs.append(RaggedIds(jnp.asarray(values.astype(np.int32)),
                                    jnp.asarray(splits.astype(np.int32))))
        elif i % 3 == 2 and k > 1:        # explicit weights
            ids = rng.randint(0, v, size=(BATCH, k))
            w = np.abs(rng.rand(BATCH, k)).astype(np.float32)
            inputs.append((jnp.asarray(ids), jnp.asarray(w)))
        else:                             # dense, weightless
            inputs.append(jnp.asarray(rng.randint(0, v, size=(BATCH, k))))
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]

    outs = {}
    for ragged in (False, True):
        monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1" if ragged else "0")
        dist, _ = make_dist(specs, input_table_map=table_map,
                            input_max_hotness=hot,
                            strategy="comm_balanced")
        params = dist.set_weights(weights)
        outs[ragged] = [np.asarray(o) for o in dist.apply(params, inputs)]
    for i, (a, b) in enumerate(zip(outs[False], outs[True])):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6,
                                   err_msg=f"output {i}")


def test_ragged_exchange_sparse_train(monkeypatch):
    """Sparse train steps (residual ids flow through the exchange) under
    the ragged flag match the padded path bit-for-bit."""
    import jax
    from test_sparse_train import TinyModel
    from distributed_embeddings_tpu.training import make_sparse_train_step

    rng = np.random.RandomState(23)
    specs = [(96, 8, "sum"), (50, 8, "sum"), (70, 8, "sum"), (300, 8, "sum"),
             (64, 8, "sum"), (120, 8, "sum"), (80, 8, "sum"), (45, 8, "sum")]
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    mesh = create_mesh(jax.devices()[:8])
    results = []
    for ragged in (False, True):
        monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1" if ragged else "0")
        model = TinyModel(specs, mesh, input_max_hotness=[3] * 8)
        init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.1)
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(np.random.RandomState(7).randn(
                      sum(w for _, w, _ in specs), 1).astype(np.float32))}}
        state = init_fn(params)
        r2 = np.random.RandomState(3)
        losses = []
        for _ in range(2):
            cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 3)))
                    for v, _, _ in specs]
            labels = jnp.asarray(r2.randn(BATCH).astype(np.float32))
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)), cats,
                                          labels)
            losses.append(float(loss))
        results.append((losses,
                        model.embedding.get_weights(params["embedding"])))
    (l_pad, w_pad), (l_rag, w_rag) = results
    np.testing.assert_allclose(l_rag, l_pad, rtol=1e-6, atol=1e-7)
    for t, (a, b) in enumerate(zip(w_pad, w_rag)):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6,
                                   err_msg=f"table {t}")


@pytest.mark.skipif(not hasattr(jax.lax, "ragged_all_to_all"),
                    reason="this jax has no lax.ragged_all_to_all; the "
                           "emulation path is covered by "
                           "test_ragged_exchange_equivalence")
def test_ragged_exchange_native_lowering(monkeypatch):
    """With DET_RAGGED_NATIVE=1 the exchange lowers to the real
    lax.ragged_all_to_all op (compile needs a TPU backend — XLA:CPU has no
    lowering — but the STABLEHLO lowering is backend-checkable here)."""
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1")
    monkeypatch.setenv("DET_RAGGED_NATIVE", "1")
    specs = [(96, 8, "sum"), (50, 8, "sum"), (70, 8, "sum"), (45, 8, "sum")]
    dist, params = make_dist(specs, input_max_hotness=[3] * 4)
    inputs = [jnp.zeros((BATCH, 3), jnp.int32) for _ in specs]
    txt = jax.jit(lambda p, i: dist.apply(p, i)).lower(params,
                                                       inputs).as_text()
    assert "ragged_all_to_all" in txt, txt[:2000]




def test_exchange_report_matches_registry_gauges_after_driven_run():
    """ISSUE 11 consistency seam: the `exchange/*` gauges a driven
    `training.fit` exports must EQUAL a fresh
    `exchange_padding_report` over the same (batch, vocab, lookahead)
    arguments — touched_rows_per_step, occupancy and
    prefetch_patch_rows_per_step at both the top level and per group.
    The model is static accounting either way; what this pins is the
    WIRING (fit exporting the report's numbers, with the live manager,
    at the run's true batch size, after the tail vocab cycle)."""
    from distributed_embeddings_tpu import obs, training
    from distributed_embeddings_tpu.vocab import VocabManager
    from distributed_embeddings_tpu.obs.instrument import (
        EXCHANGE_GAUGE_FIELDS, EXCHANGE_GROUP_GAUGE_FIELDS)

    sizes = [(48, 8), (32, 8), (100, 8), (64, 8)]
    dist = DistributedEmbedding(
        [Embedding(v, w, combiner="sum") for v, w in sizes],
        mesh=create_mesh(jax.devices()[:8]),
        strategy="memory_balanced", vocab_slack=16)

    class _M:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, params, numerical, cats, labels, taps=None,
                    return_residuals=False):
            outs, res = self.embedding.apply(
                params["embedding"], cats, taps=taps,
                return_residuals=True)
            x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                axis=1)
            loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    model = _M(dist)
    mgr = VocabManager(dist, admit_threshold=1, decay=0.99,
                       use_native=False)
    rng = np.random.RandomState(3)

    def data(step):
        cats = [rng.randint(10**8, 10**8 + 40,
                            size=(16, 2)).astype(np.int64) for _ in sizes]
        return (np.zeros((16, 1), np.float32), cats,
                rng.randn(16).astype(np.float32))

    reg = obs.MetricRegistry()
    params = {"embedding": dist.init(jax.random.PRNGKey(0))}
    params, _, hist = training.fit(
        model, params, data, steps=6, optimizer="adagrad", lr=0.05,
        vocab=mgr, vocab_every=3, registry=reg, log_every=0)
    assert "metrics_error" not in hist, hist.get("metrics_error")

    gauges = reg.snapshot()["gauges"]
    rep = dist.exchange_padding_report(batch=16, vocab=mgr, lookahead=0)
    for field in EXCHANGE_GAUGE_FIELDS:
        assert gauges[f"exchange/{field}"] == pytest.approx(rep[field]), \
            field
    for gi, entry in enumerate(rep["groups"]):
        for field in EXCHANGE_GROUP_GAUGE_FIELDS:
            key = (f"exchange/{field}"
                   f"{{bucket={entry['bucket']},group={gi}}}")
            assert gauges[key] == pytest.approx(entry[field]), key
    # the manager actually moved the needle: a live binding, not the
    # static 1.0 occupancy
    assert 0.0 < gauges["exchange/occupancy"] < 1.0
    assert gauges["exchange/touched_rows_per_step"] > 0
