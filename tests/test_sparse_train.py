"""Sparse (tapped) train step vs dense optax train step: full equivalence.

The reference's contract: training through its sparse backward + IndexedSlices
optimizer apply equals dense-gradient training (reference tests compare
post-optimizer weights, dist_model_parallel_test.py:280-291). Here: the tapped
sparse path (make_sparse_train_step) must reproduce the dense optax path's
losses and final weights on the same model, across optimizers, parallelism
modes and combiners — on the 8-virtual-CPU mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import make_sparse_train_step

BATCH = 16


class TinyModel:
    """Embeddings -> concat -> linear head; the minimal model shape
    make_sparse_train_step expects (.embedding + params['embedding'])."""

    def __init__(self, specs, mesh, input_table_map=None, **kw):
        self.embedding = DistributedEmbedding(
            [Embedding(v, w, combiner=(s[2] if len(s) > 2 else None))
             for s, (v, w) in zip(specs, [(s[0], s[1]) for s in specs])],
            mesh=mesh, input_table_map=input_table_map, **kw)
        self.specs = specs

    def init_head(self, key, n_outputs, widths):
        return {"w": jax.random.normal(key, (sum(widths), 1)) * 0.1}

    def apply(self, params, numerical, cats, taps=None,
              return_residuals=False):
        res = None
        if taps is not None or return_residuals:
            outs, res = self.embedding(params["embedding"], list(cats),
                                       taps=taps, return_residuals=True)
        else:
            outs = self.embedding(params["embedding"], list(cats))
        outs = [o.reshape(o.shape[0], -1) for o in outs]
        x = jnp.concatenate(outs, axis=1).astype(jnp.float32)
        out = x @ params["head"]["w"]
        return (out, res) if return_residuals else out

    def loss_fn(self, params, numerical, cats, labels, taps=None,
                return_residuals=False):
        out = self.apply(params, numerical, cats, taps=taps,
                         return_residuals=return_residuals)
        logits, res = out if return_residuals else (out, None)
        loss = jnp.mean((logits[:, 0] - labels.reshape(-1)) ** 2)
        return (loss, res) if return_residuals else loss


def run_equivalence(specs, optimizer, input_table_map=None, steps=3,
                    strategy="sort", seed=0, lr=0.05, rtol=5e-5, atol=5e-5,
                    inputs_fn=None, placement=None, **dist_kwargs):
    # `strategy` is the sparse DEDUP strategy; `placement` (if given) is the
    # planner strategy, forwarded as DistributedEmbedding(strategy=...)
    if placement is not None:
        dist_kwargs["strategy"] = placement
    rng = np.random.RandomState(seed)
    mesh = create_mesh(jax.devices()[:8])
    table_map = (list(input_table_map) if input_table_map
                 else list(range(len(specs))))

    def build():
        return TinyModel(specs, mesh, input_table_map=input_table_map,
                         **dist_kwargs)

    model = build()
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]
    emb_params = model.embedding.set_weights(weights)
    widths = []
    for i, t in enumerate(table_map):
        s = specs[t]
        k = 2 + (i % 3)
        widths.append(s[1] * (k if len(s) > 2 and s[2] is None else 1)
                      if False else s[1])
    # widths: combiner None multihot flattens; keep hotness-1 for None tables
    head = {"w": jnp.asarray(rng.randn(sum(widths), 1).astype(np.float32))}
    params = {"embedding": emb_params, "head": head}

    batches = []
    for _ in range(steps):
        cats = []
        for i, t in enumerate(table_map):
            s = specs[t]
            comb = s[2] if len(s) > 2 else None
            if inputs_fn is not None:
                cats.append(inputs_fn(rng, i, s))
            elif comb is None:
                cats.append(jnp.asarray(rng.randint(0, s[0], size=(BATCH,))))
            else:
                cats.append(jnp.asarray(
                    rng.randint(0, s[0], size=(BATCH, 2 + (i % 3)))))
        labels = jnp.asarray(rng.randn(BATCH).astype(np.float32))
        batches.append((jnp.zeros((BATCH, 1)), cats, labels))

    # --- dense reference: plain value_and_grad + optax over everything
    dense_opt = {"sgd": optax.sgd(lr), "adagrad": optax.adagrad(lr),
                 "adam": optax.adam(lr)}[optimizer]
    # one program for the whole step, not one per operation
    @jax.jit
    def dense_step(dparams, dstate, num, cats, labels):
        loss, grads = jax.value_and_grad(model.loss_fn)(dparams, num, cats,
                                                        labels)
        upd, dstate = dense_opt.update(grads, dstate, dparams)
        return optax.apply_updates(dparams, upd), dstate, loss

    dparams = jax.tree.map(lambda x: x, params)
    dstate = dense_opt.init(dparams)
    dlosses = []
    for num, cats, labels in batches:
        dparams, dstate, loss = dense_step(dparams, dstate, num, cats,
                                           labels)
        dlosses.append(float(loss))

    # --- sparse tapped path
    model2 = build()
    init_fn, step_fn = make_sparse_train_step(model2, optimizer, lr=lr,
                                              strategy=strategy)
    sparams = {"embedding": model2.embedding.set_weights(weights),
               "head": jax.tree.map(lambda x: x, head)}
    sstate = init_fn(sparams)
    slosses = []
    for num, cats, labels in batches:
        sparams, sstate, loss = step_fn(sparams, sstate, num, cats, labels)
        slosses.append(float(loss))

    np.testing.assert_allclose(slosses, dlosses, rtol=1e-4, atol=1e-5)
    got = model2.embedding.get_weights(sparams["embedding"])
    want = model.embedding.get_weights(dparams["embedding"])
    for t, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_allclose(b, a, rtol=rtol, atol=atol,
                                   err_msg=f"table {t} (opt={optimizer})")
    np.testing.assert_allclose(np.asarray(sparams["head"]["w"]),
                               np.asarray(dparams["head"]["w"]),
                               rtol=rtol, atol=atol)


SPECS_BASIC = [(40, 4), (60, 8), (30, 4), (50, 8), (25, 4), (70, 8),
               (45, 4), (35, 8)]


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_sparse_train_basic(optimizer):
    run_equivalence(SPECS_BASIC, optimizer)


def test_sparse_train_adam_full_coverage():
    """Lazy sparse Adam == dense Adam only when every row is touched every
    step (untouched-row momentum decay is skipped by design — the standard
    sparse-Adam compromise). Cover every row each batch."""
    specs = [(8, 4, "sum"), (12, 8, "sum"), (6, 4, "sum"), (10, 8, "sum"),
             (8, 4, "sum"), (12, 8, "sum"), (8, 4, "sum"), (8, 8, "sum")]

    def inputs_fn(rng, i, s):
        v = s[0]
        k = max(2, -(-v // BATCH) + 1)
        ids = np.concatenate([np.arange(v), rng.randint(0, v, BATCH * k - v)])
        rng.shuffle(ids)
        return jnp.asarray(ids.reshape(BATCH, k))

    run_equivalence(specs, "adam", inputs_fn=inputs_fn, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("strategy", ["sort", "dense"])
def test_sparse_train_strategies(strategy):
    run_equivalence(SPECS_BASIC, "adagrad", strategy=strategy)


def test_sparse_train_multihot_combiners():
    specs = [(40, 4, "sum"), (60, 8, "mean"), (30, 4, "sum"), (50, 8, "mean"),
             (25, 4, "sum"), (70, 8, "sum"), (45, 4, "mean"), (35, 8, "sum")]
    run_equivalence(specs, "adagrad")


def test_sparse_train_shared_tables():
    specs = [(40, 4, "sum"), (60, 8, "sum"), (30, 4, "sum"), (50, 8, "sum"),
             (25, 4, "sum"), (70, 8, "sum"), (45, 4, "sum"), (35, 8, "sum")]
    run_equivalence(specs, "adagrad",
                    input_table_map=[0, 1, 2, 3, 4, 5, 6, 7, 0, 3])


def test_sparse_train_row_slice():
    specs = [(512, 8, "sum"), (40, 8, "sum"), (300, 8, "mean"), (64, 8, "sum"),
             (128, 8, "sum"), (96, 8, "sum"), (80, 8, "sum"), (72, 8, "sum")]
    run_equivalence(specs, "adagrad", row_slice_threshold=2000, rtol=2e-4,
                    atol=2e-4)


def test_sparse_train_hybrid_dp_col_row():
    specs = [(512, 8, "sum"), (300, 8, "sum"), (8, 4), (6, 4),
             (100, 8, "sum"), (90, 8, "sum"), (80, 8, "sum"), (70, 8, "sum"),
             (60, 8, "sum"), (50, 8, "sum")]
    run_equivalence(specs, "adagrad", row_slice_threshold=2000,
                    data_parallel_threshold=64, rtol=2e-4, atol=2e-4)


def test_sparse_train_mp_input_matches_dp():
    """dp_input=False sparse training == dp_input=True sparse training on
    the same global data (the mp loader just pre-shards by feature)."""
    specs = [(40, 4, "sum"), (60, 8, "sum"), (30, 4, "sum"), (50, 8, "sum"),
             (25, 4, "sum"), (70, 8, "sum"), (45, 4, "sum"), (35, 8, "sum")]
    rng = np.random.RandomState(11)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]
    batches = []
    for _ in range(3):
        cats = [jnp.asarray(rng.randint(0, s[0], size=(BATCH, 2)))
                for s in specs]
        labels = jnp.asarray(rng.randn(BATCH).astype(np.float32))
        batches.append((cats, labels))

    results = []
    for dp_input in (True, False):
        model = TinyModel(specs, mesh, dp_input=dp_input)
        strat = model.embedding.strategy

        def to_inputs(cats, dp=dp_input):
            if dp:
                return cats
            return [[cats[strat.input_groups[1][pos]] for pos in rank_ids]
                    for rank_ids in strat.input_ids_list]

        init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.05,
                                                  strategy="sort")
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(np.random.RandomState(7).randn(
                      sum(s[1] for s in specs), 1).astype(np.float32))}}
        state = init_fn(params)
        losses = []
        for cats, labels in batches:
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)),
                                          to_inputs(cats), labels)
            losses.append(float(loss))
        results.append((losses,
                        model.embedding.get_weights(params["embedding"])))

    (l_dp, w_dp), (l_mp, w_mp) = results
    np.testing.assert_allclose(l_mp, l_dp, rtol=1e-5, atol=1e-6)
    for t, (a, b) in enumerate(zip(w_dp, w_mp)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5,
                                   err_msg=f"table {t}")


def test_sparse_train_ragged_inputs():
    """RaggedIds flow through make_taps / residuals / sparse updates (the
    padded slots must contribute nothing)."""
    from distributed_embeddings_tpu.ops.embedding_ops import RaggedIds

    rng_r = np.random.RandomState(55)

    def inputs_fn(rng, i, s):
        lengths = rng_r.randint(1, 5, size=BATCH)
        values = rng_r.randint(0, s[0], size=int(lengths.sum()))
        splits = np.cumsum([0] + list(lengths)).astype(np.int32)
        return RaggedIds(jnp.asarray(values.astype(np.int32)),
                         jnp.asarray(splits))

    specs = [(40, 4, "sum"), (60, 8, "mean"), (30, 4, "sum"), (50, 8, "sum"),
             (25, 4, "sum"), (70, 8, "mean"), (45, 4, "sum"), (35, 8, "sum")]
    run_equivalence(specs, "adagrad", inputs_fn=inputs_fn,
                    input_max_hotness=[6] * 8)


def test_sparse_train_weighted_inputs():
    rng_w = np.random.RandomState(99)

    def inputs_fn(rng, i, s):
        k = 2 + (i % 3)
        ids = jnp.asarray(rng.randint(0, s[0], size=(BATCH, k)))
        w = jnp.asarray(np.abs(rng_w.rand(BATCH, k)).astype(np.float32))
        return (ids, w)

    specs = [(40, 4, "sum"), (60, 8, "mean"), (30, 4, "sum"), (50, 8, "mean"),
             (25, 4, "sum"), (70, 8, "sum"), (45, 4, "sum"), (35, 8, "mean")]
    run_equivalence(specs, "adagrad", inputs_fn=inputs_fn)


def test_sparse_step_hlo_scatter_promises():
    """The lowered train step must carry the scatter promises the round-3
    hardware data demands (XLA's duplicate-safe scatter measured at
    100-280 ns/row): both row-update scatters say unique_indices=true, and
    they are the step's ONLY scatters — dedup sums its sorted runs with a
    scan (ISSUE 31), so it holds no segment-sum or rep-build scatter."""
    import re
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.training import make_sparse_train_step

    class _Tiny:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, p, numerical, cats, labels, taps=None,
                    return_residuals=False):
            out = self.embedding(p["embedding"], list(cats), taps=taps,
                                 return_residuals=return_residuals)
            outs, res = out if return_residuals else (out, None)
            x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                axis=1)
            loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    def lower_text():
        # big-vocab single bucket so the auto strategy takes the sort path;
        # abstract avals only — lowering needs shapes, not a 1 GiB table
        emb = DistributedEmbedding([Embedding(30_000_000, 8)], mesh=None)
        model = _Tiny(emb)
        init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.01)
        params = jax.eval_shape(
            lambda: {"embedding": emb.init(jax.random.PRNGKey(0))})
        state = jax.eval_shape(init_fn, params)
        num = jax.ShapeDtypeStruct((8, 1), jnp.float32)
        cats = [jax.ShapeDtypeStruct((8,), jnp.int32)]
        lab = jax.ShapeDtypeStruct((8,), jnp.float32)
        return jax.jit(step_fn).lower(params, state, num, cats, lab).as_text()

    txt = lower_text()
    assert len(re.findall(r'"stablehlo.scatter"', txt)) == 2, (
        "only the accumulator's and the table's row updates scatter")
    assert len(re.findall(r"unique_indices\s*=\s*true", txt)) == 2
