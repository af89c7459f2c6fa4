"""Sort folding (ISSUE 2): folded vs unfolded tapped steps are bit-exact,
and the compiled tapped step carries at most one sort op per
(bucket, hotness) exchange group.

The fold threads the forward's canonical id sort through
TapResiduals.tp_sort/row_sort into the sparse update (dedup_sum /
sparse_sgd-adagrad-adam / the tiled kernels), mirroring the reference CUDA
backward's reuse of forward-sorted ids (embedding_lookup_kernels.cu:706-773).
Because the folded and fresh sorts run the identical lax.sort_key_val over
identical canonical keys, every downstream value is the same ARRAY — the
parity assertions here are exact equality, not allclose.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import make_sparse_train_step

BATCH = 16


class _TapModel:
    def __init__(self, specs, mesh, **kw):
        self.embedding = DistributedEmbedding(
            [Embedding(v, w, combiner=(s[2] if len(s) > 2 else None))
             for s, (v, w) in zip(specs, [(s[0], s[1]) for s in specs])],
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


SPECS = [(40, 4, "sum"), (60, 8, "sum"), (30, 4, "sum"), (50, 8, "sum"),
         (25, 4, "sum"), (70, 8, "sum"), (45, 4, "sum"), (35, 8, "sum")]


def _run(optimizer, strategy, fold, specs=SPECS, steps=2, seed=0, **kw):
    rng = np.random.RandomState(seed)
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(specs, mesh, **kw)
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]
    params = {"embedding": model.embedding.set_weights(weights)}
    init_fn, step_fn = make_sparse_train_step(
        model, optimizer, lr=0.05, strategy=strategy, fold_sort=fold)
    state = init_fn(params)
    losses = []
    data = np.random.RandomState(7)
    for _ in range(steps):
        cats = [jnp.asarray(data.randint(0, s[0], size=(BATCH, 2)))
                for s in specs]
        labels = jnp.asarray(data.randn(BATCH).astype(np.float32))
        params, state, loss = step_fn(params, state, jnp.zeros((BATCH, 1)),
                                      cats, labels)
        losses.append(float(loss))
    return losses, model.embedding.get_weights(params["embedding"])


def _assert_bitexact(optimizer, strategy, **kw):
    lf, wf = _run(optimizer, strategy, True, **kw)
    lu, wu = _run(optimizer, strategy, False, **kw)
    assert lf == lu, f"losses diverged: {lf} vs {lu}"
    for t, (a, b) in enumerate(zip(wf, wu)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"table {t} ({optimizer}/{strategy})")


@pytest.mark.parametrize("strategy", ["sort", "tiled"])
def test_fold_parity_adagrad(strategy):
    _assert_bitexact("adagrad", strategy)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("strategy", ["sort", "tiled"])
def test_fold_parity_optimizers(optimizer, strategy):
    _assert_bitexact(optimizer, strategy)


def test_fold_parity_row_slice():
    """Row-sliced tables fold too (single-input tables; the sentinel-masked
    id stream is sorted once in the forward)."""
    specs = [(512, 8, "sum"), (40, 8, "sum"), (300, 8, "mean"),
             (64, 8, "sum"), (128, 8, "sum"), (96, 8, "sum"),
             (80, 8, "sum"), (72, 8, "sum")]
    _assert_bitexact("adagrad", "sort", specs=specs, row_slice_threshold=2000)


def test_fold_off_without_scope():
    """residual_sort defaults keep the change strictly additive: a tapped
    forward OUTSIDE residual_sort_scope produces no sort artifacts, and
    sparse_update accepts such residuals unchanged."""
    mesh = create_mesh(jax.devices()[:8])
    model = _TapModel(SPECS, mesh)
    rng = np.random.RandomState(3)
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in SPECS]
    params = model.embedding.set_weights(weights)
    cats = [jnp.asarray(rng.randint(0, s[0], size=(BATCH, 2)))
            for s in SPECS]
    _, res = model.embedding(params, cats, return_residuals=True)
    assert res.tp_sort is not None and all(s is None for s in res.tp_sort)
    with model.embedding.residual_sort_scope(("adagrad", "sort")):
        _, res2 = model.embedding(params, cats, return_residuals=True)
    assert any(s is not None for s in res2.tp_sort)
    for s in res2.tp_sort:
        if s is not None:
            assert s.sid.dtype == jnp.int32 and s.seg_start.dtype == bool


def _lower_sorts(strategy, fold, lookup_path=None, optimizer="adagrad",
                 monkeypatch=None):
    from tests import conftest  # noqa: F401 - platform already forced
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "det_hlo_audit", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools",
            "hlo_audit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.audit_tapped_step(strategy=strategy, fold=fold,
                                 lookup_path=lookup_path,
                                 optimizer=optimizer)


@pytest.mark.parametrize("strategy", ["sort", "tiled"])
def test_tapped_step_hlo_one_sort_per_group(strategy):
    """Acceptance gate: the compiled tapped step (default forward) carries
    <= 1 sort op per exchange group for both the 'sort' (XLA dedup) and
    'tiled' (Pallas kernel) aggregation strategies. Companion to
    test_tiled_step_hlo_scatter_free."""
    rec = _lower_sorts(strategy, fold=True)
    assert rec["hlo_sort"] <= rec["n_exchange_groups"], rec


def test_tapped_step_hlo_tiled_forward_two_sorts():
    """With the tiled forward gather active (DET_LOOKUP_PATH=tiled) the
    folded step carries exactly the forward sort + its inverse-permute
    sort (2 per group, down from 3 unfolded): the unpermute's second sort
    is irreducible without reintroducing a scatter (the round-3
    ~100 ns/row lowering the tiled family exists to avoid)."""
    folded = _lower_sorts("tiled", fold=True, lookup_path="tiled")
    unfolded = _lower_sorts("tiled", fold=False, lookup_path="tiled")
    assert folded["hlo_sort"] <= 2 * folded["n_exchange_groups"], folded
    assert unfolded["hlo_sort"] >= folded["hlo_sort"] + 1, (folded, unfolded)
