"""Wire-dtype axis over the sparse training path: every
optimizer x exchange path x weightedness, the bf16 exchange wire against
the f32 wire and the f32 wire against the seam-less default. A file of its
own beside `test_fuzz_sparse_train.py`, so that the suite's workers, which
take a file each, run the two sweeps side by side.
"""

import numpy as np
import pytest


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("weighted", [False, True])
def test_sparse_train_wire_axis(optimizer, ragged, weighted, monkeypatch):
    """Wire-dtype axis over the sparse training path: the bf16
    exchange wire must match the f32 wire within the documented
    tolerance across every optimizer x exchange-path x weightedness
    combination, and the f32 wire must match the seam-less default
    BIT-exactly. (adam is compared bf16-vs-f32 wire, both lazy — the
    dense-reference caveat of run_equivalence does not apply here.)"""
    import jax
    import jax.numpy as jnp
    from test_sparse_train import TinyModel, BATCH
    from distributed_embeddings_tpu.training import make_sparse_train_step
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1" if ragged else "0")
    specs = [(96, 8, "sum"), (50, 8, "sum"), (70, 8, "mean"),
             (300, 8, "sum"), (64, 8, "sum"), (120, 8, "sum"),
             (80, 8, "sum"), (45, 8, "sum")]
    rng = np.random.RandomState(31)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1 for v, w, _ in specs]
    head = rng.randn(sum(w for _, w, _ in specs), 1).astype(np.float32)
    batches = []
    r2 = np.random.RandomState(32)
    for _ in range(2):
        cats = []
        for v, _, _ in specs:
            ids = jnp.asarray(r2.randint(0, v, size=(BATCH, 3)))
            if weighted:
                cats.append((ids, jnp.asarray(
                    np.abs(r2.rand(BATCH, 3)).astype(np.float32))))
            else:
                cats.append(ids)
        batches.append((cats, jnp.asarray(r2.randn(BATCH)
                                          .astype(np.float32))))

    def run(wire):
        kw = {"input_max_hotness": [3] * len(specs)}
        if wire is not None:
            kw["exchange_wire"] = wire
        model = TinyModel(specs, mesh, **kw)
        init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.1)
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(head)}}
        state = init_fn(params)
        losses = []
        for cats, labels in batches:
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)), cats,
                                          labels)
            losses.append(float(loss))
        return losses, model.embedding.get_weights(params["embedding"])

    l_def, w_def = run(None)
    l_f32, w_f32 = run("f32")
    assert l_f32 == l_def
    for t, (a, b) in enumerate(zip(w_def, w_f32)):
        assert (a == b).all(), f"table {t} ({optimizer})"
    l_bf, w_bf = run("bf16")
    np.testing.assert_allclose(l_bf, l_f32, rtol=2e-2, atol=2e-2)
    for t, (a, b) in enumerate(zip(w_f32, w_bf)):
        np.testing.assert_allclose(b, a, rtol=3e-2, atol=3e-3,
                                   err_msg=f"table {t} ({optimizer})")
