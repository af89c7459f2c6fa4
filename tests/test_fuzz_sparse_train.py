"""Seeded randomized sweep of the sparse TRAINING path: random optimizer
x dedup strategy x placement x host-offload corners of the tapped sparse
train step. Seeds are fixed - failures reproduce.

`test_fuzz_equivalence.py` sweeps the forward and its dense gradient over
random configurations, and `test_fuzz_wire_axis.py` the wire dtype over
the sparse step; each is a file of its own so that the suite's workers,
which take a file each, run them side by side.
"""

import numpy as np
import pytest


def _offload_vs_device_sparse(specs, optimizer, dedup, placement, budget,
                              seed):
    """Sparse train steps on an offloaded model must equal the same steps
    on the all-device model (same lazy rules both sides, so ALL optimizers
    incl. adam are valid here — unlike the dense-reference comparison)."""
    import jax
    import jax.numpy as jnp
    from test_sparse_train import TinyModel, BATCH
    from distributed_embeddings_tpu.training import make_sparse_train_step
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    rng = np.random.RandomState(seed)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(s[0], s[1]).astype(np.float32) * 0.1 for s in specs]
    head = rng.randn(sum(s[1] for s in specs), 1).astype(np.float32)
    results = []
    for off in (False, True):
        model = TinyModel(specs, mesh, strategy=placement,
                          gpu_embedding_size=(budget if off else None))
        if off and not any(b.offload
                           for b in model.embedding.plan.tp_buckets):
            pytest.skip("budget did not offload anything")
        init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.05,
                                                  strategy=dedup)
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(head)}}
        state = init_fn(params)
        r2 = np.random.RandomState(seed + 1)
        losses = []
        for _ in range(3):
            cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 2)))
                    for v, _, _ in specs]
            labels = jnp.asarray(r2.randn(BATCH).astype(np.float32))
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)), cats,
                                          labels)
            losses.append(float(loss))
        results.append((losses,
                        model.embedding.get_weights(params["embedding"])))
    (l_dev, w_dev), (l_off, w_off) = results
    np.testing.assert_allclose(l_off, l_dev, rtol=1e-5, atol=1e-6)
    for t, (a, b) in enumerate(zip(w_dev, w_off)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5,
                                   err_msg=f"table {t} ({optimizer})")


@pytest.mark.parametrize("seed", range(10))
def test_random_sparse_train_equivalence(seed):
    """Randomized sparse TRAINING equivalence: optimizer x dedup strategy x
    placement x host-offload corners (the named cases in test_sparse_train /
    test_offload walk fixed configs; this walks random ones). Two modes:

      * no offload: sparse path vs dense optax — sgd/adagrad only (the
        rules that match dense EXACTLY on any id stream; lazy adam equals
        dense adam only under full row coverage, pinned by
        test_sparse_train_adam_full_coverage);
      * offload: sparse-offload vs sparse-device — all three optimizers
        (same lazy rules both sides), covering the round-3 host-adam rule.
    """
    from test_sparse_train import run_equivalence

    rng = np.random.RandomState(3000 + seed)
    n = int(rng.randint(5, 9))
    specs = []
    for _ in range(n):
        vocab = int(rng.choice([30, 90, 400, 1500, 4000]))
        width = int(rng.choice([4, 8, 16]))
        combiner = ["sum", "mean"][rng.randint(2)]
        specs.append((vocab, width, combiner))
    # scatter_impl axis: the fused pallas strategy rides the
    # sweep next to the XLA aggregation strategies — every random corner
    # that holds for 'sort' must hold for the deduped-row tile walk too
    dedup = ["sort", "dense", "auto", "pallas"][rng.randint(4)]
    placement = ["memory_balanced", "comm_balanced", "basic"][rng.randint(3)]
    offload = rng.rand() < 0.5
    try:
        if offload:
            optimizer = ["sgd", "adagrad", "adam"][rng.randint(3)]
            total = sum(s[0] * s[1] for s in specs)
            # gpu_embedding_size is a PER-DEVICE element budget: a third
            # of the fair per-rank share forces the biggest buckets out
            _offload_vs_device_sparse(specs, optimizer, dedup, placement,
                                      budget=total // 24, seed=seed)
        else:
            optimizer = ["sgd", "adagrad"][rng.randint(2)]
            kw = {"placement": placement}
            if rng.rand() < 0.4:
                kw["data_parallel_threshold"] = 256
            run_equivalence(specs, optimizer, strategy=dedup, seed=seed,
                            **kw)
    except ValueError as e:
        if "Not enough tables" in str(e):
            pytest.skip(f"seed {seed}: config unplaceable on 8 devices")
        raise
