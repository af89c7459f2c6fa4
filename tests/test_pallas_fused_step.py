"""Fused Pallas sparse path, the full train step: the fused
strategy runs the distributed sparse train step BIT-exactly against the
XLA sort strategy (f32, interpret mode on CPU) across sgd/adagrad/adam x
padded/ragged exchange x hot-rows on/off, and composes with lookahead=1.
The kernel-level parity and the gate are `test_pallas_fused.py`'s; these
are a file of their own so that the suite's workers, which take a file
each, run the two halves side by side.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import make_sparse_train_step

from test_sparse_train import TinyModel, BATCH

SPECS = [(96, 8, "sum"), (50, 8, "mean"), (70, 8, "sum")]


# ------------------------------------------------- full train-step matrix
def _run_steps(model, optimizer, strategy, weights, head, batches):
    init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.05,
                                              strategy=strategy)
    params = {"embedding": model.embedding.set_weights(weights),
              "head": {"w": jnp.asarray(head)}}
    state = init_fn(params)
    losses = []
    for cats, labels in batches:
        params, state, loss = step_fn(params, state,
                                      jnp.zeros((BATCH, 1)), cats, labels)
        losses.append(float(loss))
    return losses, model.embedding.get_weights(params["embedding"])


def _assert_adagrad_tables_match(w_s, w_p, lr, steps):
    """Adagrad's full-step tables agree to the rounding of
    ``rsqrt(acc + eps)``, not to the bit: since `dedup_sum` sums its runs
    with a scan, the sort strategy's delta fusion holds the tail of its
    shift network
    (elementwise, so XLA:CPU fuses it in), LLVM no longer vectorises that
    loop, and jaxlib 0.9.0's scalar and vector rsqrt round one ulp apart
    on some inputs. Measured at these shapes: the dedup sums and the
    accumulators of the two strategies are bit-identical, 6 of 768 table
    elements differ by one ulp of the delta, and none with
    ``--xla_backend_optimization_level=0``. |delta| <= lr, so a step moves
    an element by at most a few eps * lr; the kernel-level parity test
    (`test_pallas_fused.py`, no fused producer) stays bit-exact for
    adagrad too."""
    eps = np.finfo(np.float32).eps
    for t, (a, b) in enumerate(zip(w_s, w_p)):
        np.testing.assert_allclose(b, a, rtol=2 * eps,
                                   atol=steps * 4 * eps * lr,
                                   err_msg=f"table {t}")


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
@pytest.mark.parametrize("ragged", [False, True])
def test_pallas_train_step_bitexact_matrix(optimizer, ragged, monkeypatch):
    """The acceptance gate: DET_SCATTER_IMPL strategy 'pallas' runs the
    full distributed sparse train step (8-device mesh, interpret-mode
    kernels) BIT-exactly vs the 'sort' strategy, across optimizers and
    the padded/ragged exchange axis (adagrad to its rsqrt's rounding:
    `_assert_adagrad_tables_match`)."""
    monkeypatch.setenv("DET_RAGGED_EXCHANGE", "1" if ragged else "0")
    rng = np.random.RandomState(17)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in SPECS]
    head = rng.randn(sum(w for _, w, _ in SPECS), 1).astype(np.float32)
    r2 = np.random.RandomState(23)
    batches = []
    for _ in range(2):
        cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 3)))
                for v, _, _ in SPECS]
        batches.append((cats, jnp.asarray(r2.randn(BATCH)
                                          .astype(np.float32))))

    def build():
        return TinyModel(SPECS, mesh, input_max_hotness=[3] * len(SPECS))

    l_p, w_p = _run_steps(build(), optimizer, "pallas", weights, head,
                          batches)
    l_s, w_s = _run_steps(build(), optimizer, "sort", weights, head,
                          batches)
    if optimizer == "adagrad":
        np.testing.assert_allclose(l_p, l_s, rtol=1e-6)
        _assert_adagrad_tables_match(w_s, w_p, lr=0.05, steps=len(batches))
    else:
        assert l_p == l_s, f"losses diverged: {l_p} vs {l_s}"
        for t, (a, b) in enumerate(zip(w_s, w_p)):
            np.testing.assert_array_equal(b, a, err_msg=f"table {t}")


def test_pallas_train_step_bitexact_hot_rows():
    """Hot-rows axis of the matrix: with a replicated hot shard admitted
    mid-run (observe -> sync), the pallas and sort strategies still agree
    (to adagrad's rsqrt rounding) — the hot shard's dense psum update is
    strategy-independent and the sentinel-masked miss stream rides the
    same dedup seam."""
    specs = [(60, 8, "sum"), (90, 8, "sum")]
    rng = np.random.RandomState(31)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in specs]
    head = rng.randn(16, 1).astype(np.float32)
    data = np.random.RandomState(41)
    batches = []
    for _ in range(4):
        cats = [jnp.asarray(np.minimum(
            data.zipf(1.3, size=(BATCH, 2)) - 1, v - 1).astype(np.int32))
            for v, _, _ in specs]
        batches.append((cats, jnp.asarray(data.randn(BATCH)
                                          .astype(np.float32))))

    def run(strategy):
        model = TinyModel(specs, mesh, hot_rows=8,
                          input_max_hotness=[2, 2])
        init_fn, step_fn = make_sparse_train_step(model, "adagrad",
                                                  lr=0.05,
                                                  strategy=strategy)
        params = {"embedding": model.embedding.set_weights(weights),
                  "head": {"w": jnp.asarray(head)}}
        state = init_fn(params)
        losses = []
        for i, (cats, labels) in enumerate(batches):
            model.embedding.observe_hot_ids(cats)
            if i == 1:      # admit mid-run: steps 2+ exercise hot hits
                p_emb, s_emb = model.embedding.sync_hot_rows(
                    params["embedding"], state["emb"], admit=True)
                params = {**params, "embedding": p_emb}
                state = {**state, "emb": s_emb}
            params, state, loss = step_fn(params, state,
                                          jnp.zeros((BATCH, 1)), cats,
                                          labels)
            losses.append(float(loss))
        p_sync, _ = model.embedding.sync_hot_rows(params["embedding"],
                                                  state["emb"])
        return losses, model.embedding.get_weights(p_sync)

    l_p, w_p = run("pallas")
    l_s, w_s = run("sort")
    np.testing.assert_allclose(l_p, l_s, rtol=1e-6)
    _assert_adagrad_tables_match(w_s, w_p, lr=0.05, steps=len(batches))


def test_pallas_composes_with_lookahead():
    """LookaheadEngine(strategy='pallas') at lookahead=1 reproduces the
    monolithic pallas step (the drain stage dispatches through the same
    fused kernels) to f32 rounding, and compile counts hold at one
    executable per stage per (plan, batch-shape).

    Not bit-for-bit: the engine differentiates the dense stage w.r.t. the
    carried activations in its own program, and jaxlib 0.9.0's XLA:CPU
    rounds that backward (the 2*(logit-y)/B * w chain) one ulp apart from
    the monolithic program's at these shapes — the tap gradients entering
    the identical drain stage already differ by 3e-8 on step 0, with
    strategy='sort' exactly as with 'pallas'. The kernels' own
    bit-exactness against the sort path is pinned by the matrix above,
    inside one program."""
    from distributed_embeddings_tpu.schedule import LookaheadEngine

    specs = [(80, 8, "sum"), (50, 8, "sum")]
    rng = np.random.RandomState(53)
    mesh = create_mesh(jax.devices()[:8])
    weights = [rng.randn(v, w).astype(np.float32) * 0.1
               for v, w, _ in specs]
    head = rng.randn(16, 1).astype(np.float32)
    r2 = np.random.RandomState(59)
    batches = []
    for _ in range(4):
        cats = [jnp.asarray(r2.randint(0, v, size=(BATCH, 2)))
                for v, _, _ in specs]
        batches.append((jnp.zeros((BATCH, 1)), cats,
                        jnp.asarray(r2.randn(BATCH).astype(np.float32))))

    from jax.sharding import NamedSharding, PartitionSpec as P

    def params_for(model):
        # replicated head, like test_schedule._build: an uncommitted
        # single-device head would re-specialize the fused step once its
        # first output comes back replicated. One per model: the steps
        # donate their params
        return {"embedding": model.embedding.set_weights(weights),
                "head": {"w": jax.device_put(jnp.asarray(head),
                                             NamedSharding(mesh, P()))}}

    m1 = TinyModel(specs, mesh)
    init_fn, step_fn = make_sparse_train_step(m1, "adagrad", lr=0.05,
                                              strategy="pallas")
    p1 = params_for(m1)
    s1 = init_fn(p1)
    mono = []
    for num, cats, lab in batches:
        p1, s1, loss = step_fn(p1, s1, num, cats, lab)
        mono.append(float(loss))

    m2 = TinyModel(specs, mesh)
    # patch_capacity=BATCH: the compile-stability configuration (the
    # default capacity overflows at these tiny zipf-free shapes and the
    # full-reprefetch fallback re-specializes — same posture as
    # test_schedule.test_compile_count_stable)
    engine = LookaheadEngine(m2, "adagrad", lr=0.05, strategy="pallas",
                             patch_capacity=BATCH)
    p2 = params_for(m2)
    s2 = engine.init(p2)
    eng = []
    for i, b in enumerate(batches):
        nxt = batches[i + 1] if i + 1 < len(batches) else None
        p2, s2, loss = engine.step(p2, s2, b, nxt)
        eng.append(float(loss))
    np.testing.assert_allclose(eng, mono, rtol=2e-6, atol=0)
    assert engine.compile_counts() == {"prefetch": 1, "fused": 1}
    for t, (a, b) in enumerate(zip(m1.embedding.get_weights(
            p1["embedding"]), m2.embedding.get_weights(p2["embedding"]))):
        np.testing.assert_allclose(b, a, rtol=0, atol=2e-7,
                                   err_msg=f"table {t}")
