"""Timing utilities: fetch_sync contract + slope-based chained timing.

Every benchmark in the repo routes through fetch_sync /
benchmark_chained: a host fetch cannot complete before the data exists,
and work that is never fetched may never execute. The tests pin the API
contract on CPU.
"""

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.utils import profiling


def test_fetch_sync_handles_leaf_zoo():
    out = {
        "f32": jnp.ones((4, 4)),
        "bf16": jnp.ones((2,), jnp.bfloat16),
        "int": jnp.arange(3),
        "bool": jnp.ones((2,), bool),          # fetched as 1.0
        "empty": jnp.zeros((0, 8)),            # skipped
        "scalar": jnp.float32(2.5),
        "none": None,                          # not an array leaf
    }
    total = profiling.fetch_sync(out)
    # 1.0 (f32[0]) + 1.0 (bf16[0]) + 0 (int[0]) + 1.0 (bool[0]) + 2.5
    assert abs(total - 5.5) < 1e-6


def test_fetch_sync_no_fetchable_leaves_still_syncs():
    # ADVICE r3: an output of only empty/non-array leaves must not silently
    # time dispatch-only; fetch_sync falls back to block_until_ready and
    # returns 0.0 without raising
    assert profiling.fetch_sync({"e": jnp.zeros((0,)), "n": None}) == 0.0
    assert profiling.fetch_sync(None) == 0.0


def test_benchmark_chained_measures_real_work():
    def step(s):
        x, acc = s
        y = x @ x
        return y / (jnp.max(jnp.abs(y)) + 1.0), acc + y[0, 0]

    x = jnp.asarray(np.random.RandomState(0).randn(128, 128),
                    dtype=jnp.float32)
    res = profiling.benchmark_chained(step, (x, jnp.float32(0)), iters=4)
    assert res.mean_s > 0
    assert res.compile_s > res.mean_s          # compile dominates tiny work
    assert np.isfinite(res.mean_s)


def test_benchmark_fetches_each_iteration():
    calls = []

    def fn(x):
        calls.append(1)
        return x + 1.0

    res = profiling.benchmark(fn, jnp.zeros((2, 2)), iters=3, warmup=1)
    assert len(calls) == 1 + 1 + 3             # compile + warmup + iters
    assert res.iters == 3 and res.min_s > 0


def test_benchmark_train_steps_threads_donated_state():
    """The state returned by call i is what call i+1 receives, so a
    donating step works; batches rotate; compile + warmup + iters calls."""
    seen = []

    @jax.jit
    def core(params, opt_state, x):
        return params + x, opt_state + 1, jnp.sum(params)

    step = jax.jit(core, donate_argnums=(0, 1))

    def step_fn(params, opt_state, x):
        seen.append(int(opt_state))
        return step(params, opt_state, x)

    batches = [(jnp.float32(1.0),), (jnp.float32(10.0),)]
    res, params, opt_state = profiling.benchmark_train_steps(
        step_fn, jnp.zeros((4,)), jnp.int32(0), batches, iters=3, warmup=2)
    assert seen == [0, 1, 2, 3, 4, 5]          # threaded, never reused
    assert int(opt_state) == 6 and res.iters == 3 and res.min_s > 0
    # compile: batch 0; warmup: 0, 1; iters: 0, 1, 0
    np.testing.assert_allclose(np.asarray(params), 1 + 1 + 10 + 1 + 10 + 1)
