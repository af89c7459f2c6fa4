"""Measure the TPU primitive costs that decide the sparse-update design.

Each measurement runs the op `iters` times inside ONE jitted computation with
a forced data dependency between iterations (the output perturbs the next
input), so XLA cannot hoist, DCE, or overlap the work away; the per-call
dispatch cost is paid once.

Sync + timing: every chain is timed slope-style with a host FETCH as the sync:
run the loop program once (t1) and twice back-to-back (t2); per-iter =
(t2 - t1) / iters. Constant overheads (dispatch, fetch RTT, queue drain)
cancel in the subtraction. See utils/profiling.fetch_sync.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from distributed_embeddings_tpu.utils.profiling import fetch_sync

RESULTS = {}
_ITERS = 10


def timed_chain(make_fn, init_state, iters=None, label="", n_rows=None):
    """make_fn: state -> state (same pytree structure/shapes)."""
    iters = iters or _ITERS

    def loop(state):
        def body(i, s):
            return make_fn(s)
        return lax.fori_loop(0, iters, body, state)

    lf = jax.jit(loop)
    out = lf(init_state)
    fetch_sync(out)                      # warm + drain the queue
    t0 = time.perf_counter()
    out = lf(init_state)
    fetch_sync(out)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = lf(init_state)
    out = lf(out)
    fetch_sync(out)
    t2 = time.perf_counter() - t0
    dt = max(t2 - t1, 1e-9) / iters
    print(f"{label}: {dt * 1e3:.3f} ms/iter "
          f"(t1={t1 * 1e3:.1f}ms t2={t2 * 1e3:.1f}ms)", flush=True)
    RESULTS[label] = {"ms": round(dt * 1e3, 3),
                      "t1_ms": round(t1 * 1e3, 1),
                      "t2_ms": round(t2 * 1e3, 1)}
    if n_rows:
        RESULTS[label]["ns_per_row"] = round(dt / n_rows * 1e9, 1)
    return dt


def main():
    global _ITERS
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    _ITERS = args.iters
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    rng = np.random.default_rng(0)

    # 1. sort_key_val: key depends on previous output
    for n in (65536, 720896, 2883584):
        keys = jnp.asarray(rng.integers(0, 25_000_000, n).astype(np.int32))
        vals = jnp.arange(n, dtype=jnp.int32)

        def step(s, n=n):
            k, v = s
            ks, vs = lax.sort_key_val(k, v)
            # perturb: rotate sorted keys so next sort is real work
            return jnp.roll(ks, 1) ^ vs, vs
        timed_chain(step, (keys, vals), label=f"sort_key_val n={n}")

    # 2. dense scatter-add into [25M, 16] fresh zeros each iter
    v = 25_000_000
    for n in (720896, 65536):
        ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
        rows = jnp.asarray(rng.standard_normal((n, 16), dtype=np.float32))

        def step(s, n=n):
            i, r = s
            buf = jnp.zeros((v, 16), jnp.float32).at[i].add(r)
            # derive next ids from the scattered buffer (forces execution)
            i2 = (i + buf[0, 0].astype(jnp.int32) + 1) % v
            return i2, r
        timed_chain(step, (ids, rows), label=f"dense-scatter-add V=25M n={n}")

    # 3. in-place scatter-add into a live table carried through the loop
    table = jnp.zeros((v, 16), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, 720896).astype(np.int32))
    rows = jnp.asarray(rng.standard_normal((720896, 16), dtype=np.float32))

    def step(s):
        t, i = s
        t = t.at[i].add(rows)
        return t, (i + 1) % v
    timed_chain(step, (table, ids), label="carried scatter-add V=25M n=720896")

    # 4. gather 65536 rows from 25M x 16
    ids1 = jnp.asarray(rng.integers(0, v, 65536).astype(np.int32))

    def step(s):
        t, i = s
        out = jnp.take(t, i, axis=0)
        return t, (i + out[0, 0].astype(jnp.int32) + 1) % v
    timed_chain(step, (table, ids1), label="gather 65536 from 25Mx16")

    # 4b. gather 720896 rows (multi-hot scale)
    def stepb(s):
        t, i = s
        out = jnp.take(t, i, axis=0)
        return t, (i + out[0, 0].astype(jnp.int32) + 1) % v
    timed_chain(stepb, (table, ids), label="gather 720896 from 25Mx16")

    # 5. dense adagrad pass over 16M x 16 (1 GiB param + 1 GiB acc)
    p = jnp.zeros((16_000_000, 16), jnp.float32)
    a = jnp.ones((16_000_000, 16), jnp.float32)

    def step5(s):
        p, a = s
        g = p * 1e-6 + 1e-3
        a = a + g * g
        p = p - 0.01 * g * lax.rsqrt(a + 1e-10)
        return p, a
    timed_chain(step5, (p, a), label="dense adagrad pass 16Mx16 (2GiB state)")

    # 6. segment_sum 720k x 16 -> 720k segments
    n = 720896
    seg = jnp.asarray(np.sort(rng.integers(0, n, n)).astype(np.int32))
    rows = jnp.asarray(rng.standard_normal((n, 16), dtype=np.float32))

    def step6(s):
        sg, r = s
        out = jax.ops.segment_sum(r, sg, num_segments=n)
        return (sg + out[0, 0].astype(jnp.int32) % 2) % n, r
    timed_chain(step6, (seg, rows), label="segment_sum n=720k w=16")

    # 7. permute 720k x 16 rows
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))

    def step7(s):
        r, pm = s
        out = jnp.take(r, pm, axis=0)
        return out, pm
    timed_chain(step7, (rows, perm), label="permute 720k x 16 rows")

    # 8. fused sparse-adagrad row update (the per-bucket backward cost;
    # either side of sparse_update.DENSE_ELEMS_MAX), both dedup strategies
    from distributed_embeddings_tpu.ops import sparse_update as su
    tbl = jnp.zeros((v, 16), jnp.float32)
    acc = jnp.full((v, 16), 0.1, jnp.float32)
    sids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    contribs = jnp.asarray(rng.standard_normal((n, 16), dtype=np.float32))
    for strat in ("sort", "dense"):
        def step8(s, strat=strat):
            t, a, i = s
            t2, a2 = su.sparse_adagrad(t, a, su.SparseRowGrad(i, contribs),
                                       0.01, strategy=strat)
            return t2, a2, (i * 1103515245 + 12345) % v
        timed_chain(step8, (tbl, acc, sids),
                    label=f"sparse_adagrad[{strat}] n=720k V=25M",
                    n_rows=n)

    print(json.dumps(RESULTS), flush=True)


if __name__ == "__main__":
    sys.exit(main())
