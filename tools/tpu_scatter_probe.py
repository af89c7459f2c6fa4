"""Measure how much XLA:TPU scatter/gather cost drops when the indices are
promised unique and/or sorted.

Round-3 prims data: row scatter-add is THE bottleneck on this chip
(~100-280 ns/row — a 720k-row update costs 74 ms while the same bytes
stream in ~0.2 ms), and the sparse-update sort path scatters with ids that
ARE sorted+unique post-dedup but never says so, forcing XLA's conservative
duplicate-safe lowering. This probe times every (flags x shape) combination
the framework's update paths use, chained + fetch-synced (see
utils/profiling.fetch_sync for why).

Usage: python tools/tpu_scatter_probe.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

RESULTS = {}


def timed_chain(step, state, iters=8, label=""):
    def loop(s):
        return lax.fori_loop(0, iters, lambda i, x: step(x), s)

    lf = jax.jit(loop)
    out = lf(state)
    _fetch(out)
    t0 = time.perf_counter()
    out = lf(state)
    _fetch(out)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = lf(state)
    out = lf(out)
    _fetch(out)
    t2 = time.perf_counter() - t0
    dt = max(t2 - t1, 1e-9) / iters
    print(f"{label}: {dt * 1e3:.3f} ms/iter", flush=True)
    RESULTS[label] = round(dt * 1e3, 3)
    return dt


def _fetch(out):
    total = 0.0
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "dtype"):
            total += float(jnp.sum(leaf.astype(jnp.float32)))
    return total


def unique_sorted_ids(rng, n, v):
    """Strictly increasing in-bounds ids: sorted sample + arange offset."""
    return np.sort(rng.integers(0, v - n, n).astype(np.int64)) + np.arange(n)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    rng = np.random.default_rng(0)

    # --- width 16 (tiny-model class): V=25M, n=720896 rows
    for (v, n, w) in ((25_000_000, 720_896, 16), (2_600_000, 1_703_936, 128)):
        tag = f"V={v//1000}k n={n} w={w}"
        dup_ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
        uniq = jnp.asarray(unique_sorted_ids(rng, n, v).astype(np.int32))
        rows = jnp.asarray(rng.standard_normal((n, w), dtype=np.float32))
        table = jnp.zeros((v, w), jnp.float32)

        def mk_scatter(ids, unique, sorted_):
            def step(s):
                t, r = s
                t = t.at[ids].add(r, mode="drop", unique_indices=unique,
                                  indices_are_sorted=sorted_)
                # chain: next iteration's rows depend on this scatter
                return t, r + t[0, :1] * 0
            return step

        timed_chain(mk_scatter(dup_ids, False, False), (table, rows),
                    label=f"scatter-add dupes noflags {tag}")
        timed_chain(mk_scatter(uniq, False, False), (table, rows),
                    label=f"scatter-add uniqsorted noflags {tag}")
        timed_chain(mk_scatter(uniq, True, False), (table, rows),
                    label=f"scatter-add uniqsorted unique {tag}")
        timed_chain(mk_scatter(uniq, True, True), (table, rows),
                    label=f"scatter-add uniqsorted unique+sorted {tag}")

        def mk_gather(ids, unique, sorted_):
            def step(s):
                t, i = s
                out = jnp.take(t, i, axis=0, mode="clip",
                               unique_indices=unique,
                               indices_are_sorted=sorted_)
                return t, (i + out[0, 0].astype(jnp.int32) % 2)
            return step

        timed_chain(mk_gather(dup_ids, False, False), (table, dup_ids),
                    label=f"gather dupes noflags {tag}")
        timed_chain(mk_gather(uniq, True, True), (table, uniq),
                    label=f"gather uniqsorted unique+sorted {tag}")
        # sorted-with-duplicates gather: the shape a sorted-lookup forward
        # would issue (sort ids once, gather with locality, inverse-permute)
        sdup = jnp.sort(dup_ids)
        timed_chain(mk_gather(sdup, False, True), (table, sdup),
                    label=f"gather dupes sorted {tag}")

        # composite: sort + sorted-gather + inverse-permute vs the raw
        # unsorted gather above — the end-to-end decision for a
        # sorted-lookup forward path. Inverse permute is SCATTER-FREE
        # (argsort + take): an .at[perm].set would reintroduce the
        # 106 ns/row scatter this path exists to avoid
        def composite(s):
            t, i = s
            iota = jnp.arange(i.shape[0], dtype=jnp.int32)
            sid, perm = lax.sort_key_val(i, iota)
            inv = lax.sort_key_val(perm, iota)[1]
            rows_srt = jnp.take(t, sid, axis=0, mode="clip",
                                indices_are_sorted=True)
            out = jnp.take(rows_srt, inv, axis=0)
            return t, (i + out[0, 0].astype(jnp.int32) % 2)

        timed_chain(composite, (table, dup_ids),
                    label=f"sort+sortedgather+unperm {tag}")
        del table, rows, dup_ids, uniq, sdup

    # segment aggregation alternatives: jax.ops.segment_sum(sorted) measured
    # 45 ns/row in round-3a (it is a sorted-dupes scatter underneath); a
    # cumsum-difference formulation is pure streaming if XLA lowers cumsum
    # at bandwidth (cost: ~N*eps precision, acceptable as an opt-in)
    for w in (16, 128):
        n = 720_896
        seg_ids = jnp.asarray(np.sort(rng.integers(0, n, n)).astype(np.int32))
        rows = jnp.asarray(rng.standard_normal((n, w), dtype=np.float32))
        starts = jnp.concatenate([jnp.ones((1,), bool),
                                  seg_ids[1:] != seg_ids[:-1]])
        seg = jnp.cumsum(starts.astype(jnp.int32)) - 1

        def seg_scatter(s):
            sg, r = s
            out = jax.ops.segment_sum(r, sg, num_segments=n,
                                      indices_are_sorted=True)
            return (sg + out[0, 0].astype(jnp.int32) % 2) % n, r

        timed_chain(seg_scatter, (seg, rows),
                    label=f"segment_sum scatter n=720k w={w}")

        sid_sorted = jnp.sort(jnp.asarray(
            rng.integers(0, n, n).astype(np.int32)))

        def seg_cumsum(s):
            # scatter-FREE per-segment totals over sorted ids: cumsum +
            # cummax + one sorted gather; totals land at each segment's
            # END row (other rows zero), which downstream unique-promise
            # scatters consume just as well as a compacted layout
            sid, r = s
            iota = jnp.arange(n, dtype=jnp.int32)
            is_start = jnp.concatenate(
                [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
            is_end = jnp.concatenate(
                [sid[1:] != sid[:-1], jnp.ones((1,), bool)])
            p = jnp.cumsum(r, axis=0)
            begin = lax.cummax(jnp.where(is_start, iota, -1))
            p_prev = jnp.where(
                (begin > 0)[:, None],
                jnp.take(p, jnp.maximum(begin - 1, 0), axis=0,
                         indices_are_sorted=True), 0.0)
            sums_at_end = jnp.where(is_end[:, None], p - p_prev, 0.0)
            return (sid + sums_at_end[0, 0].astype(jnp.int32) % 2) % n, r

        timed_chain(seg_cumsum, (sid_sorted, rows),
                    label=f"segment_sum cumsum-scatterfree n=720k w={w}")
        del rows

    # the real update path, now carrying the unique+sorted promises — direct
    # comparison against round-3a prims (sort 200.2ms / dense 93.7ms)
    from distributed_embeddings_tpu.ops import sparse_update as su
    v, n = 25_000_000, 720_896
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
        timed_chain(step8, (tbl, acc, sids), iters=6,
                    label=f"sparse_adagrad[{strat}]+flags n=720k V=25M")

    # Pallas RMW scatter kernel vs the flagged XLA scatter (width-128 f32
    # rows only: see pallas_lookup.check_row_dma)
    try:
        from distributed_embeddings_tpu.ops import pallas_scatter as ps
        n_u = 655_360                       # unique sorted rows
        uniq2 = jnp.asarray(unique_sorted_ids(rng, n_u, v).astype(np.int32))
        deltas = jnp.asarray(
            rng.standard_normal((n_u, 16), dtype=np.float32))
        # correctness first at a small shape, compiled
        small_ids = jnp.asarray(
            np.sort(rng.choice(10_000, 512, replace=False)).astype(np.int32))
        small_d = jnp.asarray(
            rng.standard_normal((512, 16), dtype=np.float32))
        small_t = jnp.zeros((10_000, 16), jnp.float32)
        got = ps.scatter_add_sorted_unique(small_t, small_ids, small_d,
                                           interpret=False)
        want = small_t.at[small_ids].add(small_d, mode="drop")
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5

        def step_rmw(s):
            t, d = s
            t = ps.scatter_add_sorted_unique(t, uniq2, d, interpret=False)
            return t, d + t[0, :1] * 0

        timed_chain(step_rmw, (tbl, deltas), iters=6,
                    label=f"pallas_rmw_scatter n={n_u} V=25M w=16")

        def step_fused(s):
            t, a, d = s
            t, a = ps.adagrad_rows_sorted_unique(t, a, uniq2, d, 0.01,
                                                 interpret=False)
            return t, a, d + t[0, :1] * 0

        timed_chain(step_fused, (tbl, acc, deltas), iters=6,
                    label=f"pallas_fused_adagrad n={n_u} V=25M w=16")
    except Exception as e:  # noqa: BLE001 - toolchain may reject the kernel
        RESULTS["pallas_rmw_scatter"] = f"FAIL {str(e)[:200]}"
        print(f"pallas_rmw_scatter: FAIL {str(e)[:300]}", flush=True)

    # round-4 tiled one-hot-matmul kernels (ops/pallas_tiled.py): BlockSpec
    # streams only — the form this toolchain compiles (the one-hot lookup
    # kernel compiles; the DMA kernels do not). Timed at the two real
    # workload shapes with duplicate ids straight in (no dedup pass), plus
    # a (tile, chunk) sweep on the tiny-class shape.
    try:
        from distributed_embeddings_tpu.ops import pallas_tiled as ptl
        # compiled correctness at a small shape first
        small_ids = jnp.asarray(rng.integers(0, 10_000, 4096)
                                .astype(np.int32))
        small_d = jnp.asarray(
            rng.standard_normal((4096, 16), dtype=np.float32))
        small_t = jnp.asarray(
            rng.standard_normal((10_000, 16), dtype=np.float32))
        small_a = jnp.full((10_000, 16), 0.1, jnp.float32)
        got_t, got_a = ptl.tiled_adagrad(small_t, small_a, small_ids,
                                         small_d, 0.01, interpret=False)
        want_t, want_a = su.sparse_adagrad(
            small_t, small_a, su.SparseRowGrad(small_ids, small_d), 0.01,
            strategy="sort")
        err = float(jnp.max(jnp.abs(got_t - want_t)))
        assert err < 1e-3, f"tiled_adagrad mismatch {err}"
        RESULTS["tiled_correctness"] = "PASS"
        print("tiled correctness: PASS", flush=True)

        for (v2, n2, w2) in ((25_000_000, 720_896, 16),
                             (2_600_000, 1_703_936, 128)):
            tbl2 = jnp.zeros((v2, w2), jnp.float32)
            acc2 = jnp.full((v2, w2), 0.1, jnp.float32)
            ids2 = jnp.asarray(rng.integers(0, v2, n2).astype(np.int32))
            d2 = jnp.asarray(
                rng.standard_normal((n2, w2), dtype=np.float32))

            def step_tiled(s, v2=v2, d2=d2):
                t, a, i = s
                t, a = ptl.tiled_adagrad(t, a, i, d2, 0.01,
                                         interpret=False)
                return t, a, (i * 1103515245 + 12345) % v2

            timed_chain(step_tiled, (tbl2, acc2, ids2), iters=6,
                        label=f"tiled_adagrad dupes n={n2} V={v2//1000}k "
                              f"w={w2}")

            def step_tgather(s, d2=d2):
                t, i = s
                out = ptl.tiled_gather(t, i, interpret=False)
                return t, (i + out[0, 0].astype(jnp.int32) % 2)

            timed_chain(step_tgather, (tbl2, ids2), iters=6,
                        label=f"tiled_gather dupes n={n2} V={v2//1000}k "
                              f"w={w2}")
            del tbl2, acc2, ids2, d2

        # block-size sweep at the tiny-class shape
        v3, n3, w3 = 25_000_000, 720_896, 16
        tbl3 = jnp.zeros((v3, w3), jnp.float32)
        acc3 = jnp.full((v3, w3), 0.1, jnp.float32)
        ids3 = jnp.asarray(rng.integers(0, v3, n3).astype(np.int32))
        d3 = jnp.asarray(rng.standard_normal((n3, w3), dtype=np.float32))
        for tile in (1024, 2048, 4096):
            for chunk in (512, 1024):
                def step_sweep(s, tile=tile, chunk=chunk):
                    t, a, i = s
                    t, a = ptl.tiled_adagrad(t, a, i, d3, 0.01, tile=tile,
                                             chunk=chunk, interpret=False)
                    return t, a, (i * 1103515245 + 12345) % v3
                timed_chain(step_sweep, (tbl3, acc3, ids3), iters=6,
                            label=f"tiled_adagrad T={tile} C={chunk} "
                                  f"n=720k V=25M w=16")
    except Exception as e:  # noqa: BLE001 - toolchain may reject the kernel
        RESULTS["tiled_kernels"] = f"FAIL {str(e)[:200]}"
        print(f"tiled_kernels: FAIL {str(e)[:300]}", flush=True)

    print(json.dumps(RESULTS), flush=True)


if __name__ == "__main__":
    main()
