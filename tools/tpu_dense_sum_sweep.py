"""What the dense aggregate's two implementations cost on the attached chip.

`ops/sparse_update._dense_sum` sums a narrow bucket's contribution stream
into a small target either by XLA's scatter-add, paid by the row
(`sparse_update._scatter_ns_per_row`), or by the resident Pallas kernel
(`pallas_tiled.dense_sum`), paid by the (chunk, tile) pair
(`pallas_tiled.dense_sum_pair_ns`), and picks between them at run time by
those two prices. Both were fitted on one v5e chip (PERF.md section 6,
PR 41). This tool reads them again: for each width it times the scatter,
the min/max walk and the kernel over a feature-major stream and over the
same slots batch-major, and prints the price each one paid beside the
price the rule assumes and what the rule picked.

The stream is a bucket's as `DistributedEmbedding` hands it over: for each
id slot of a sample, `batch` ids into that slot's table's rows of the
bucket. `--tables` describes it as rows x slots x tables; the default is
Tiny V3's width-8 bucket (41 slots a sample into 60,160 rows), cut from the
end at a width whose target would not fit fast memory. `--cell` takes the
streams of one generated batch of a benchmark cell instead and needs no
chip for `--count`, which prints `dense_sum_pairs` and stops.

    chiprun -- python tools/tpu_dense_sum_sweep.py --widths 8,16,32,64,96
    python tools/tpu_dense_sum_sweep.py --cell tiny-v3.zipf --count
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.ops import pallas_tiled as ptl
from distributed_embeddings_tpu.ops import sparse_update as su

TINY_WIDTH_8 = "10000x11x1,10x1x16,1000x1x10,10000x1x4"


def synthetic_streams(tables: str, width: int, batch: int, alpha: float,
                      rng):
    """(rows, feature-major ids, batch-major ids) of a bucket described as
    rows x slots x tables, cut to what fits at this width."""
    slots, first = [], 0        # (first row, rows) of each id slot's table
    for part in tables.split(","):
        rows, per_table, count = (int(x) for x in part.split("x"))
        for _ in range(count):
            if ptl.dense_sum_blocks(first + rows, width) is None:
                break
            slots += [(first, rows)] * per_table
            first += rows
    draws = (rng.zipf(alpha, (len(slots), batch)) - 1 if alpha > 1
             else rng.randint(0, 2 ** 31 - 1, (len(slots), batch)))
    ids = np.stack([start + d % rows
                    for (start, rows), d in zip(slots, draws)])
    return first, ids.reshape(-1).astype(np.int32), \
        ids.T.reshape(-1).astype(np.int32)


def cell_streams(cell_name: str, seed: int):
    """{bucket: (rows, width, feature-major ids, batch-major ids)} of one
    generated batch of a benchmark cell's narrow buckets, in the order the
    bucket's exchange groups hand their slots to the update."""
    from benchmark.harness import spec
    cell = spec.load_cell(cell_name)
    built = spec.plugin("builders", cell.config["builder"]).build(
        cell.config, None, False)
    emb = built.model.embedding
    _, cats, _ = spec.plugin("generators", cell.traffic["generator"]).generate(
        dict(cell.traffic, num_batches=1),
        [(built.tables[t][0], h)
         for t, h in zip(built.table_map, built.hotness)],
        built.global_batch, built.num_numerical, built.numerical_scale,
        seed)[0]
    cats = [np.asarray(c).reshape(built.global_batch, -1) for c in cats]
    tp_inputs = emb.strategy.input_groups[1]
    groups, _ = emb._exchange_groups_for_key(
        tuple((cats[i].shape[1], False) for i in tp_inputs))
    streams = {}
    for grp in groups:
        ids = np.stack(
            [cats[tp_inputs[grp.class_inputs[grp.sel[0, j]]]]
             for j in range(grp.f_max)], axis=1) + grp.offs[0][None, :, None]
        streams.setdefault(grp.bucket, []).append(ids.astype(np.int32))
    out = {}
    for b, parts in streams.items():
        bucket = emb.plan.tp_buckets[b]
        if su._lane_width(bucket.width):
            out[b] = (max(bucket.rows_max, 1), bucket.width,
                      np.concatenate([np.moveaxis(p, 0, 2).reshape(-1)
                                      for p in parts]),
                      np.concatenate([p.reshape(-1) for p in parts]))
    return out


def timed(f, *args, reps=5):
    out = jax.block_until_ready(f(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), out


def sweep(rows, width, streams, configs, rng):
    """One line a (stream order, chunk, tile): the scatter's, the walk's
    and the kernel's ms, and the prices they come to."""
    n = streams["feature_major"].shape[0]
    contribs = jnp.asarray(rng.randn(n, width).astype(np.float32))
    scatter = jax.jit(lambda i, c: su._scatter_sum(i, c, rows))
    for order, ids in streams.items():
        ids = jnp.asarray(ids)
        scatter_ms, (g_want, counts_want) = timed(scatter, ids, contribs)
        picked = su.dense_sum_pairs(ids, rows, width)
        for chunk, tile in configs or [ptl.dense_sum_blocks(rows, width)]:
            line = {"width": width, "rows": rows, "n": n, "order": order,
                    "chunk": chunk, "tile": tile, "scatter_ms": scatter_ms,
                    "scatter_ns_per_row": scatter_ms * 1e6 / n,
                    "rule_ns_per_row": su._scatter_ns_per_row(width),
                    "rule_pairs": int(picked[0]),
                    "rule_picks_kernel": int(picked[1])}
            walk = jax.jit(lambda i, chunk=chunk, tile=tile:
                           ptl.dense_sum_walk(i, rows, chunk, tile))

            def kernel(i, c, chunk=chunk, tile=tile):
                kids, lo, hi, pairs = ptl.dense_sum_walk(i, rows, chunk, tile)
                return ptl.dense_sum(kids, lo, hi, c, rows, tile), pairs

            try:
                walk_ms, _ = timed(walk, ids)
                kernel_ms, ((g, counts), pairs) = timed(
                    jax.jit(kernel), ids, contribs)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                print(json.dumps(dict(line, error=str(e)[:300])), flush=True)
                continue
            scale = jnp.maximum(
                jnp.max(jnp.abs(g_want), axis=1, keepdims=True), 1e-30)
            print(json.dumps(dict(
                line, pairs=int(pairs), walk_ms=walk_ms, kernel_ms=kernel_ms,
                kernel_ns_per_pair=(kernel_ms - walk_ms) * 1e6
                / max(int(pairs), 1),
                rule_ns_per_pair=ptl.dense_sum_pair_ns(chunk, tile, width),
                kernel_wins=bool(kernel_ms < scatter_ms),
                rel_err=float(jnp.max(jnp.abs(g - g_want) / scale)),
                counts_equal=bool(jnp.all(counts == counts_want)))),
                flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", default="8,16")
    ap.add_argument("--tables", default=TINY_WIDTH_8)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--alpha", type=float, default=1.05,
                    help="zipf exponent of the ids; 1 or less = uniform")
    ap.add_argument("--configs", default="",
                    help="chunk:tile,... in place of the rule's own")
    ap.add_argument("--cell", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--count", action="store_true",
                    help="print dense_sum_pairs of the streams and stop")
    args = ap.parse_args()
    rng = np.random.RandomState(args.seed)
    configs = [tuple(int(x) for x in c.split(":"))
               for c in args.configs.split(",") if c]
    if args.cell:
        buckets = [(rows, width, fm, bm) for rows, width, fm, bm
                   in cell_streams(args.cell, args.seed).values()]
    else:
        buckets = []
        for width in (int(w) for w in args.widths.split(",")):
            rows, fm, bm = synthetic_streams(args.tables, width, args.batch,
                                             args.alpha, rng)
            buckets.append((rows, width, fm, bm))
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for rows, width, fm, bm in buckets:
        streams = {"feature_major": fm, "batch_major": bm}
        if args.count:
            for order, ids in streams.items():
                pairs, kernel = su.dense_sum_pairs(jnp.asarray(ids), rows,
                                                   width)
                print(json.dumps({
                    "rows": rows, "width": width, "n": int(ids.shape[0]),
                    "order": order, "walk": su._dense_walk(
                        rows, width, ids.shape[0]),
                    "pairs": int(pairs), "kernel": int(kernel)}), flush=True)
            continue
        assert jax.default_backend() == "tpu", "times are a chip's to give"
        sweep(rows, width, streams, configs, rng)


if __name__ == "__main__":
    main()
