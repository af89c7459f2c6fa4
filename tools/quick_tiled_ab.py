"""Minimal tiled-vs-default A/B for ultra-short chip runs.

The full bench.py run (tiny + DLRM + all arms) needs half an hour of chip
time. This script answers ONE question — do the tiled one-hot-matmul kernels
beat the XLA path at the tiny benchmark shape (docs/perf_model.md decision
rule 5) — in the fewest minutes that can produce an honest number:
one batch-65536 tiny config, default arm then tiled arms, slope-timed with
the fetch-sync methodology, one JSON line to stdout.

Run on the chip: `chiprun -- python tools/quick_tiled_ab.py`.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    dev = jax.devices()[0]
    out = {"device": f"{dev.platform}:{getattr(dev, 'device_kind', '?')}",
           "started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    if dev.platform == "cpu" and os.environ.get(
            "DET_QUICKAB_ALLOW_CPU") != "1":
        # DET_QUICKAB_ALLOW_CPU=1: the unattended-window rehearsal
        # (tools/window_rehearsal.py) runs this stage on CPU with shrunken
        # shapes (DET_QUICKAB_BATCH/ITERS) to validate the plumbing
        out["verdict"] = "SKIP cpu backend"
        print(json.dumps(out), flush=True)
        return

    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "det_bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._isolate_from_measured_defaults()

    from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                             SyntheticModel)
    from distributed_embeddings_tpu.ops import sparse_update

    cfg = SYNTHETIC_MODELS["tiny"]
    batch = int(os.environ.get("DET_QUICKAB_BATCH", 65536))
    iters = int(os.environ.get("DET_QUICKAB_ITERS", 8))
    out["git_sha"] = bench._git_sha()
    t0 = time.perf_counter()
    try:
        dt = bench.run_at_batch(SyntheticModel(cfg, mesh=None,
                                               distributed=True),
                                batch, iters=iters)
        out["tiny_default_ms"] = round(dt * 1e3, 3)
        out["tiny_default_raw"] = getattr(bench.run_at_batch, "last_raw",
                                          None)
    except Exception as e:  # noqa: BLE001
        out["tiny_default_error"] = str(e)[:300]
        dt = None
    out["default_wall_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(out), flush=True)      # partial evidence ASAP

    for key, env, validate in (
            ("tiny_ab_tiled", {"DET_SCATTER_IMPL": "tiled"},
             sparse_update.prevalidate_tiled),
            ("tiny_ab_tiled_full",
             {"DET_SCATTER_IMPL": "tiled", "DET_LOOKUP_PATH": "tiled"},
             sparse_update.prevalidate_tiled)):
        t0 = time.perf_counter()
        bench.run_ab_arm(out, key, env, cfg, batch, iters,
                         validate=validate)
        out[f"{key}_wall_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(out), flush=True)  # refresh after every arm

    if dt is not None and out.get("tiny_ab_tiled_ms"):
        out["tiled_speedup"] = round(out["tiny_default_ms"]
                                     / out["tiny_ab_tiled_ms"], 2)
    out["finished"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
