"""One run of one cell of BENCHMARK.json.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It refuses any ``DET_*`` variable (a cell times the
default path), needs a TPU with at least the cell's chips (``--rehearse`` is
the explicit CPU path: sizes cut as the configuration file says, no timing
and no device metric, ``"correct": false`` on the last line), builds the
cell from its data files, holds the system's first steps to the plain
reference, and then either measures for ``--seconds`` (``--trace 0``: the
end-to-end metrics) or traces a short steady window (``--trace 1``: the
per-layer metrics and ``breakdown``). Progress goes to earlier lines; the
last line of standard output is the one JSON object of the contract.
"""

import time

_T0 = time.perf_counter()        # set-up is counted from here

import argparse                  # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import shutil                    # noqa: E402
import statistics                # noqa: E402
import sys                       # noqa: E402
from contextlib import nullcontext  # noqa: E402

from benchmark.harness import spec  # noqa: E402

END_TO_END = ("samples_per_s", "step_ms_p50", "setup_s")   # what a window gives
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg):
    print(msg, flush=True)


def fail(msg, code=2):
    print(f"benchmark.run: {msg}", file=sys.stderr, flush=True)
    return code


class Phases:
    """Seconds of set-up by phase, in order."""

    def __init__(self):
        self.seconds = {}
        self._last = _T0

    def done(self, name):
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now


def count_compiles():
    """{"compiles", "cache_hits"}, kept current by jax.monitoring."""
    import jax

    counts = {"compiles": 0, "cache_hits": 0}

    def on_duration(name, *args, **kwargs):
        counts["compiles"] += name == BACKEND_COMPILE

    def on_event(name, *args, **kwargs):
        counts["cache_hits"] += name == CACHE_HIT

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return counts


def stage(built, batch):
    """A host batch onto the device(s): batch-sharded over a mesh, as
    `fit` stages it, and plain arrays on one chip."""
    import jax.numpy as jnp

    inputs, cats, labels = batch
    cats = built.shape_ids(cats)
    if built.mesh is not None:
        from distributed_embeddings_tpu.parallel.staging import stage_dp_batch
        return stage_dp_batch(built.mesh, (inputs, cats, labels))
    return (jnp.asarray(inputs), [jnp.asarray(c) for c in cats],
            jnp.asarray(labels))


def timed_metrics(cell, built, win, setup_s):
    """The cell's end-to-end metrics from a timed window."""
    measured = {
        "samples_per_s": win.attempted * built.global_batch / win.elapsed_s,
        "step_ms_p50": statistics.median(win.block_ms),
        "setup_s": setup_s}
    log(f"WINDOW step_ms_p50 is the median of {len(win.block_ms)} sync "
        f"blocks; sample unit: {cell.config['sample_unit']}")
    return {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def traced_metrics(result, cell, built, win, trace_path, kind, peak):
    """Fill `result` with the cell's per-layer metrics, the device's busy
    time and the breakdown, from the trace. Returns an error or None."""
    from benchmark.harness import layers, xplane

    if trace_path is None:
        return "the profiler wrote no .xplane.pb"
    rules = xplane.load_classes()
    chips = xplane.reduce(xplane.load(trace_path), rules)
    if not chips:
        return (f"{trace_path} holds no device plane with an "
                f"{xplane.OPS_LINE!r} line")
    for chip in chips:
        log(f"TRACE {chip.plane}: busy {chip.busy_ns * 1e-6:.3f} ms of "
            f"{chip.window_ns * 1e-6:.3f} ms; ms per class "
            + json.dumps({k: round(v * 1e-6, 3)
                          for k, v in sorted(chip.class_ns.items())}))
    log("TRACE class patterns that matched no operation: "
        + json.dumps(xplane.idle_patterns(chips, rules)))
    ctx = layers.Context(chips, win.attempted, built, cell, kind, peak or None)
    result["metrics"] = layers.read_all(ctx)
    for note in ctx.notes:
        log("TRACE " + note)
    if cell.chips == 1 and any(c.class_ns.get("exchange") for c in chips):
        log("TRACE a one-chip cell ran an operation of class `exchange`")
        result["correct"] = False
    result["device"]["busy_s"] = (sum(c.busy_ns for c in chips) / len(chips)
                                  * 1e-9)
    result["device"]["window_s"] = chips[0].window_ns * 1e-9
    idlest = max(chips, key=lambda c: c.window_ns - c.busy_ns)
    result["breakdown"] = {"device_ops": xplane.top_ops(idlest),
                           "idle_gaps": xplane.top_gaps(idlest)}
    return None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's cut sizes")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the profiler's files here and keep them "
                         "(default: a directory inside the checkout, removed "
                         "after the reduction)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    knobs = sorted(k for k in os.environ if k.startswith("DET_"))
    if knobs:
        return fail(f"unset {knobs}: a cell times the default path")
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        return fail(str(e))
    unknown = [m["name"] for m in cell.end_to_end if m["name"] not in END_TO_END]
    if unknown:
        return fail(f"BENCHMARK.json asks {cell.name} for end-to-end metrics "
                    f"{unknown}; this harness measures {list(END_TO_END)}")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()

    import jax
    import numpy as np

    try:
        from distributed_embeddings_tpu.parallel.mesh import create_mesh
        from distributed_embeddings_tpu.utils.compile_cache import (
            enable_compile_cache)
    except ImportError as e:
        return fail(f"the system under test is not in this checkout: {e}")
    from benchmark.harness import check, layers, loop

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        return fail(f"no accelerator: jax.devices() = {devices}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chip(s), jax sees "
                    f"{len(devices)}")
    used = devices[:cell.chips]
    cache_dir = enable_compile_cache()
    # a program that took a quarter of a second to compile is kept for the
    # cell's later runs in this checkout; the tens of one-operation programs
    # of a run compile in milliseconds and are not worth a file each
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)
    counts = count_compiles()
    log(f"cell {cell.name}: {cell.chips} x {used[0].platform} / "
        f"{used[0].device_kind}; jax {jax.__version__}; compile cache "
        f"{cache_dir}; seed {args.seed}")
    phases = Phases()
    phases.done("import")

    # ---- the cell, from its data files
    config, traffic = cell.config, cell.traffic
    mesh = create_mesh(used) if cell.chips > 1 else None
    built = spec.plugin("builders", config["builder"]).build(
        config, mesh, args.rehearse)
    phases.done("build")
    host_batches = spec.plugin("generators", traffic["generator"]).generate(
        traffic, [(built.tables[t][0], h)
                  for t, h in zip(built.table_map, built.hotness)],
        built.global_batch, built.num_numerical, built.numerical_scale,
        args.seed)
    phases.done("inputs")
    sync_every = int(config["sync_every"])
    trace_dir = args.keep_trace or os.path.join(spec.ROOT, ".benchmark_trace",
                                                cell.name)

    with mesh or nullcontext():
        params = built.model.init(jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
        phases.done("init")
        batches = [stage(built, b) for b in host_batches]
        jax.block_until_ready(batches)
        phases.done("inputs")

        # ---- the first steps, held to the plain reference; they are the
        # warm-up too: the second runs on the first's donated outputs
        first = [i % len(batches)
                 for i in range(check.check_steps(built.optimizer))]
        chk = check.Check(built, params, [host_batches[i] for i in first],
                          batches[0][1], config["matmul_precision"],
                          phases.done)
        init_fn, step_fn = built.make_step()
        opt_state = init_fn(params)
        sys_losses = []
        for i in first:
            inputs, cats, labels = batches[i]
            params, opt_state, loss = step_fn(params, opt_state, inputs,
                                              cats, labels)
            sys_losses.append(float(loss))
        phases.done("compile+warmup")
        check_error = None
        try:
            summary = chk.finish(params, sys_losses)
        except check.CheckFailed as e:
            check_error, summary = str(e), e.summary
        reference_peak = chk.reference_peak_bytes
        del chk
        phases.done("check")
        log("REFERENCE_CHECK " + json.dumps(
            {"ok": check_error is None, "error": check_error, **summary,
             "reference_peak_bytes": reference_peak}))

        # ---- the window
        state = (params, opt_state)
        del params, opt_state
        trace_path = None
        if args.trace:
            # one more synced block after the check's reads, then the trace
            _, state = loop.timed(step_fn, state, batches, 0.0, sync_every)
            os.makedirs(trace_dir, exist_ok=True)
            compiles_before = counts["compiles"]
            setup_s = time.perf_counter() - _T0
            win, state, trace_path = loop.traced(
                step_fn, state, batches, int(config["trace_steps"]),
                sync_every, trace_dir)
        else:
            compiles_before = counts["compiles"]
            setup_s = time.perf_counter() - _T0
            win, state = loop.timed(step_fn, state, batches, args.seconds,
                                    sync_every)
        compiled_in_window = counts["compiles"] - compiles_before
    del state

    log("SETUP " + json.dumps(
        {k: round(v, 3) for k, v in phases.seconds.items()}
        | {"total": round(setup_s, 3), "backend_compiles": compiles_before,
           "compile_cache_hits": counts["cache_hits"]}))
    if win.error:
        log(f"WINDOW a step raised: {win.error}")
    log(f"WINDOW {win.attempted} steps, a loss fetch every {sync_every}, "
        f"{win.elapsed_s:.3f} s, backend compiles inside "
        f"{compiled_in_window}, losses {win.losses[:2]} .. {win.losses[-2:]}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    result = {
        "correct": (check_error is None and win.failed == 0
                    and bool(win.losses) and bool(np.all(np.isfinite(win.losses)))
                    and compiled_in_window == 0 and not args.rehearse),
        "attempted": win.attempted, "failed": win.failed, "metrics": {},
        "device": {"platform": used[0].platform, "kind": used[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": peak}}
    error = None
    if args.rehearse:
        log("rehearsal on the CPU: no timing and no device metric is "
            "reported, and `correct` is false by definition")
        if args.trace:
            # what the program counts needs no chip: such readers are
            # rehearsed, on an earlier line and never under `metrics`
            ctx = layers.Context([], win.attempted, built, cell,
                                 used[0].device_kind, None)
            log("REHEARSED_LAYER_METRICS " + json.dumps(layers.read_all(ctx)))
    elif args.trace:
        error = traced_metrics(result, cell, built, win, trace_path,
                               used[0].device_kind, peak)
    else:
        result["metrics"] = timed_metrics(cell, built, win, setup_s)
    if args.trace and not args.keep_trace:
        shutil.rmtree(os.path.join(spec.ROOT, ".benchmark_trace"),
                      ignore_errors=True)
    if error:
        return fail(error, 1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
