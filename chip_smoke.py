"""chip_smoke.py — does the training main path still start on the chip?

One process, one TPU v5e chip, no arguments (what the driver runs):

  1. tiny   the reference's Synthetic "Tiny V3" exactly as published
            (models/synthetic.py: 55 tables, 4.2 GiB of f32 rows, multi-hot
            [1,10] features, widths 8/16, Adagrad, global batch 65,536)
            through SyntheticModel(distributed=True) +
            make_sparse_train_step + InputGenerator — the calls of
            examples/benchmarks/synthetic_models/main.py — for a compile
            step and five more, state threaded and donated. Then the same
            seeded steps on the repo's plain XLA path (DET_LOOKUP_PATH=xla,
            strategy="sort": jnp.take + combine, XLA scatter).
  2. dlrm   the DLRM of examples/dlrm/main.py at its published widths
            (embedding dim 128, bottom 512,256,128, top 1024,1024,512,256,1)
            with sgd under its lr schedule and one model.apply eval batch.
            The one reduction: 26 tables of 100,000 rows
            (SYNTHETIC_MODELS["criteo"]) — the MLPerf vocabularies are 96 GB
            at this width and the chip has 16.
  3. kernels  every Pallas kernel a dispatch on a TPU backend can reach,
            compiled (never interpreted), once per real width against its
            XLA formulation; and the widths the per-row DMA kernels cannot
            address must be refused by name.

Each training phase checks: the loss is finite on every step; probed rows
that the batches touch changed and a row they never touch did not (read
back through the layer's own forward); the losses and the probed rows agree
with the plain XLA path to f32 tolerance.

``--chips 4`` (never given by the driver) runs only the hybrid-parallel
path: Tiny V3 on a 4-device mesh built from jax.devices() by this one
process, then the same seeded steps on one device; losses and probed rows
must agree, every device must hold its share of the tables, and the
exchange path that ran (padded or ragged) is printed.

``--rehearse`` is the no-chip rehearsal of the control flow: CPU, tables
and batch cut down, kernels interpreted. It can never print "ok": true.

Any failed check raises: there is no try/except around a phase. The last
line of a passing chip run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

TINY_BATCH = 65536
STEPS = 6                  # one compile step + five
COMPARE_STEPS = 4          # steps 0..3 are held against the plain XLA path
PROBE_ROWS = 256


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# --------------------------------------------------------------- training
def probe_inputs(batches, tables, table_map, hotness, rows=PROBE_ROWS):
    """A probe batch for the layer's forward that reads single rows back.

    For every one-hot input whose table has spare ids: `rows` ids the
    training batches touch, and ids that no batch touches through any
    input of that table. Returns (cats, probes) with
    probes = [(input index, number of touched ids)]; slots past the
    touched ids of a probed input hold untouched ids."""
    seen = {}
    for _, cats, _ in batches:
        for inp, ids in enumerate(cats):
            seen.setdefault(table_map[inp], []).append(
                np.unique(np.asarray(ids)))
    seen = {t: np.unique(np.concatenate(v)) for t, v in seen.items()}
    cats, probes = [], []
    for inp, t in enumerate(table_map):
        vocab, h = tables[t][0], hotness[inp]
        ids = np.zeros((rows, h), np.int32)
        free = np.setdiff1d(np.arange(vocab - min(vocab, 65536), vocab),
                            seen[t])
        if h == 1 and len(free) >= rows // 2 and len(seen[t]) >= rows // 2:
            n_t = rows // 2
            pick = np.linspace(0, len(seen[t]) - 1, n_t).astype(np.int64)
            ids[:n_t, 0] = seen[t][pick]
            ids[n_t:, 0] = free[:rows - n_t]
            probes.append((inp, n_t))
        cats.append(ids)
    return cats, probes


def train_phase(name, build_model, make_step, batches, probe, seed,
                lookup_path=None, after_init=None, after=None):
    """Init from `seed` (`after_init(model, params) -> params` may swap
    weights in), run STEPS donated steps over `batches`, call
    `after(model, params)`; return (losses, probed rows before, probed
    rows after)."""
    import jax

    if lookup_path is None:
        os.environ.pop("DET_LOOKUP_PATH", None)
    else:
        os.environ["DET_LOOKUP_PATH"] = lookup_path
    model = build_model()
    params = model.init(jax.random.PRNGKey(seed))
    if after_init is not None:
        params = after_init(model, params)
    init_fn, step_fn = make_step(model)
    opt_state = init_fn(params)
    probe_cats, probes = probe
    read_rows = jax.jit(lambda p, cats: model.embedding(p, cats))

    def probed(params):
        outs = read_rows(params["embedding"], probe_cats)
        return [np.asarray(outs[inp]) for inp, _ in probes]

    before = probed(params)
    losses, t_steps = [], []
    for i in range(STEPS):
        numerical, cats, labels = batches[i % len(batches)]
        t0 = time.perf_counter()
        params, opt_state, loss = step_fn(params, opt_state, numerical,
                                          cats, labels)
        losses.append(float(loss))          # host fetch = the sync
        t_steps.append(time.perf_counter() - t0)
        check(np.isfinite(losses[-1]), f"{name}: loss at step {i} is "
              f"{losses[-1]}")
    rows_after = probed(params)
    step_ms = 1e3 * float(np.median(t_steps[1:]))
    log(f"[{name}] compile+first step {t_steps[0]:.1f} s, step "
        f"{step_ms:.1f} ms (median of {STEPS - 1}, host clock), losses "
        f"{[round(x, 6) for x in losses]}")
    if after is not None:
        after(model, params)
    return losses, before, rows_after


def check_rows(name, probes, before, after):
    changed = 0
    for (inp, n_t), b, a in zip(probes, before, after):
        check(np.array_equal(b[n_t:], a[n_t:]),
              f"{name}: rows of input {inp} that no batch touched changed")
        changed += int(np.any(b[:n_t] != a[:n_t], axis=-1).sum())
    total = sum(n for _, n in probes)
    check(total > 0, f"{name}: no probe-able one-hot input")
    # a touched row may stay put only if its gradient is exactly zero
    check(changed >= 0.9 * total,
          f"{name}: only {changed} of {total} probed touched rows changed")
    log(f"[{name}] {changed}/{total} probed touched rows changed; "
        f"{sum(len(b) - n for (_, n), b in zip(probes, before))} untouched "
        "rows bit-identical")


def check_agree(name, other, got, want, rtol=1e-4):
    """Losses of the first COMPARE_STEPS steps and the probed rows after
    all steps, `got` vs `want`, to f32 tolerance."""
    l_got, _, a_got = got
    l_want, _, a_want = want
    dev = max(abs(g - w) / max(abs(w), 1e-12)
              for g, w in zip(l_got[:COMPARE_STEPS], l_want[:COMPARE_STEPS]))
    check(dev <= rtol, f"{name}: losses {l_got[:COMPARE_STEPS]} vs {other} "
          f"{l_want[:COMPARE_STEPS]} (max rel dev {dev:.2e} > {rtol})")
    row_dev = max(float(np.max(np.abs(a - b))) for a, b in zip(a_got, a_want))
    check(all(np.allclose(a, b, rtol=1e-3, atol=1e-5)
              for a, b in zip(a_got, a_want)),
          f"{name}: probed rows differ from {other} (max abs {row_dev:.2e})")
    log(f"[{name}] agrees with {other}: loss max rel dev {dev:.2e} over "
        f"steps 0-{COMPARE_STEPS - 1}, probed rows max abs dev "
        f"{row_dev:.2e}")


def tiny_setup(sizes, seed):
    from distributed_embeddings_tpu.models.synthetic import (
        SYNTHETIC_MODELS, InputGenerator, expand_embedding_configs)

    cfg = SYNTHETIC_MODELS["tiny"]
    if sizes["table_scale"] != 1.0:          # --rehearse only
        cfg = cfg._replace(embedding_configs=[
            c._replace(num_rows=max(4, int(c.num_rows
                                           * sizes["table_scale"])))
            for c in cfg.embedding_configs])
    tables, table_map, hotness = expand_embedding_configs(cfg)
    gib = sum(r * w for r, w in tables) * 4 / 2 ** 30
    log(f"[tiny] {cfg.name}: {len(tables)} tables, {gib:.2f} GiB of f32 "
        f"rows (+ as much Adagrad state), {len(table_map)} inputs, "
        f"batch {sizes['tiny_batch']}")
    gen = InputGenerator(cfg, sizes["tiny_batch"], alpha=1.05,
                         num_batches=3, seed=seed)
    batches = [gen[i] for i in range(len(gen))]
    probe = probe_inputs(batches, tables, table_map, hotness)
    return cfg, batches, probe


def tiny_builders(cfg, mesh, strategy):
    from distributed_embeddings_tpu.models.synthetic import SyntheticModel
    from distributed_embeddings_tpu.training import make_sparse_train_step

    def build_model():
        return SyntheticModel(cfg, mesh=mesh, distributed=True,
                              strategy="memory_balanced")

    def make_step(model):
        return make_sparse_train_step(model, "adagrad", lr=0.01,
                                      strategy=strategy)
    return build_model, make_step


def run_tiny(sizes, seed):
    cfg, batches, probe = tiny_setup(sizes, seed)
    got = train_phase("tiny", *tiny_builders(cfg, None, "auto"), batches,
                      probe, seed)
    check_rows("tiny", probe[1], got[1], got[2])
    want = train_phase("tiny/xla", *tiny_builders(cfg, None, "sort"),
                       batches, probe, seed, lookup_path="xla")
    check_agree("tiny", "the plain XLA path", got, want)


def run_dlrm(sizes, seed):
    import jax
    import jax.numpy as jnp
    from distributed_embeddings_tpu.models.dlrm import DLRM, make_lr_schedule
    from distributed_embeddings_tpu.models.synthetic import SYNTHETIC_MODELS
    from distributed_embeddings_tpu.training import make_sparse_train_step
    from distributed_embeddings_tpu.utils.metrics import StreamingAUC

    ec = SYNTHETIC_MODELS["criteo"].embedding_configs[0]
    vocab = sizes.get("dlrm_vocab", ec.num_rows)        # --rehearse only
    table_sizes = [vocab] * ec.num_tables
    batch = sizes["dlrm_batch"]
    log(f"[dlrm] {len(table_sizes)} tables x {vocab} x {ec.width}, bottom "
        f"512,256,128, top 1024,1024,512,256,1, batch {batch}, sgd")
    rng = np.random.RandomState(seed)
    batches = []
    for _ in range(3):
        batches.append((
            jnp.asarray(rng.rand(batch, 13).astype(np.float32)),
            [jnp.asarray(rng.randint(0, v, batch).astype(np.int32))
             for v in table_sizes],
            jnp.asarray(rng.randint(0, 2, (batch, 1)).astype(np.float32))))
    host = [(n, [np.asarray(c)[:, None] for c in cats], lab)
            for n, cats, lab in batches]
    cats, probes = probe_inputs(
        host, [(v, ec.width) for v in table_sizes],
        list(range(len(table_sizes))), [1] * len(table_sizes))
    probe = ([c[:, 0] for c in cats], probes)
    schedule = make_lr_schedule(24.0, 8000, 48000, 24000)

    def build_model():
        return DLRM(table_sizes=table_sizes, embedding_dim=ec.width,
                    bottom_mlp_dims=[512, 256, 128],
                    top_mlp_dims=[1024, 1024, 512, 256, 1],
                    num_numerical_features=13, mesh=None,
                    dist_strategy="memory_balanced")

    def eval_batch(model, params):
        metric = StreamingAUC()
        numerical, cats, labels = batches[0]
        logits = jax.jit(model.apply)(params, numerical, cats)
        state = metric.update(metric.init(), labels, logits[:, 0])
        auc = float(metric.result(state))
        check(logits.shape == (batch, 1)
              and bool(jnp.all(jnp.isfinite(logits)))
              and 0.0 <= auc <= 1.0,
              f"dlrm: eval batch gave shape {logits.shape}, AUC {auc}")
        log(f"[dlrm] eval batch through model.apply: logits {logits.shape} "
            f"finite, AUC {auc:.4f}")

    def steps(strategy):
        return lambda model: make_sparse_train_step(
            model, "sgd", lr=schedule, strategy=strategy)

    got = train_phase("dlrm", build_model, steps("auto"), batches, probe,
                      seed, after=eval_batch)
    # the schedule's lr is 0 on step 0, so rows move from step 1 on
    check_rows("dlrm", probes, got[1], got[2])
    want = train_phase("dlrm/xla", build_model, steps("sort"), batches,
                       probe, seed, lookup_path="xla")
    check_agree("dlrm", "the plain XLA path", got, want)


# ---------------------------------------------------------------- kernels
def run_kernels(interpret):
    """Every Pallas entry point a TPU dispatch can select, compiled, at the
    widths the two models use, against XLA. The update/gather families
    carry their own compiled-vs-XLA checks (sparse_update.prevalidate_*),
    which raise on a mismatch or a compile error."""
    import jax.numpy as jnp
    from distributed_embeddings_tpu.ops import (pallas_lookup, pallas_scatter,
                                                pallas_tiled, sparse_update)

    if not interpret:
        for mod in (pallas_lookup, pallas_tiled, pallas_scatter):
            check(mod._interpret_default(None) is False,
                  f"{mod.__name__} would run interpreted on this backend")
    t0 = time.perf_counter()
    # forward lookups: the MXU one-hot kernel (small vocab) and the row-DMA
    # gather (width 128) behind fused_embedding_lookup, sum and mean
    rng = np.random.RandomState(0)
    for vocab, width, hot, kernel in ((1000, 8, 10, "onehot"),
                                      (10000, 16, 10, "xla-by-shape"),
                                      (100000, 128, 10, "row-dma"),
                                      (100000, 128, 1, "row-dma")):
        vocab = vocab if not interpret else min(vocab, 9000)
        table = jnp.asarray(rng.randn(vocab, width).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, vocab, (2048, hot)).astype(np.int32))
        w = jnp.asarray(rng.rand(2048, hot).astype(np.float32))
        for combiner in ("sum", "mean"):
            got = pallas_lookup.fused_embedding_lookup(
                table, ids, w, combiner, interpret=interpret)
            ww = w / jnp.maximum(w.sum(1, keepdims=True), 1.0) \
                if combiner == "mean" else w
            want = jnp.einsum("bk,bkw->bw", ww, jnp.take(table, ids, axis=0))
            dev = float(jnp.max(jnp.abs(got - want)))
            check(dev < 1e-4, f"kernels: fused_embedding_lookup[{kernel}] "
                  f"V={vocab} w={width} k={hot} {combiner}: max dev {dev}")
        log(f"[kernels] lookup {kernel}: V={vocab} w={width} k={hot} ok")
    if interpret:
        log("[kernels] rehearsal: compiled checks skipped (no chip)")
        return
    for width in (8, 16, 128):
        check(sparse_update.prevalidate_tiled(width)
              and sparse_update.prevalidate_pallas_fused(width),
              f"kernels: tiled/fused width {width}")
        log(f"[kernels] tiled gather/sgd/adagrad/adam + fused rows/forward: "
            f"width {width} ok")
    check(sparse_update.prevalidate_pallas_scatter(128),
          "kernels: row-DMA scatter width 128")
    log("[kernels] row-DMA scatter-add + adagrad: width 128 ok")
    # the widths the row-DMA kernels cannot address are refused by name
    for width in (8, 16, 256):
        for fn in (lambda: pallas_lookup.check_lookup_kernel(
                       10 ** 6, width, jnp.float32),
                   lambda: sparse_update.prevalidate_pallas_scatter(width)):
            try:
                fn()
            except ValueError as e:
                check(f"width {width}" in str(e), f"kernels: {e}")
            else:
                check(False, f"kernels: row-DMA width {width} not refused")
    log("[kernels] row-DMA kernels refuse widths 8, 16, 256 by name; "
        f"{time.perf_counter() - t0:.1f} s")


# ------------------------------------------------------------- four chips
def run_four_chips(sizes, seed, devices):
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    cfg, batches, probe = tiny_setup(sizes, seed)
    mesh = create_mesh(devices)
    shares = {}

    def record_shares(model, params):
        del params
        for d in devices:
            shares[d.id] = (d.memory_stats() or {}).get("bytes_in_use", 0)
        rep = model.embedding.exchange_padding_report()
        log(f"[tiny/4chips] exchange paths (bucket, f_max, k) -> path: "
            f"{rep['exchange_paths']}")
        check(rep["exchange_paths"], "tiny/4chips: no exchange ran")

    # a table's initial rows are drawn per (table, rank), so the same seed
    # gives other rows under another placement: carry the meshed model's
    # weights over to the one-device run through get/set_weights
    weights = []

    def export_weights(model, params):
        t0 = time.perf_counter()
        weights.extend(model.embedding.get_weights(params["embedding"]))
        log(f"[tiny/4chips] get_weights: {len(weights)} tables to the host "
            f"in {time.perf_counter() - t0:.1f} s")
        return params

    def import_weights(model, params):
        params["embedding"] = None          # free the seeded tables first
        params["embedding"] = model.embedding.set_weights(weights)
        return params

    with mesh:
        got = train_phase("tiny/4chips", *tiny_builders(cfg, mesh, "auto"),
                          batches, probe, seed, after_init=export_weights,
                          after=record_shares)
    check_rows("tiny/4chips", probe[1], got[1], got[2])
    total = sum(shares.values())
    log("[tiny/4chips] bytes in use per device after training: "
        + ", ".join(f"{k}: {v / 2 ** 30:.2f} GiB" for k, v in shares.items()))
    if devices[0].platform == "tpu":
        check(all(0.05 * total < v < 0.6 * total for v in shares.values()),
              f"tiny/4chips: tables are not spread over the devices: "
              f"{shares}")
    want = train_phase("tiny/1of4", *tiny_builders(cfg, None, "auto"),
                       batches, probe, seed, after_init=import_weights)
    check_agree("tiny/4chips", "one device of the same process", got, want)


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="no-chip rehearsal on the CPU at a tiny size")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    knobs = sorted(k for k in os.environ if k.startswith("DET_"))
    check(not knobs, f"unset {knobs}: the smoke runs the default path")

    import jax
    import jaxlib

    from distributed_embeddings_tpu.utils.compile_cache import (
        enable_compile_cache)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no accelerator: jax.devices() = {devices}",
              file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but jax sees {len(devices)} device(s)")
    cache_dir = enable_compile_cache()
    libtpu = next((d.version for d in importlib.metadata.distributions()
                   if d.metadata["Name"] == "libtpu"), "not installed")
    log(f"device: {dev.platform} / {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu}; compile cache: {cache_dir}")

    sizes = {"table_scale": 1.0, "tiny_batch": TINY_BATCH,
             "dlrm_batch": TINY_BATCH}
    if args.rehearse:
        sizes = {"table_scale": 0.001, "tiny_batch": 512, "dlrm_batch": 512,
                 "dlrm_vocab": 5000}
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(sizes, args.seed, devices[:4])
    else:
        run_tiny(sizes, args.seed)
        run_dlrm(sizes, args.seed)
        run_kernels(interpret=args.rehearse)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"all phases passed in {time.perf_counter() - t0:.0f} s; peak "
        f"device memory {peak / 2 ** 30:.2f} GiB")
    result = {"ok": not args.rehearse,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
