"""Compiles for a DESCRIBED TPU v5e — no chip attached, nothing runs.

The installed TPU compiler builds programs for a chip that is described and
not attached (`on-chip-measurement` guide, section 2). Interpret mode cannot
see what it refuses: block shapes that are not tiling-legal, DMA semaphore
and scalar memory that overflow, row slices the HBM tiling cannot address, a
step that does not fit the chip's memory. These cases hold every Pallas
entry point a dispatch on a TPU backend can select to that compiler at the
widths the models use (16 and 128, at the models' batch of 65,536), the two
kernels of Tiny V3's default path at their buckets' real shapes, and the
full-size Tiny V3 Adagrad step to the chip's 16 GB. A kernel that cannot be
made legal at a width must be refused by name before any step runs, and the
case pins that refusal instead.

A compile that passes here is not a chip run: `chip_smoke.py` is.

The chip is described inside a module-scoped fixture, which the token
cells' cases share (`test_chip_compile_cells.py`, `test_chip_compile_check.py`:
a whole program's compile each, in files of their own so that the suite's
workers, handed a file each, run them side by side): only a worker handed
one of these files loads the TPU library, and the suite lets several
processes load it (`ALLOW_MULTIPLE_LIBTPU_LOAD`). Nothing here touches the
topology at import, in a skipif or in a parametrize argument.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_embeddings_tpu.ops import (pallas_lookup, pallas_scatter,
                                            pallas_tiled)

BATCH = 65536
HBM_BYTES = 16 * 10 ** 9        # one TPU v5e chip (Google Cloud, "TPU v5e")
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: every later run would warn
    # and compile again, so the cache is off around these cases
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _vocab(width):
    return 100_000 if width == 128 else 1_000_000


def _lookup_args(S, width, vocab, hot=10):
    return (S((vocab, width), F32), S((BATCH, hot), I32),
            S((BATCH, hot), F32))


def _stream_args(S, width, n=BATCH):
    """(table, sorted ids / rep, permutation, rows) of an update stream."""
    return (S((_vocab(width), width), F32), S((n,), I32), S((n,), I32),
            S((n, width), F32))


# name -> (S, width) -> (fn, args): the entry point called with
# interpret=False, exactly as a TPU dispatch calls it. The tiled update
# kernels take the caller's sort (`presorted`), as the folded train step
# passes it; a fresh 65,536-key XLA sort only adds ten seconds of compile.
def _onehot(S, width):
    return (lambda t, i, w: pallas_lookup._onehot_lookup(
        t, i, w, interpret=False)), _lookup_args(S, width, 1000)


def _dma_gather(S, width):
    pallas_lookup.check_lookup_kernel(_vocab(width), width, F32)
    return (lambda t, i, w: pallas_lookup._dma_gather_lookup(
        t, i, w, interpret=False)), _lookup_args(S, width, _vocab(width))


def _tiled_gather(S, width):
    t, sid, _, _ = _stream_args(S, width)
    return (lambda t, s: pallas_tiled.tiled_gather_sorted(
        t, s, interpret=False)), (t, sid)


def _tiled_gather_weighted(S, width):
    t, sid, _, _ = _stream_args(S, width)
    return (lambda t, s, w: pallas_tiled.tiled_gather_sorted_weighted(
        t, s, w, interpret=False)), (t, sid, S((BATCH,), F32))


def _tiled_sgd(S, width):
    return (lambda t, s, p, r: pallas_tiled.tiled_sgd(
        t, s, r, 0.01, interpret=False, presorted=(s, p))
    ), _stream_args(S, width)


def _tiled_adagrad(S, width):
    t, sid, perm, rows = _stream_args(S, width)
    return (lambda t, a, s, p, r: pallas_tiled.tiled_adagrad(
        t, a, s, r, 0.01, interpret=False, presorted=(s, p))
    ), (t, t, sid, perm, rows)


def _tiled_adam(S, width):
    t, sid, perm, rows = _stream_args(S, width)
    return (lambda t, m, v, c, s, p, r: pallas_tiled.tiled_adam(
        t, m, v, c, s, r, 0.01, interpret=False, presorted=(s, p))
    ), (t, t, t, S((), I32), sid, perm, rows)


def _tiled_sgd_rows(S, width):
    t, rep, _, sums = _stream_args(S, width)
    return (lambda t, r, s: pallas_tiled.tiled_sgd_rows(
        t, r, s, 0.01, interpret=False)), (t, rep, sums)


def _tiled_adagrad_rows(S, width):
    t, rep, _, sums = _stream_args(S, width)
    return (lambda t, a, r, s: pallas_tiled.tiled_adagrad_rows(
        t, a, r, s, 0.01, interpret=False)), (t, t, rep, sums)


def _tiled_adam_rows(S, width):
    t, rep, _, sums = _stream_args(S, width)
    return (lambda t, m, v, c, r, s: pallas_tiled.tiled_adam_rows(
        t, m, v, c, r, s, 0.01, interpret=False)
    ), (t, t, t, S((), I32), rep, sums)


def _dma_scatter(S, width):
    # a DLRM-sized update: 26 features x 65,536 deduped rows
    t, rep, _, sums = _stream_args(S, width, n=26 * BATCH)
    return (lambda t, r, s: pallas_scatter.scatter_add_sorted_unique(
        t, r, s, interpret=False)), (t, rep, sums)


def _dma_adagrad(S, width):
    t, rep, _, sums = _stream_args(S, width, n=26 * BATCH)
    return (lambda t, a, r, s: pallas_scatter.adagrad_rows_sorted_unique(
        t, a, r, s, 0.01, interpret=False)), (t, t, rep, sums)


KERNELS = {f.__name__.lstrip("_"): f for f in (
    _onehot, _dma_gather, _tiled_gather, _tiled_gather_weighted, _tiled_sgd,
    _tiled_adagrad, _tiled_adam, _tiled_sgd_rows, _tiled_adagrad_rows,
    _tiled_adam_rows, _dma_scatter, _dma_adagrad)}
# the per-row DMA kernels address only float32 rows of width 128
# (pallas_lookup._ROW_DMA_WIDTH): at 16 their dispatch must raise
REFUSED = {("dma_gather", 16), ("dma_scatter", 16), ("dma_adagrad", 16)}


def _lane_picks(text, count_rows=0):
    """The fusions that pick one lane of 128 out of every vector of a padded
    activation array, to put the batch back on the lanes. One such slice
    is no activation's and is left out by its shape: the count row of a
    `count_rows`-row dense aggregate (Tiny V3's width-8 bucket: 60,160
    floats, 240 KB), once in each branch of `_dense_sum`'s conditional
    and nowhere else."""
    lines = re.findall(
        r"^\s*%?slice_reduce_fusion[.\d]* = \(?(?:bf16|f32)\[.*$", text, re.M)
    def elements(line):
        dims = re.search(r"\[([\d,]*)\]", line).group(1)
        return math.prod(int(d) for d in dims.split(","))

    counts = [line for line in lines if "det.dedup/cond/branch_" in line
              and elements(line) == count_rows]
    assert len(counts) <= 2, counts
    return [line for line in lines if line not in counts]


def _pathless_row_loops(text):
    """The compiler's own `while` loops (no source path) that re-tile a
    group's `[1, w, n]` rows."""
    return [line for line in text.splitlines()
            if re.search(r" while\(", line) and "op_name" not in line
            and re.search(r"f32\[1,(8|16),\d+\]", line)]


def _adagrad_step(cfg, batch, one_chip, monkeypatch):
    """A synthetic model's sparse Adagrad step, donated, lowered for the
    described chip. The library asks `jax.default_backend()` where a TPU
    takes another branch than the CPU (lookup dispatch, kernel interpret
    mode), and here it would see the CPU: steer it, in the test, so that
    what lowers is what the chip is given."""
    from distributed_embeddings_tpu.models.synthetic import (
        SyntheticModel, expand_embedding_configs)
    from distributed_embeddings_tpu.training import make_sparse_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_tiled, "_BACKEND_INTERPRET", None)
    model = SyntheticModel(cfg, mesh=None, distributed=True,
                           strategy="memory_balanced")
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.01)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(init_fn, params)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda s: S(s.shape, s.dtype), tree)

    _, _, hotness = expand_embedding_configs(cfg)
    return jax.jit(step_fn, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state),
        S((batch, cfg.num_numerical_features), F32),
        [S((batch, h), I32) for h in hotness], S((batch, 1), F32))


def _compile_adagrad_step(cfg, batch, one_chip, monkeypatch):
    """`_adagrad_step` compiled for the described chip, with one departure
    from what the chip is given: a bucket's fresh sort orders the ids
    alone and its rows are gathered by the sort's permutation
    (`SORT_PAYLOAD_WIDTHS` empty). A sort that carries 16 contribution columns
    (`pallas_tiled._sort_carrying`, 17 operands) takes the chip's compiler
    4 min on the chip's host and ~18 min on an 8-core CPU host (PERF.md
    section 6); the chip compiles and runs it in every run of the Tiny V3
    cells, and `_carried_sorts` holds the lowered program to it. What the
    compiled asserts read (custom calls, the dense aggregate's branches,
    lane picks, row loops, memory) lies outside that sort."""
    monkeypatch.setattr(pallas_tiled, "SORT_PAYLOAD_WIDTHS", frozenset())
    return _adagrad_step(cfg, batch, one_chip, monkeypatch).compile()


def _carried_sorts(text, n, width):
    """(the operand count of each sort in a lowered program, its gathers
    whose source is an `[n, width]` f32 stream: a permutation gather of
    the contributions, as `stablehlo.gather` or as the call that holds
    it)."""
    sorts = [args.count("%") for args in
             re.findall(r'"stablehlo\.sort"\(([^)]*)\)', text)]
    gathers = [line for line in text.splitlines()
               if ('"stablehlo.gather"' in line or "call @_take" in line)
               and f": (tensor<{n}x{width}xf32>," in line]
    return sorts, gathers


def _tiny_v3_adagrad_step(one_chip, monkeypatch):
    """The program `chip_smoke.py` trains: Tiny V3 as published, sparse
    Adagrad, batch 65,536, donated."""
    from distributed_embeddings_tpu.models.synthetic import SYNTHETIC_MODELS

    cfg = SYNTHETIC_MODELS["tiny"]
    # as the chip is given it (lowered): the width-16 bucket, fed by two
    # exchange groups, sorts its stream afresh in the update, the step's
    # one sort, carrying the 16 contribution columns; no permutation
    # gather reads them (it took 65 ms of the step: PERF.md section 5)
    lowered = _adagrad_step(cfg, BATCH, one_chip, monkeypatch).as_text()
    assert _carried_sorts(lowered, BUCKET_IDS, 16) == ([17], [])
    # compiled, with the payload gathered (`_compile_adagrad_step`): the
    # asserts below read the kernels, the layout and the memory
    compiled = _compile_adagrad_step(cfg, BATCH, one_chip, monkeypatch)
    # the width-16 bucket's apply is the Pallas tile stream (ISSUE 33), the
    # width-8 bucket's dense aggregate the resident kernel (ISSUE 41): no
    # other kernel is on this step's path
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    _dense_aggregate_is_the_kernels(text)
    # both narrow buckets' streams run feature-major (ISSUE 39): the parent
    # held 15 lane-picking fusions and 8 such loops here
    assert not _lane_picks(text, count_rows=DENSE_ROWS)
    assert not _pathless_row_loops(text)
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    tables = sum(r * w for c in cfg.embedding_configs
                 for r, w in [(c.num_rows, c.width)] * c.num_tables) * 4
    # tables + accumulators are arguments, and all of them are donated
    assert m.argument_size_in_bytes >= 2 * tables
    assert m.alias_size_in_bytes >= 2 * tables
    # the gather's padded `[n, 16]` rows (1.47 GB) count here where the
    # chip holds the carried sort's operands (0.2 GB): 8.53 against 8.54
    # GiB of peak on the chip (PERF.md section 6)
    assert live < HBM_BYTES, (
        f"Tiny V3 step needs {live / 2**30:.2f} GiB of a 16 GB chip")


def _fresh_tile_stream(one_chip, monkeypatch):
    """`tiled_adagrad` over a fresh sort of 65,536 slots, as a bucket that
    two exchange groups feed calls it, lowered for the described chip: its
    one sort carries the 16 contribution columns (17 operands) and no
    gather reads them, so the permutation gather cannot come back under
    another name. Lowered and not compiled (`_compile_adagrad_step`). With
    the payload gathered the same reading finds the sort of (key, slot)
    and the gather, so it is known to see them."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state, n = S((BUCKET_ROWS, 16), F32), BATCH

    def lowered():
        return jax.jit(
            lambda t, a, i, c: pallas_tiled.tiled_adagrad(
                t, a, i, c, 0.01, eps=1e-7, interpret=False),
            donate_argnums=(0, 1)).lower(
                state, state, S((n,), I32), S((n, 16), F32)).as_text()

    assert _carried_sorts(lowered(), n, 16) == ([17], [])
    monkeypatch.setattr(pallas_tiled, "SORT_PAYLOAD_WIDTHS", frozenset())
    sorts, gathers = _carried_sorts(lowered(), n, 16)
    assert sorts == [2] and len(gathers) >= 1


# Tiny V3's width-8 bucket as a chip holds it: 41 id slots a sample
DENSE_ROWS, DENSE_IDS = 60_160, 41 * BATCH


def _computations(text):
    """{name: body} of a compiled module's computations."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?\{\n(.*?)^\}", text, re.M | re.S)}


def _dense_aggregate_is_the_kernels(text):
    """The width-8 bucket's aggregate in the compiled Tiny V3 step (ISSUE
    41): one `conditional` under `det.dedup` whose one branch is the
    resident kernel, fed the stream and handing on the target as bitcasts
    (no copy, pad, sort or scatter beside it), and whose other keeps XLA's
    scatter-add into `f32[60160,9]` with the padded copy of the stream and
    the sort the compiler makes of 2,686,976 ids. The parent held those
    three on the step's own path, 45 ms of it."""
    conds = [line for line in text.splitlines() if " conditional(" in line]
    assert len(conds) == 1 and "det.dedup" in conds[0]
    kernel, scatter = [], []
    for name, body in _computations(text).items():
        if (f"f32[8,{DENSE_IDS}]" in body and "tpu_custom_call" in body):
            kernel.append(name)
            assert not re.search(r" (copy|pad|sort|scatter)\(", body), name
        if re.search(rf"f32\[{DENSE_IDS},9\][^ ]* pad\(", body):
            scatter.append(name)
            assert re.search(rf"s32\[{DENSE_IDS}\][^ ]*\) sort\(", body)
    assert len(kernel) == 1 and len(scatter) == 1
    # each a branch of the conditional, and neither the step's own path
    branches = re.search(r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}",
                         conds[0]).groups()
    assert sorted(branches) == sorted(kernel + scatter)
    entry = re.search(r"^ENTRY .*?^\}", text, re.M | re.S).group(0)
    assert f"[{DENSE_IDS},9]" not in entry
    assert not re.search(rf"s32\[{DENSE_IDS}\][^ ]*\) sort\(", entry)


def _tiny_v3_dense_sum(one_chip):
    """`_dense_sum`'s resident kernel at the bucket's real shape with the
    walk in front of it, as `_dense_sum` calls it: it compiles, the
    stream goes in and the `[rows, 8]` target comes out as bitcasts (a
    copy of the stream would be 86 MB a step), and it keeps nothing but
    the walk's ids beside its arguments."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    chunk, tile = pallas_tiled.dense_sum_blocks(DENSE_ROWS, 8)

    def aggregate(ids, contribs):
        kids, lo, hi, _ = pallas_tiled.dense_sum_walk(ids, DENSE_ROWS,
                                                      chunk, tile)
        return pallas_tiled.dense_sum(kids, lo, hi, contribs, DENSE_ROWS,
                                      tile, interpret=False)

    compiled = jax.jit(aggregate).lower(
        S((DENSE_IDS,), I32), S((DENSE_IDS, 8), F32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r" (copy|pad|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 25


def _widest_dense_sum(one_chip, width):
    """The resident kernel at a lane width that no cell has, over the most
    rows `dense_sum_blocks` lets in at that width: the target's two
    buffers AND a pair's operands, which grow with the width, fit the
    chip's fast memory (the compiler refuses a kernel that overflows its
    16 MiB), and one more tile of rows is refused by the rule."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tile = pallas_tiled.dense_sum_blocks(9_000, width)[1]
    rows = pallas_tiled._DENSE_SUM_BYTES_MAX // (4 * (width + 8) * tile) * tile
    assert pallas_tiled.dense_sum_blocks(rows, width) == (1024, tile)
    assert pallas_tiled.dense_sum_blocks(rows + 1, width) is None
    n = 64 * 1024 + 100

    def aggregate(ids, contribs):
        kids, lo, hi, _ = pallas_tiled.dense_sum_walk(ids, rows, 1024, tile)
        return pallas_tiled.dense_sum(kids, lo, hi, contribs, rows, tile,
                                      interpret=False)

    compiled = jax.jit(aggregate).lower(
        S((n,), I32), S((n, width), F32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1


def _narrow_bucket_step(one_chip, monkeypatch):
    """A narrow bucket's step, two exchange groups (hotness 1 and 10),
    batch 1,024: with the id stream feature-major (ISSUE 39) the batch
    stays on the lanes from the gather to the model and back. Batch-major,
    the compiler put the slot on the lanes: it padded a group's outputs to
    `[1, B, f, 16]` with the 24 features on 128 lanes, picked each input
    out of it with a `slice_reduce_fusion` a lane at a time, and (at Tiny
    V3's size) re-tiled every group's `[1, w, n]` rows in `while` loops of
    its own, which carry no source path. The timing cannot be asked about
    on a CPU; the compiled text can. Forced batch-major the same step
    shows the fusions, so the count below is known to see them."""
    from distributed_embeddings_tpu.models.synthetic import (
        EmbeddingConfig, ModelConfig)
    from distributed_embeddings_tpu.ops import sparse_update

    batch = 1024
    cfg = ModelConfig(
        "narrow", [EmbeddingConfig(2, [1, 10], 200000, 16, True),
                   EmbeddingConfig(22, [1], 100000, 16, False)],
        [256, 128], 10, None)

    def compiled_text():
        return _compile_adagrad_step(cfg, batch, one_chip,
                                     monkeypatch).as_text()

    assert sparse_update.feature_major_stream(16, batch)
    text = compiled_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not _lane_picks(text)
    assert not _pathless_row_loops(text)
    monkeypatch.setattr(sparse_update, "feature_major_stream",
                        lambda width, batch: False)
    assert _lane_picks(compiled_text())


# Tiny V3's width-16 bucket as a chip holds it (PERF.md section 4): the one
# shape a cell runs a Pallas kernel at
BUCKET_ROWS, BUCKET_IDS = 70_200_320, 2_883_584


def _tiny_v3_bucket_stream(one_chip):
    """`tiled_adagrad` at the bucket's real shape behind a caller's sort
    (`presorted`), the branch a bucket that ONE exchange group feeds takes
    with the forward's folded sort (the Tiny V3 step's own width-16
    bucket, fed by two groups, sorts its stream afresh in the update: its
    one sort is `det.contrib/det.apply/det.dedup`), donated as the step
    donates it: it compiles (row-major blocks made the compiler ask for a
    36 GB copy of the table, its 16 lanes padded to 128), in
    place, and what it keeps beside its arguments is the permuted and the
    padded stream."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = S((BUCKET_ROWS, 16), F32)
    ids = S((BUCKET_IDS,), I32)
    compiled = jax.jit(
        lambda t, a, i, c, s, p: pallas_tiled.tiled_adagrad(
            t, a, i, c, 0.01, eps=1e-7, interpret=False, presorted=(s, p)),
        donate_argnums=(0, 1)).lower(
            state, state, ids, S((BUCKET_IDS, 16), F32), ids, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.5 * 2 ** 30
    assert m.alias_size_in_bytes >= 2 * BUCKET_ROWS * 16 * 4


@pytest.mark.parametrize("backend,rows,width,n,want", [
    ("tpu", BUCKET_ROWS, 16, BUCKET_IDS, "pallas"),
    ("tpu", BUCKET_ROWS, 128, BUCKET_IDS, "xla"),       # a row-major table
    ("cpu", BUCKET_ROWS, 16, BUCKET_IDS, "xla"),
    # 33,203,125 chunks of ids alone pass the scalar memory's 130,000 pairs
    ("tpu", BUCKET_ROWS, 16, 8_500_000_000, "xla"),
    ("tpu", 2 ** 31 - 10 ** 6, 16, BUCKET_IDS, "xla"),  # 262,023 tiles of 8192
])
def test_tile_stream_selection(backend, rows, width, n, want, monkeypatch):
    """The rule that hands adagrad's sort branch to the tile stream, by
    what the code sees: a TPU, a table stored column-major, a pair walk
    that fits the chip's scalar memory. Needs no chip described; it is
    here because the bound it asserts is the one the case above compiles
    under."""
    from distributed_embeddings_tpu.ops import sparse_update
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sparse_update.active_scatter_impl(
        "auto", kind="adagrad", rows=rows, width=width, n=n) == want


@pytest.mark.parametrize(
    "kernel,width",
    [(k, w) for k in KERNELS for w in (16, 128)]
    + [("tiny_v3_step", None), ("tiny_v3_bucket_stream", 16),
       ("fresh_tile_stream", 16),
       ("tiny_v3_dense_sum", 8), ("narrow_bucket_step", 16),
       ("widest_dense_sum", 16), ("widest_dense_sum", 32),
       ("widest_dense_sum", 64), ("widest_dense_sum", 96),
       ("widest_dense_sum", 104)],
    ids=lambda v: str(v))
def test_compiles_for_described_v5e(kernel, width, one_chip, monkeypatch):
    if kernel == "tiny_v3_step":
        _tiny_v3_adagrad_step(one_chip, monkeypatch)
        return
    if kernel == "tiny_v3_bucket_stream":
        _tiny_v3_bucket_stream(one_chip)
        return
    if kernel == "fresh_tile_stream":
        _fresh_tile_stream(one_chip, monkeypatch)
        return
    if kernel == "tiny_v3_dense_sum":
        _tiny_v3_dense_sum(one_chip)
        return
    if kernel == "widest_dense_sum":
        _widest_dense_sum(one_chip, width)
        return
    if kernel == "narrow_bucket_step":
        _narrow_bucket_step(one_chip, monkeypatch)
        return

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_it():
        fn, args = KERNELS[kernel](S, width)
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()

    if (kernel, width) in REFUSED:
        with pytest.raises(ValueError, match=f"width {width}"):
            compile_it()
    else:
        compile_it()
