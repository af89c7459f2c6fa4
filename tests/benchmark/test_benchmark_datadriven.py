"""A later PR adds a cell and a metric as files and entries, and edits
nothing that is there: a scratch configuration, traffic mix, reader and
layer metric dropped into a copy of the benchmark are found by name. So are
a model that is no click model (a next-token model under adam with its depth
and its vocabulary cut), its plain reference, its loss and its generator:
by the harness, by the copy's own generic tests (what the driver runs over
every cell) and by `describe_chip`."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

CONFIG = {
    "name": "scratch-model", "source": "https://example.org/scratch",
    "builder": "synthetic", "sample_unit": "example",
    "embedding_configs": [
        {"num_tables": 2, "nnz": [1, 3], "num_rows": 500, "width": 8,
         "shared": True},
        {"num_tables": 3, "nnz": [1], "num_rows": 100, "width": 8,
         "shared": False}],
    "mlp_sizes": [16], "num_numerical_features": 3, "interact_stride": None,
    "global_batch": 32,
    "optimizer": {"kind": "adagrad", "lr": 0.01,
                  "initial_accumulator_value": 0.1, "eps": 1e-07},
    "placement": "memory_balanced", "numerical_scale": 1.0,
    "matmul_precision": "highest", "sync_every": 2, "trace_steps": 5,
    "reduced": [], "rehearse": {"table_scale": 1.0, "global_batch": 32}}
READER = '''"""Steps the traced window ran: a count, so a CPU run can show it."""


def read(ctx, params):
    return ctx.steps * params["times"]
'''

# ---- a next-token model small enough for the CPU: one [T] one-hot input
# into a DistributedEmbedding table, `num_hidden_layers` dense layers, an
# untied head over the table's rows, softmax cross-entropy against the next
# id, counted where the next id belongs to the same document. Its depth and
# its vocabulary are cut, as a language model's are for one chip
# (model-configs, section 4), with the published counts beside them
LM_CONFIG = {
    "name": "scratch-lm", "source": "https://example.org/scratch-lm",
    "builder": "scratch_lm", "sample_unit": "token",
    "vocab_size": 512, "vocab_size_published": 4096,
    "num_hidden_layers": 2, "num_hidden_layers_published": 8,
    "hidden_size": 16, "intermediate_size": 32, "tokens_per_step": 256,
    "optimizer": {"kind": "adam", "lr": 0.001, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-08},
    "matmul_precision": "highest", "sync_every": 2, "trace_steps": 4,
    "reduced": ["num_hidden_layers", "vocab_size"], "rehearse": {}}
LM_TRAFFIC = {"generator": "scratch_tokens", "num_batches": 3, "skew": 2.0,
              "mean_document": 32}
LM_BUILDER = '''"""Scratch: configuration file -> a next-token model on the repo's public
training path, ``make_sparse_train_step(model, "adam")``."""

import jax
import jax.numpy as jnp

from benchmark.harness.built import Built, mlp_train_flops


class NextToken:
    def __init__(self, vocab, width, hidden, layers, mesh):
        from distributed_embeddings_tpu.layers.dist_model_parallel import (
            DistributedEmbedding)
        from distributed_embeddings_tpu.layers.embedding import Embedding

        self.sizes = vocab, width, hidden, layers
        self.embedding = DistributedEmbedding([Embedding(vocab, width)],
                                              mesh=mesh)

    def init(self, key):
        vocab, width, hidden, layers = self.sizes
        ke, kh, *kd = jax.random.split(key, 2 + layers)
        dims = [width] + [hidden] * layers
        return {"embedding": self.embedding.init(ke),
                "dense": [{"w": jax.random.normal(k, (a, b)) / a ** 0.5,
                           "b": jnp.zeros(b)}
                          for k, a, b in zip(kd, dims[:-1], dims[1:])],
                "head": jax.random.normal(kh, (hidden, vocab)) / hidden ** 0.5}

    def loss_fn(self, params, same_document, cats, next_ids, taps=None,
                return_residuals=False):
        (x,), res = self.embedding(params["embedding"], list(cats), taps=taps,
                                   return_residuals=True)
        for layer in params["dense"]:
            x = jnp.maximum(x @ layer["w"] + layer["b"], 0.0)
        logits = x @ params["head"]
        nll = (jax.nn.logsumexp(logits, axis=1)
               - jnp.take_along_axis(logits, next_ids[:, None], axis=1)[:, 0])
        loss = jnp.sum(nll * same_document) / jnp.sum(same_document)
        return (loss, res) if return_residuals else loss


def build(config, mesh, rehearse):
    from distributed_embeddings_tpu.training import make_sparse_train_step

    vocab, width, hidden, layers = (
        config["vocab_size"], config["hidden_size"],
        config["intermediate_size"], config["num_hidden_layers"])
    model = NextToken(vocab, width, hidden, layers, mesh)
    opt = config["optimizer"]
    return Built(
        model=model,
        make_step=lambda: make_sparse_train_step(model, opt["kind"],
                                                 lr=opt["lr"]),
        tables=[(vocab, width)], table_map=[0], hotness=[1],
        num_numerical=0, numerical_scale=0.0,
        global_batch=config["tokens_per_step"], optimizer=opt,
        reference="scratch_lm",
        dense_params=lambda p: {"dense": p["dense"], "head": p["head"]},
        mlp_flops_per_sample=mlp_train_flops(
            [width] + [hidden] * layers + [vocab]),
        ids_1d=True, mesh=mesh)
'''
LM_REFERENCE = '''"""Scratch: the next-token model's forward and loss, plainly. ``inputs``
is ``[T]`` f32, 1 where the next id belongs to the same document; ``labels``
is ``[T]`` int32, the next ids."""

import jax.numpy as jnp

from benchmark.reference import mlp

LABEL_OFFSET = 0


def loss(dense, embs, inputs, labels):
    (x,) = embs
    logits = mlp(dense["dense"], x, final_activation=True) @ dense["head"]
    top = jnp.max(logits, axis=1)
    log_sum = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=1))
    labels = jnp.roll(labels, LABEL_OFFSET)
    nll = log_sum - logits[jnp.arange(labels.shape[0]), labels]
    return jnp.sum(nll * inputs) / jnp.sum(inputs)
'''
LM_GENERATOR = '''"""Scratch: packed documents of skewed token ids. Returns per batch
(same-document mask [T] f32, [ids [T, 1] int32], next ids [T] int32)."""

import numpy as np


def generate(traffic, inputs, batch, num_numerical, numerical_scale, seed):
    ((rows, _),) = inputs
    rng = np.random.RandomState(seed)
    batches = []
    for _ in range(int(traffic["num_batches"])):
        ids = (rows * rng.rand(batch + 1) ** float(traffic["skew"])
               ).astype(np.int32)
        ends = rng.rand(batch) < 1.0 / float(traffic["mean_document"])
        batches.append(((~ends).astype(np.float32), [ids[:-1, None]],
                        ids[1:].copy()))
    return batches
'''


def _copy_of_the_benchmark(tmp_path):
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}


def _rehearse(tmp_path, bench, workload, trace):
    """Run the copy's harness on its BENCHMARK.json; the output's lines."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {k: v for k, v in os.environ.items() if not k.startswith("DET_")}
    env["PYTHONPATH"] = spec.ROOT          # the system under test only
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", "1", "--seconds", "0.1", "--trace", str(trace),
         "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.strip().splitlines()


def _line(lines, tag):
    return json.loads(next(ln for ln in lines if ln.startswith(
        tag + " ")).split(" ", 1)[1])


def test_scratch_cell_and_metric_are_picked_up_by_name(tmp_path):
    before = _copy_of_the_benchmark(tmp_path)
    (tmp_path / "benchmark/configs/scratch-model.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "benchmark/traffic/mild-skew.json").write_text(json.dumps(
        {"generator": "power_law", "alpha": 0.5, "num_batches": 3}))
    (tmp_path / "benchmark/readers/window_steps.py").write_text(READER)
    (tmp_path / "benchmark/layer_metrics/scratch.steps_x10.json").write_text(
        json.dumps({"reader": "window_steps", "times": 10}))
    bench = spec.load_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "scratch-model", "source": CONFIG["source"],
        "file": "benchmark/configs/scratch-model.json", "reduced": [],
        "why": "scratch"})
    bench["workloads"].append({
        "name": "scratch-model.mild", "config": "scratch-model",
        "traffic": "mild-skew", "chips": 1, "why": "scratch"})
    bench["per_layer"].append({
        "name": "scratch.steps_x10", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train_step",
        "moves": "samples_per_s", "workloads": ["scratch-model.mild"]})
    lines = _rehearse(tmp_path, bench, "scratch-model.mild", 1)
    check = _line(lines, "REFERENCE_CHECK")
    assert check["ok"] is True and check["probed_tables"] == 5
    assert _line(lines, "REHEARSED_LAYER_METRICS") == {
        "scratch.steps_x10": {"value": 50, "unit": "steps"}}
    assert json.loads(lines[-1])["attempted"] == 5
    # nothing that was there was edited
    assert all(p.read_bytes() == data for p, data in before.items())


def test_the_four_chip_cell_comes_back_by_entries_alone(tmp_path):
    """PR 23 could not keep `dlrm-mlperf.zipf-4chip` (its configuration's
    file says why). Its files are all there: the PR that makes it fit adds
    three entries to BENCHMARK.json and no code. Rehearsed on four virtual
    devices: the mesh, the staging, the export and the check over shards."""
    before = _copy_of_the_benchmark(tmp_path)
    held = spec.load_json("benchmark/configs/dlrm-mlperf-4chip.json")
    bench = spec.load_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "dlrm-mlperf-4chip", "source": held["source"],
        "file": "benchmark/configs/dlrm-mlperf-4chip.json",
        "reduced": held["reduced"], "why": "the 4/16 share on a 2x2 mesh"})
    bench["workloads"].append({
        "name": "dlrm-mlperf.zipf-4chip", "config": "dlrm-mlperf-4chip",
        "traffic": "zipf-1.05", "chips": 4, "why": "exchanges"})
    bench["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower",
         "source": "device_trace", "layer": "exchange",
         "moves": "samples_per_s", "workloads": ["dlrm-mlperf.zipf-4chip"]}
        for name, unit in (("exchange.device_ms", "ms"),
                           ("exchange.exposed_share", "%"))]
    lines = _rehearse(tmp_path, bench, "dlrm-mlperf.zipf-4chip", 1)
    check = _line(lines, "REFERENCE_CHECK")
    assert check["ok"] is True and check["probed_tables"] == 26
    assert check["touched_rows_moved"] > 0 and check["untouched_rows"] > 0
    last = json.loads(lines[-1])
    assert last["device"]["count"] >= 4 and last["failed"] == 0
    assert last["attempted"] == held["trace_steps"]
    # no chip, so nothing to read: the exchange's readers return nothing
    assert _line(lines, "REHEARSED_LAYER_METRICS") == {}
    assert all(p.read_bytes() == data for p, data in before.items())


def _add_the_token_cell(tmp_path, label_offset=0):
    """The next-token model's files into the copy (none may be there), and
    BENCHMARK.json with its three entries: the configuration with its depth
    and vocabulary cut, the cell, a per-layer metric listed for it alone."""
    for path, text in (
            ("builders/scratch_lm.py", LM_BUILDER),
            ("references/scratch_lm.py", LM_REFERENCE.replace(
                "LABEL_OFFSET = 0", f"LABEL_OFFSET = {label_offset}")),
            ("generators/scratch_tokens.py", LM_GENERATOR),
            ("configs/scratch-lm.json", json.dumps(LM_CONFIG)),
            ("traffic/scratch-docs.json", json.dumps(LM_TRAFFIC)),
            ("readers/window_steps.py", READER),
            ("layer_metrics/scratch.steps_x10.json",
             json.dumps({"reader": "window_steps", "times": 10}))):
        assert not (tmp_path / "benchmark" / path).exists()
        (tmp_path / "benchmark" / path).write_text(text)
    bench = spec.load_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "scratch-lm", "source": LM_CONFIG["source"],
        "file": "benchmark/configs/scratch-lm.json",
        "reduced": LM_CONFIG["reduced"], "why": "scratch"})
    bench["workloads"].append({
        "name": "scratch-lm.docs", "config": "scratch-lm",
        "traffic": "scratch-docs", "chips": 1, "why": "scratch"})
    bench["per_layer"].append({
        "name": "scratch.steps_x10", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train_step",
        "moves": "samples_per_s", "workloads": ["scratch-lm.docs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


@pytest.mark.parametrize("label_offset, outcome", [(0, None), (1, "(b)")])
def test_a_next_token_model_under_adam_by_new_files_alone(
        tmp_path, label_offset, outcome):
    """Builder, plain reference (forward and softmax cross-entropy over the
    vocabulary), generator (int32 [T] labels), configuration
    (`sample_unit: token`, adam: so three held steps; `num_hidden_layers`
    and `vocab_size` reduced), traffic and three entries: the check holds
    the system to the reference's loss and adam rule. With the reference's
    labels one position off, the loss (b) fails."""
    before = _copy_of_the_benchmark(tmp_path)
    bench = _add_the_token_cell(tmp_path, label_offset)
    lines = _rehearse(tmp_path, bench, "scratch-lm.docs", 0)
    check = _line(lines, "REFERENCE_CHECK")
    if outcome is None:
        assert check["ok"] is True, check
        assert len(check["loss"]) == 3 and check["probed_tables"] == 1
        assert check["touched_rows_moved"] > 0 and check["untouched_rows"] > 0
        assert check["row_err_over_tolerance_max"] <= 1.0
    else:
        assert check["ok"] is False and check["error"].startswith(outcome)
        assert (check["compared"]["loss0_off"][0]
                > check["compared"]["loss0_off"][1])
    last = json.loads(lines[-1])
    assert last["failed"] == 0 and last["attempted"] > 0
    assert all(p.read_bytes() == data for p, data in before.items())


def _copy_of_a_checkout(tmp_path):
    """The benchmark's two directories copied, what else a checkout holds
    for them (the system under test, the example the DLRM share is held to)
    linked -> the copied files' bytes."""
    before = _copy_of_the_benchmark(tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "tests", "benchmark"),
                    tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before.update({p: p.read_bytes()
                   for p in (tmp_path / "tests").rglob("*") if p.is_file()})
    for name in ("distributed_embeddings_tpu", "examples"):
        os.symlink(os.path.join(spec.ROOT, name), tmp_path / name)
    return before


def _in_the_copy(tmp_path, *argv, timeout=900):
    env = {k: v for k, v in os.environ.items() if not k.startswith("DET_")}
    env.update(PYTHONPATH=str(tmp_path), JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_the_copys_own_tests_hold_the_token_cell(tmp_path):
    """What the driver runs on every PR: the copy's own `tests/benchmark`
    over the cell it gained. The contract's tests take a configuration whose
    `reduced` names `num_hidden_layers` (a count, not a width) beside its
    published value, and the rehearsal's case of the new cell expects the
    losses the check holds under adam: `loss0_off`, `loss1_off`,
    `loss2_off`. PRs 29 and 30 each lost a cell that ran `correct` on the
    chip to these two tests (PERF.md section 6, PR 34)."""
    before = _copy_of_a_checkout(tmp_path)
    _add_the_token_cell(tmp_path)
    done = _in_the_copy(
        tmp_path, "-m", "pytest", "-q", "-p", "no:cacheprovider",
        "tests/benchmark/test_benchmark_contract.py",
        "tests/benchmark/test_benchmark_rehearse.py",
        "-k", "test_benchmark_contract or scratch-lm.docs")
    assert done.returncode == 0, done.stdout[-6000:] + done.stderr[-2000:]
    # the contract's file whole and the one rehearsal: none skipped or lost
    ran = done.stdout.strip().splitlines()[-1]
    assert " passed" in ran and "skipped" not in ran, ran
    assert all(p.read_bytes() == data for p, data in before.items())


# ---- `describe_chip` describes the cell's own batch (PR 34). A topology
# may be described in one test file alone (`tests/test_chip_compile.py`:
# one process at a time holds the TPU's library), so here the local CPU
# devices stand in for the described ones: the shapes the tool lowers, the
# handle it lowers through and what it prints are the same code
STAND_IN = (
    "import sys, types\n"
    "import jax\n"
    "from jax.experimental import topologies\n"
    "topologies.get_topology_desc = lambda **kw: types.SimpleNamespace(\n"
    "    devices=jax.devices('cpu'))\n"
    "from benchmark.tools import describe_chip\n"
    "sys.exit(describe_chip.main(['--workload', sys.argv[1]]))\n")


def _described(tmp_path, workload):
    done = _in_the_copy(tmp_path, "-c", STAND_IN, workload)
    assert done.returncode == 0, done.stderr[-2000:]
    said = json.loads(done.stdout.strip().splitlines()[-1])
    assert said["workload"] == workload and said["devices"] == 1
    return said["per_device_GiB"]


def test_describe_chip_lowers_the_cells_own_batch(tmp_path):
    """The token cell's `[T]` f32 boundaries and `[T]` int32 next ids, with
    no edit to the tool; and `dlrm-mlperf.zipf` as before: these three are
    what the parent's tool prints under the same stand-in (my CPU run,
    PR 34; on the described v5e both print arguments 5.6596, temporaries
    5.7809, live 11.4405 GiB)."""
    before = _copy_of_a_checkout(tmp_path)
    _add_the_token_cell(tmp_path)
    token = _described(tmp_path, "scratch-lm.docs")
    assert set(token) == {"arguments", "outputs", "aliased", "temporaries",
                          "live"}
    # table, two dense layers and head, with adam's two moments of each;
    # 256 ids, next ids and boundaries of 4 bytes
    floats = 3 * (512 * 16 + 16 * 32 + 32 + 32 * 32 + 32 + 32 * 512)
    assert floats * 4 + 3 * 256 * 4 <= token["arguments"] * 2 ** 30 \
        <= floats * 4 * 1.05
    assert 0 < token["aliased"] <= token["outputs"]
    dlrm = _described(tmp_path, "dlrm-mlperf.zipf")
    assert (dlrm["arguments"], dlrm["outputs"], dlrm["aliased"]) == (
        5.65950633212924, 5.658896133303642, 5.65889598056674)
    assert all(p.read_bytes() == data for p, data in before.items())
