"""A later PR adds a cell and a metric as files and entries, and edits
nothing that is there: a scratch configuration, traffic mix, reader and
layer metric dropped into a copy of the benchmark are found by name."""

import json
import os
import shutil
import subprocess
import sys

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
