"""BENCHMARK.json against the contract it is checked by, the cells' data
files against the sources they name, and the runs that must be refused."""

import importlib.util
import math
import os
import re
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.harness import spec

ROOT = spec.ROOT
BENCH = spec.load_json("BENCHMARK.json")
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# `reduced` never names a width (model-configs, section 4; the driver's
# contract): a hidden, intermediate, latent, state, head or projection size,
# a window, an expansion factor, the experts per token, a key that ends in
# `_dim` or `_rank`. A count (layers, experts held, rows of a vocabulary or
# a table, the batch) may be the chip's share
WIDTH = re.compile(
    r"(_dim|_rank)$|width|window|expan|experts_per_tok"
    r"|(hidden|intermediate|latent|state|head|proj\w*)_size")
# reduced keys whose published value stands elsewhere in today's files, which
# this test does not edit: dlrm-mlperf's `exchange_peers` (1 or 4) is cut
# from `deployment.chips` (16), and `test_dlrm_share_follows_its_rule` holds
# the two together
PUBLISHED_ELSEWHERE = {"exchange_peers": ("deployment", "chips")}


def _under_paths(path):
    return any(path == p or path.startswith(p + "/") for p in BENCH["paths"])


def test_keys_names_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert not any(a.startswith("/") or ".." in a for a in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
               for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(len(e["why"]) <= 200
               for e in BENCH["configs"] + BENCH["workloads"])


def test_every_file_under_paths_has_a_plain_name():
    for top in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


def test_configs_and_cells():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert _under_paths(c["file"])
        held = spec.load_json(c["file"])
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            # what the guide asks of a cut: the published value beside it
            if key in PUBLISHED_ELSEWHERE:
                group, inner = PUBLISHED_ELSEWHERE[key]
                assert inner in held[group], (c["name"], key)
            elif key in held:
                assert key + "_published" in held, (c["name"], key)
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert {w["config"] for w in cells} == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        cell = spec.load_cell(w["name"])
        assert cell.traffic["generator"] and cell.config["builder"]
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        assert all(m["moves"] in reported for m in cell.per_layer)


@pytest.mark.parametrize("key, is_a_width", [
    ("hidden_size", True), ("head_dim", True), ("kv_lora_rank", True),
    ("moe_intermediate_size", True), ("sliding_window", True),
    ("embedding_dim", True), ("table_width", True),
    ("num_experts_per_tok", True), ("ssm_state_size", True),
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("vocab_size", False), ("table_rows", False), ("global_batch", False),
    ("exchange_peers", False)])
def test_reduced_may_name_a_count_and_never_a_width(key, is_a_width):
    assert bool(WIDTH.search(key)) is is_a_width


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
        assert m["better"] in {"higher", "lower"}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert LAYER.match(m["layer"]), m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        params = spec.load_json("benchmark", "layer_metrics",
                                m["name"] + ".json")
        assert callable(spec.plugin("readers", params["reader"]).read)


def test_tiny_v3_is_the_reference_config_verbatim():
    from distributed_embeddings_tpu.models.synthetic import SYNTHETIC_MODELS

    held = spec.load_json("benchmark/configs/synthetic-tiny-v3.json")
    want = SYNTHETIC_MODELS["tiny"]
    assert [tuple(c.values()) for c in held["embedding_configs"]] == [
        tuple(c) for c in want.embedding_configs]
    assert held["mlp_sizes"] == want.mlp_sizes
    assert held["num_numerical_features"] == want.num_numerical_features
    assert held["interact_stride"] == want.interact_stride
    assert held["reduced"] == [] and held["global_batch"] == 65536


@pytest.mark.parametrize("name", ["dlrm-mlperf", "dlrm-mlperf-4chip"])
def test_dlrm_share_follows_its_rule(name):
    path = os.path.join(ROOT, "examples", "dlrm", "main.py")
    example = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location("dlrm_example", path))
    held = spec.load_json(f"benchmark/configs/{name}.json")
    sys_path = list(sys.path)
    try:
        example.__spec__.loader.exec_module(example)
    finally:
        sys.path[:] = sys_path
    assert held["table_rows_published"] == example.CRITEO_TABLE_SIZES
    # dlrm-mlperf-4chip is no cell yet (its file says why): its chips are
    # its exchange's peers; a configuration with a cell runs on that many
    chips, of = held["exchange_peers"], held["deployment"]["chips"]
    assert all(w["chips"] == chips for w in BENCH["workloads"]
               if w["config"] == name)
    assert held["table_rows"] == [
        math.ceil(v * chips / of) if v >= held["share_min_rows"] else v
        for v in example.CRITEO_TABLE_SIZES]
    assert held["global_batch"] == 4096 * chips == held["global_batch_published"] * chips // 16
    assert held["exchange_peers"] == chips
    defaults = example.parse_args([])
    assert held["embedding_dim"] == defaults.embedding_dim
    assert held["bottom_mlp_dims"] == [int(x) for x in defaults.bottom_mlp.split(",")]
    assert held["top_mlp_dims"] == [int(x) for x in defaults.top_mlp.split(",")]
    assert held["placement"] == defaults.dist_strategy
    assert held["sync_every"] == defaults.log_every
    sched = held["optimizer"]["lr_schedule"]
    assert sched["base_lr"] == defaults.lr            # published, unscaled
    assert of == 16 and "optimizer" not in held["reduced"]
    assert (sched["warmup_steps"], sched["decay_start_step"],
            sched["decay_steps"]) == (defaults.warmup_steps,
                                      defaults.decay_start_step,
                                      defaults.decay_steps)


def _refused(capsys, argv):
    code = run.main(argv)
    out = capsys.readouterr()
    assert code != 0
    assert out.out == ""            # no result is printed
    return out.err


def test_a_det_variable_is_refused(monkeypatch, capsys):
    monkeypatch.setenv("DET_LOOKUP_PATH", "xla")
    err = _refused(capsys, ["--workload", "dlrm-mlperf.zipf", "--rehearse"])
    assert "DET_LOOKUP_PATH" in err


def test_a_run_that_finds_no_chip_fails(capsys):
    err = _refused(capsys, ["--workload", "dlrm-mlperf.zipf", "--seed", "0",
                            "--seconds", "1", "--trace", "0"])
    assert "no accelerator" in err


def test_an_unknown_workload_is_refused(capsys):
    err = _refused(capsys, ["--workload", "no-such.cell", "--rehearse"])
    assert "no-such.cell" in err


def test_no_topology_is_described_at_import():
    """on-chip-measurement, section 2: one process at a time may load the
    TPU's library, and the suite's workers import every file."""
    modules = []
    for folder, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                rel = os.path.relpath(os.path.join(folder, f), ROOT)[:-3]
                modules.append(rel.replace(os.sep, ".").removesuffix(
                    ".__init__"))
    code = (
        "import importlib, json, sys\n"
        "from jax.experimental import topologies\n"
        "def boom(*a, **k): raise SystemExit('described a topology at import')\n"
        "topologies.get_topology_desc = boom\n"
        f"for m in {sorted(modules)!r}: importlib.import_module(m)\n"
        "import jax\n"
        "assert 'tpu' not in jax._src.xla_bridge._backends, 'a backend started'\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
