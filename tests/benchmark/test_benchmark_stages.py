"""The readers of what PR 25 put into the program: device time per stage
scope (`readers/stage_ms.py`) on hand-built windows, on the recorded traces
from before the scopes and on the ones recorded with them
(benchmark/fixtures/README-stages.md), and the step's temporaries through
its handle (`readers/step_temp_gib.py`)."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark.harness import layers, spec, xplane
from benchmark.harness.xplane import Op, Trace
from benchmark.readers import stage_ms, step_temp_gib

STEP = "jit(det_train_step)"
# paths as the compiled step has them (my described-v5e compile, PR 25)
GATHER = f"{STEP}/det.model/jvp(det.lookup)/det.lookup/jit(_take)/gather:"
ACTS_BACK = f"{STEP}/det.model/transpose(jvp(det.acts))/det.acts/mul:"
DEDUP = f"{STEP}/det.contrib/det.apply/det.dedup"
FIXTURES = os.path.join(spec.ROOT, "benchmark", "fixtures")


def _op(name, start, end, path):
    sig = f"name={name} cat=fusion op={path} src=a.py:1".lower()
    return Op(name, float(start), float(end), sig)


def _ctx(ops_per_chip, steps=1):
    trace = Trace({f"/device:TPU:{i}": ops
                   for i, ops in enumerate(ops_per_chip)}, {}, [])
    return layers.Context(xplane.reduce(trace, []), steps, None, None,
                          "TPU v5 lite", None)


def _read(ctx, scope, which="any"):
    return stage_ms.read(ctx, {"scope": scope, "pass": which})


def _hand_built():
    """One chip, ns. A forward gather [0, 100) under lookup inside model; a
    while loop [200, 600) under dedup whose body holds a sort [250, 400)
    under dedup and an unnamed copy [400, 450); the backward of the
    activation exchange [700, 800); an op under model alone [800, 830); an
    op of the program under no scope [900, 920); a copy of the compiler's
    with no path [1000, 1500)."""
    return [
        _op("fusion.1", 0, 100, GATHER),
        _op("while.2", 200, 600, f"{DEDUP}/while:"),
        _op("sort.3", 250, 400, f"{DEDUP}/sort:"),
        _op("copy.4", 400, 450, ""),
        _op("fusion.5", 700, 800, ACTS_BACK),
        _op("fusion.6", 800, 830, f"{STEP}/det.model/jvp()/dot_general:"),
        _op("fusion.7", 900, 920, f"{STEP}/add:"),
        _op("copy.8", 1000, 1500, ""),
    ]


def test_the_innermost_scope_is_the_stage():
    ctx = _ctx([_hand_built()])
    assert _read(ctx, "lookup") == pytest.approx(100e-6)
    assert _read(ctx, "model") == pytest.approx(30e-6)       # not the gather
    assert _read(ctx, "apply") == 0.0                         # dedup took it
    assert _read(ctx, "contrib") == 0.0
    assert stage_ms.stage_of("name=x cat=y op=det.apply/det.dedup/sort: src=") \
        == ("dedup", False)


def test_nested_operations_are_charged_once():
    ctx = _ctx([_hand_built()])
    # the loop's 400 ns less the 50 ns of the copy inside it, which has no
    # path; the sort inside it is dedup's either way
    assert _read(ctx, "dedup") == pytest.approx(350e-6)
    parts = stage_ms.partition(ctx)
    assert parts[stage_ms.NO_PATH, False] == pytest.approx(550e-6)
    assert parts[stage_ms.NO_STAGE, False] == pytest.approx(20e-6)
    assert sum(parts.values()) == pytest.approx(
        ctx.chips[0].busy_ns * 1e-6, rel=1e-12)


def test_forward_and_backward_are_told_apart():
    ctx = _ctx([_hand_built()])
    assert _read(ctx, "acts", "forward") == 0.0
    assert _read(ctx, "acts", "backward") == pytest.approx(100e-6)
    assert _read(ctx, "acts", "any") == pytest.approx(100e-6)
    assert _read(ctx, "lookup", "forward") == pytest.approx(100e-6)
    assert _read(ctx, "lookup", "backward") == 0.0


def test_the_partition_is_noted_once_and_sums_to_the_step():
    ctx = _ctx([_hand_built()], steps=2)
    for scope in ("lookup", "dedup", "apply"):
        _read(ctx, scope)
    (note,) = ctx.notes
    assert note.startswith("stages, ms per step: ")
    held = json.loads(note.split(": ", 1)[1].split(";")[0])
    assert set(held) == {"lookup", "dedup", "acts", "model",
                         stage_ms.NO_STAGE, stage_ms.NO_PATH}
    busy_ms = ctx.chips[0].busy_ns * 1e-6 / 2
    assert sum(held.values()) == pytest.approx(busy_ms, abs=1e-3)
    assert note.endswith(f"sum {busy_ms:.4f} = step.device_ms")


def test_chips_are_averaged_and_steps_divided():
    second = [_op("fusion.1", 0, 300, GATHER)]
    ctx = _ctx([_hand_built(), second], steps=4)
    assert _read(ctx, "lookup") == pytest.approx((100 + 300) / 2 / 4 * 1e-6)


@pytest.mark.parametrize("ops", [
    [[]], [[_op("fusion.1", 0, 10, "jit(step_fn)/jvp()/gather:"),
            _op("copy.2", 10, 20, "")]]],
    ids=["no-chip", "no-scope"])
def test_nothing_is_read_where_there_is_no_scope(ops, capsys):
    ctx = (layers.Context([], 3, None, None, "cpu", None) if ops == [[]]
           else _ctx(ops))
    assert _read(ctx, "lookup") is None and _read(ctx, "dedup") is None
    assert ctx.notes == []
    said = capsys.readouterr().out
    # a rehearsal says nothing; a trace without scopes says so, once
    assert said.count("no operation of this trace lies under a det.* scope") \
        == (0 if ops == [[]] else 1)


def test_the_trace_recorded_before_the_scopes_reads_as_it_did(capsys):
    """`test_layer_metrics_read_from_the_recorded_trace` holds PR 23's DLRM
    steps to exact lists of metrics and notes, with today's BENCHMARK.json:
    the new entries must add nothing there."""
    from benchmark.builders import dlrm

    cell = spec.load_cell("dlrm-mlperf.zipf")
    assert {"lookup.fwd_stage_ms", "update.contrib_stage_ms",
            "update.apply_stage_ms", "step.temp_gib"} <= {
                m["name"] for m in cell.per_layer}
    built = dlrm.build(cell.config, None, False)
    chips = xplane.reduce(xplane.load(os.path.join(
        FIXTURES, "dlrm-mlperf.zipf.3steps.xplane.pb")), xplane.load_classes())
    ctx = layers.Context(chips, 3, built, cell, "TPU v5 lite", 12157459456)
    got = layers.read_all(ctx)
    assert not any("stage" in k or k == "step.temp_gib" for k in got)
    assert len(got) == 9 and len(ctx.notes) == 1
    assert "no operation of this trace lies under" in capsys.readouterr().out


def _tiny_ctx(kind, chips=True):
    from benchmark.builders import synthetic

    cell = spec.load_cell("tiny-v3.zipf")
    built = synthetic.build(cell.config, None, True)      # rehearsal sizes
    ops = [_op("fusion.1", 0, 10, f"{STEP}/det.lookup/gather:")]
    return layers.Context(_ctx([ops]).chips if chips else [], 1, built, cell,
                          kind, None)


def test_temporaries_are_read_only_from_the_traced_kind_of_device():
    here = jax.devices()[0].device_kind
    assert step_temp_gib.read(_tiny_ctx(here, chips=False), {}) is None
    ctx = _tiny_ctx("TPU v5 lite")
    assert here != "TPU v5 lite"
    assert step_temp_gib.read(ctx, {}) is None and ctx.notes == []


def test_temporaries_come_from_the_steps_own_handle(monkeypatch):
    """On the kind of device that was traced (here the CPU stands in for
    it), the number is `memory_analysis()` of `step_fn.lower(...)`: the
    program's own jitted function, not a second `jax.jit` around it."""
    lowered = []
    ctx = _tiny_ctx(jax.devices()[0].device_kind)
    make_step = ctx.built.make_step

    def spy():
        init_fn, step_fn = make_step()
        lower = step_fn.lower
        step_fn.lower = lambda *a: lowered.append(lower(*a)) or lowered[-1]
        return init_fn, step_fn

    jitted, jit = [], jax.jit

    def recording_jit(fun=None, *args, **kwargs):
        jitted.append(getattr(fun, "__name__", None))
        return jit(fun, *args, **kwargs)

    monkeypatch.setattr(ctx.built, "make_step", spy)
    monkeypatch.setattr(jax, "jit", recording_jit)
    got = step_temp_gib.read(ctx, {})
    (low,) = lowered
    # the one jit is the program's, around its own step; none around `run`
    assert jitted.count("det_train_step") == 1 and "run" not in jitted
    assert "jit_det_train_step" in low.as_text()[:200]
    assert got == low.compile().memory_analysis().temp_size_in_bytes / 2 ** 30
    assert 0 < got < 1
    (note,) = ctx.notes
    assert note.startswith("step.temp_gib: det_train_step lowered through")
    assert all(w in note for w in ("arguments", "outputs", "aliased", "live"))


def test_a_step_without_a_handle_gives_nothing(monkeypatch, capsys):
    """The parent's program under this PR's benchmark files."""
    ctx = _tiny_ctx(jax.devices()[0].device_kind)
    monkeypatch.setattr(ctx.built, "make_step",
                        lambda: (None, lambda *a: None))
    assert step_temp_gib.read(ctx, {}) is None and ctx.notes == []
    assert "no `lower` handle" in capsys.readouterr().out


EXCHANGE = {"exchange.ids_stage_ms": ("ids", "any"),
            "exchange.acts_stage_ms": ("acts", "forward"),
            "exchange.grads_stage_ms": ("acts", "backward")}


def test_the_exchange_metrics_are_files_that_wait_for_a_four_chip_cell():
    bench = spec.load_json("BENCHMARK.json")
    assert not {m["name"] for m in bench["per_layer"]} & set(EXCHANGE)
    model = f"{STEP}/det.model"
    ops = [_op("all-to-all.1", 0, 10, f"{model}/jvp(det.ids)/all_to_all:"),
           _op("all-to-all.2", 20, 50,
               f"{model}/jvp(det.acts)/det.acts/all_to_all:"),
           _op("all-to-all.3", 60, 130,
               f"{model}/transpose(jvp(det.acts))/det.acts/all_to_all:")]
    ctx = _ctx([ops, ops])
    want = {"exchange.ids_stage_ms": 10e-6, "exchange.acts_stage_ms": 30e-6,
            "exchange.grads_stage_ms": 70e-6}
    for name, (scope, which) in EXCHANGE.items():
        params = spec.load_json("benchmark", "layer_metrics", name + ".json")
        assert params == {"reader": "stage_ms", "scope": scope, "pass": which}
        assert spec.plugin("readers", params["reader"]).read(
            ctx, params) == pytest.approx(want[name])


def test_the_exchange_metrics_come_in_by_entries_alone(tmp_path):
    """`test_benchmark_datadriven`'s pattern: a copy of the benchmark, the
    four-chip configuration's cell and the three metrics added as entries of
    BENCHMARK.json, nothing edited, rehearsed with a trace on four virtual
    devices. No chip, so the readers are found by name and return nothing."""
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    held = spec.load_json("benchmark/configs/dlrm-mlperf-4chip.json")
    bench = spec.load_json("BENCHMARK.json")
    cell = "dlrm-mlperf.zipf-4chip"
    bench["configs"].append({
        "name": "dlrm-mlperf-4chip", "source": held["source"],
        "file": "benchmark/configs/dlrm-mlperf-4chip.json",
        "reduced": held["reduced"], "why": "the 4/16 share on a 2x2 mesh"})
    bench["workloads"].append({
        "name": cell, "config": "dlrm-mlperf-4chip", "traffic": "zipf-1.05",
        "chips": 4, "why": "exchanges"})
    bench["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "exchange",
         "moves": "samples_per_s", "workloads": [cell]} for name in EXCHANGE]
    for metric in bench["per_layer"]:
        if metric["name"] in ("lookup.fwd_stage_ms", "step.temp_gib"):
            metric["workloads"] = metric["workloads"] + [cell]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {k: v for k, v in os.environ.items() if not k.startswith("DET_")}
    env["PYTHONPATH"] = spec.ROOT
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2", "--seconds", "0.1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert "REHEARSED_LAYER_METRICS {}" in lines
    assert json.loads(lines[-1])["failed"] == 0
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_meshed_step_is_lowered_with_the_plans_shardings():
    """The four-chip configuration at its real size on four virtual
    devices: nothing is allocated, the step is lowered from shapes."""
    from benchmark.builders import dlrm
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    config = spec.load_json("benchmark/configs/dlrm-mlperf-4chip.json")
    built = dlrm.build(config, create_mesh(jax.devices()[:4]), False)
    cell = spec.Cell("four", 4, config, {}, [], [])
    ctx = layers.Context(_ctx([[_op("fusion.1", 0, 10, GATHER)]]).chips, 1,
                         built, cell, jax.devices()[0].device_kind, None)
    got = step_temp_gib.read(ctx, {})
    # per device: a quarter of the padded tables is the arguments' 7.67 GiB
    assert got > 0 and "arguments 7.67" in ctx.notes[0]


# ---- the steps recorded with the scopes (fixtures/README-stages.md)
RECORDED = {
    "dlrm": ("dlrm-mlperf.zipf.stages.3steps.xplane.pb", 3, 47.5582, {
        ("lookup", "forward"): 0.9788, ("lookup", "backward"): 0.0,
        ("contrib", "any"): 0.0, ("apply", "any"): 8.1673,
        ("dedup", "any"): 0.0, ("model", "backward"): 1.1121,
        ("acts", "backward"): 0.0152, ("ids", "any"): 0.0086}),
    "tiny": ("tiny-v3.zipf.stages.1step.xplane.pb", 1, 1232.3996, {
        ("lookup", "forward"): 77.7227, ("lookup", "backward"): 0.0,
        ("contrib", "any"): 3.7622, ("apply", "any"): 655.2172,
        ("dedup", "any"): 438.8053, ("acts", "forward"): 30.9150,
        ("acts", "backward"): 1.7340, ("ids", "any"): 0.7390}),
}


def _recorded(name):
    path, steps, step_ms, want = RECORDED[name]
    chips = xplane.reduce(xplane.load(os.path.join(FIXTURES, path)),
                          xplane.load_classes())
    return layers.Context(chips, steps, None, None, "TPU v5 lite",
                          None), step_ms, want


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_steps_read_per_stage(name):
    ctx, step_ms, want = _recorded(name)
    got = {key: _read(ctx, *key) for key in want}
    assert got == pytest.approx(want, abs=1e-4)
    parts = stage_ms.partition(ctx)
    # every operation with a path lies in a stage, and the parts are the step
    assert not any(stage == stage_ms.NO_STAGE for stage, _ in parts)
    assert sum(parts.values()) == pytest.approx(step_ms, abs=1e-4)
    assert sum(parts.values()) == pytest.approx(
        ctx.chips[0].busy_ns * 1e-6 / ctx.steps, rel=1e-12)
    # what has no path is the compiler's: DLRM's two table-sized copies
    assert parts[stage_ms.NO_PATH, False] == pytest.approx(
        {"dlrm": 36.9567, "tiny": 22.7765}[name], abs=1e-4)


def test_recorded_tiny_step_puts_the_update_where_it_belongs():
    """What the classes by primitive could not say (PERF.md section 5): the
    segment-sum and the permutation gather are dedup's, the accumulator
    re-read is apply's."""
    ctx, _, _ = _recorded("tiny")
    by_name = {}
    for op in ctx.chips[0].ops:
        ms, _ = by_name.get(op.name, (0.0, None))
        by_name[op.name] = (ms + op.self_ns * 1e-6,
                            (op.cls, stage_ms.stage_of(op.signature)[0]))
    assert {k: by_name[k][1] for k in ("fusion.15", "fusion.4", "fusion.5",
                                       "fusion.17", "fusion.18", "fusion.2")} == {
        "fusion.15": ("update", "dedup"),      # segment-sum, 310 ms
        "fusion.4": ("lookup", "dedup"),       # gather by the permutation
        "fusion.5": ("lookup", "apply"),       # accumulator re-read
        "fusion.17": ("update", "apply"), "fusion.18": ("update", "apply"),
        "fusion.2": ("lookup", "lookup")}      # a forward gather
    assert by_name["fusion.15"][0] == pytest.approx(310.22, abs=0.01)
    assert by_name["fusion.4"][0] == pytest.approx(64.33, abs=0.01)
    assert by_name["fusion.5"][0] == pytest.approx(95.58, abs=0.01)
    # the sorts alone are what `dedup.device_ms` reads
    assert ctx.chips[0].class_ns["dedup"] * 1e-6 == pytest.approx(7.9946,
                                                                  abs=1e-4)


def test_recorded_dlrm_steps_through_the_cells_own_entries():
    """The cell's per-layer metrics from the steps recorded with the scopes:
    what the classes read is what they read before the scopes (the same
    program, PR 23's fixture), and the stage metrics are there."""
    from benchmark.builders import dlrm

    cell = spec.load_cell("dlrm-mlperf.zipf")
    built = dlrm.build(cell.config, None, False)
    ctx, _, _ = _recorded("dlrm")
    ctx.built, ctx.cell, ctx.memory_peak_bytes = built, cell, 12157459456
    got = {k: v["value"] for k, v in layers.read_all(ctx).items()}
    before = xplane.reduce(xplane.load(os.path.join(
        FIXTURES, "dlrm-mlperf.zipf.3steps.xplane.pb")), xplane.load_classes())
    for name in ("lookup", "update", "dense", "layout", "other"):
        assert got[f"{name}.device_ms"] == pytest.approx(
            before[0].class_ns[name] / 3e6, rel=1e-3)
    assert {k: got[k] for k in got if "stage" in k} == pytest.approx({
        "lookup.fwd_stage_ms": 0.9788, "update.contrib_stage_ms": 0.0,
        "update.apply_stage_ms": 8.1673}, abs=1e-4)
    assert "step.temp_gib" not in got          # no chip in this process
    assert [n.split(":")[0] for n in ctx.notes] == ["step_roofline",
                                                     "stages, ms per step"]
