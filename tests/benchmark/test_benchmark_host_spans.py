"""The per-layer metrics that read the program's own host spans (PR 40):
``benchmark/readers/program_span.py`` over the program's recorder and
registry after a rehearsed cell, and ``benchmark/readers/
idle_program_ms.py`` over a trace written here by hand.

A rehearsal hands the readers no chip and they stay silent (three other
tests hold a rehearsal to ``REHEARSED_LAYER_METRICS {}``); here each gets a
`layers.Context` with one synthetic `xplane.Chip`, as a traced run on a
chip would give it.
"""

import jax
import pytest

from benchmark import run
from benchmark.harness import layers, spec, xplane
from benchmark.readers import idle_program_ms, program_span
from distributed_embeddings_tpu import obs

HOST_METRICS = ("setup.init_s", "setup.export_s", "setup.compile_s",
                "setup.step_compile_s", "host.dispatch_ms")
CELLS = ("dlrm-mlperf.zipf", "lfm2.packed-4k")


def _chip(intervals):
    ops = [xplane.Op(f"fusion.{i}", s, e, "name=fusion") for i, (s, e)
           in enumerate(intervals)]
    busy = sum(e - s for s, e in intervals)
    window = max(e for _, e in intervals) - min(s for s, _ in intervals)
    return xplane.Chip("/device:TPU:0", busy, window, {"other": busy}, 0.0,
                       0.0, ops, [])


@pytest.fixture(scope="module", params=CELLS)
def rehearsed(request):
    """A cell rehearsed in this process, from a fresh recorder and registry:
    (cell, the steps of its traced window)."""
    obs.reset_default_recorder()
    obs.reset_default_registry()
    code = run.main(["--workload", request.param, "--seed", "5", "--seconds",
                     "0.2", "--trace", "1", "--rehearse"])
    assert code == 0
    cell = spec.load_cell(request.param)
    yield cell, int(cell.config["trace_steps"])
    obs.reset_default_recorder()
    obs.reset_default_registry()


def _context(cell, steps, chips, kind=None):
    """As a traced run builds it; the traced kind is this process's own
    device's unless the test says otherwise."""
    return layers.Context(chips, steps, None, cell,
                          kind or jax.devices()[0].device_kind, None)


@pytest.mark.parametrize("metric", HOST_METRICS)
def test_host_side_readers_read_the_rehearsed_program(rehearsed, metric):
    cell, steps = rehearsed
    assert metric in [m["name"] for m in cell.per_layer]
    params = spec.load_json("benchmark", "layer_metrics", metric + ".json")
    reader = spec.plugin("readers", params["reader"])
    ctx = _context(cell, steps, [_chip([(0.0, 10.0)])])
    value = reader.read(ctx, params)
    assert isinstance(value, float) and value > 0, (metric, value)
    # no chip traced, or one traced by another process (a recorded trace
    # read in a test): this process's seconds are nobody's metric
    assert reader.read(_context(cell, steps, []), params) is None
    assert reader.read(_context(cell, steps, ctx.chips, "TPU v5 lite"),
                       params) is None
    rec = obs.default_recorder()
    if metric == "host.dispatch_ms":
        window = program_span.window_steps(ctx, rec)
        assert len(window) == steps
        assert [s.step for s in window] == list(range(
            window[0].step, window[0].step + steps))
        assert value <= max(s.end_ns - s.start_ns for s in window) * 1e-6
    if metric == "setup.step_compile_s":
        first = min(rec.spans("train/dispatch"), key=lambda s: s.start_ns)
        assert value == (first.end_ns - first.start_ns) * 1e-9
    if metric == "setup.init_s":
        # the model's init, not the embedding's beneath it a second time
        assert value == sum(s.end_ns - s.start_ns
                            for s in rec.spans("model/init")) * 1e-9
    if metric == "setup.compile_s":
        assert any("compile/seconds" in note and "cache hits" in note
                   for note in ctx.notes)
        # the program's own compiles: what its init, its export and its
        # dispatches before the window hold, and not the plain reference's
        # (the durations nest, so the time they cover is under their sum)
        total = sum(v for k, v in obs.default_registry().snapshot()[
            "counters"].items() if k.startswith("compile/seconds"))
        spans = [s for path in params["within"] for s in rec.spans(path)]
        inside = sum(args["seconds"] for at, args
                     in rec.instants("compile/seconds")
                     if any(s.start_ns <= at <= s.end_ns for s in spans))
        assert 0 < value <= inside * (1 + 1e-6) and inside < total
        assert value < sum(s.end_ns - s.start_ns for s in spans) * 1e-9


@pytest.mark.parametrize("metric", HOST_METRICS)
def test_a_readers_unit_is_the_benchmarks(metric):
    """The metric's file holds no unit: set-up reads in seconds, the
    window's steps in milliseconds, and `BENCHMARK.json` says the same."""
    params = spec.load_json("benchmark", "layer_metrics", metric + ".json")
    entry, = [m for m in spec.load_cell(CELLS[0]).per_layer
              if m["name"] == metric]
    assert "unit" not in params
    assert entry["unit"] == program_span.UNITS[params.get("window", "before")]


def test_readers_are_silent_over_a_program_without_the_spans(rehearsed):
    """What the parent commit gives them: a recorder with no span view, a
    registry without the counters."""
    cell, steps = rehearsed
    ctx = _context(cell, steps, [_chip([(0.0, 10.0)])])

    class OldRecorder:
        dropped = 0

        def events(self):
            return []

    old = obs.default_recorder
    obs.default_recorder = OldRecorder
    try:
        for metric in HOST_METRICS:
            params = spec.load_json("benchmark", "layer_metrics",
                                    metric + ".json")
            assert program_span.read(ctx, params) is None
    finally:
        obs.default_recorder = old


# ---- a trace by hand: the XSpace wire format `xplane.read_planes` reads
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, line, events):
    """An XPlane `name` with one line `line` holding (name, start ns,
    end ns) events."""
    names = sorted({n for n, _, _ in events})
    metadata = b"".join(
        _field(4, _field(1, i + 1) + _field(2, _field(1, i + 1)
                                            + _field(2, n)))
        for i, n in enumerate(names))
    body = _field(2, line) + _field(3, 0) + b"".join(
        _field(4, _field(1, names.index(n) + 1) + _field(2, int(s * 1000))
               + _field(3, int((e - s) * 1000)))
        for n, s, e in events)
    return _field(1, _field(2, name) + _field(3, body) + metadata)


def _write_trace(root, cell, host, programs=()):
    """The host plane's events and the chip's programs (start, end)."""
    folder = root / ".benchmark_trace" / cell / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    space = _plane("/host:CPU", "python", host)
    if programs:
        space += _plane("/device:TPU:0", idle_program_ms.MODULES_LINE,
                        [("jit_det_train_step(1)", s, e)
                         for s, e in programs])
    (folder / "host.xplane.pb").write_bytes(space)
    return str(folder / "host.xplane.pb")


PROGRAMS = [(1000.0, 2000.0), (3000.0, 4000.0)]


@pytest.mark.parametrize("behind", [0.0, 700.0])
def test_idle_program_ms_over_a_trace_by_hand(tmp_path, monkeypatch, behind):
    """Two programs and one gap between them, half of it under a
    ``det:train/dispatch`` event: 500 ns in two steps, also where the host
    plane's clock runs 700 ns behind the device's, so that the dispatch
    reads as beginning after the program it enqueued."""
    cell = spec.load_cell("dlrm-mlperf.zipf")
    params = spec.load_json("benchmark", "layer_metrics",
                            "device.idle_program_ms.json")
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    chip = _chip(PROGRAMS)
    path = _write_trace(tmp_path, cell.name, [
        (name, s + behind, e + behind) for name, s, e in [
            ("dispatch", 900.0, 1400.0),                  # the loop's own
            ("det:train/dispatch", 950.0, 1300.0),
            ("DoEnqueueProgram", 1000.0, 1010.0),         # binds the shift
            ("det:train/dispatch", 2500.0, 3100.0),       # half of the gap
            ("DoEnqueueProgram", 2990.0, 3000.0),
            ("fetch", 3500.0, 4000.0)]], PROGRAMS)
    det, loop, enqueues, programs = idle_program_ms.read_trace(path)
    assert [name for name, _, _ in det] == ["det:train/dispatch"] * 2
    assert [name for name, _, _ in loop] == ["dispatch", "fetch"]
    assert programs == {"/device:TPU:0": [1000.0, 3000.0]}
    shift = idle_program_ms.plane_shift(programs[chip.plane], enqueues)
    assert shift == -behind
    loop = idle_program_ms.shifted(loop, shift)
    assert idle_program_ms.idle_gaps(chip, [chip], loop) == [
        (900.0, 1000.0), (2000.0, 3000.0)]
    ctx = _context(cell, 2, [chip])
    # 50 ns of the window's first gap and 500 of the second, in two steps
    assert idle_program_ms.read(ctx, params) == pytest.approx(550e-6 / 2)
    note, = ctx.notes
    assert f"shifted by {shift * 1e-6:.3f} ms" in note
    assert "0.001 ms, 0.001 of it under det:train/dispatch" in note
    assert idle_program_ms.read(_context(cell, 2, []), params) is None
    elsewhere = _context(cell, 2, [chip], "TPU v5 lite")
    assert idle_program_ms.read(elsewhere, params) is None
    assert elsewhere.notes == []


def test_gaps_are_labelled_by_the_span_that_covers_most():
    """Of the spans over a gap the one that covers most of it, the
    innermost of those that cover as much; none where none does."""
    labelled = idle_program_ms.label_gaps(
        [(2000.0, 3000.0), (4000.0, 4500.0), (6000.0, 6100.0)],
        [("det:train/dispatch", 1900.0, 3100.0),
         ("det:host/gc", 2100.0, 2900.0),
         ("det:train/dispatch", 2950.0, 3200.0),
         ("det:train/dispatch", 4400.0, 4900.0)], first_ordinal=7)
    assert labelled == [(1000.0, "det:train/dispatch", 7, 1000.0),
                        (500.0, "det:train/dispatch", 9, 100.0),
                        (100.0, None, None, 0.0)]


@pytest.mark.parametrize("host, programs, note", [
    # a program from before the spans leaves no det: event
    ([("dispatch", 1000.0, 1400.0), ("fetch", 3500.0, 4000.0)], PROGRAMS,
     None),
    # nothing to anchor the planes on: no program line, or one enqueue short
    ([("det:train/dispatch", 2500.0, 3100.0)], (), "anchor"),
    ([("det:train/dispatch", 2500.0, 3100.0),
      ("DoEnqueueProgram", 3050.0, 3060.0)], PROGRAMS, "anchor"),
    # a run under --keep-trace leaves no file where the reader looks
    (None, (), "--keep-trace"),
])
def test_idle_program_ms_is_silent_without_its_events(
        tmp_path, monkeypatch, host, programs, note):
    cell = spec.load_cell("dlrm-mlperf.zipf")
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    if host:
        _write_trace(tmp_path, cell.name, host, programs)
    ctx = _context(cell, 2, [_chip(PROGRAMS)])
    assert idle_program_ms.read(ctx, {}) is None
    assert bool(ctx.notes) == bool(note)
    assert not note or note in ctx.notes[0]
