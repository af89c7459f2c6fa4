"""The reduction from a device trace to time per class: on hand-built
windows whose answers can be checked by eye, and on a small trace recorded
on the chip (benchmark/fixtures)."""

import os
import re

import pytest

from benchmark.harness import spec, xplane
from benchmark.harness.xplane import Op, Trace

RULES = [("exchange", [re.compile("all-to-all")]),
         ("dedup", [re.compile(r"\bsort")]),
         ("lookup", [re.compile("gather")])]


def _op(name, start, end):
    return Op(name, float(start), float(end), name.lower())


def _hand_built():
    """One chip, ns: a while loop [100, 500) whose body is a gather
    [120, 220), a sort [250, 400) and an unknown op [400, 450); then an
    all-to-all [600, 700) alone; then a gather [900, 1000). The host
    dispatched over [0, 150), waited [150, 950), fetched [950, 1100)."""
    ops = [_op("while.1", 100, 500), _op("gather.2", 120, 220),
           _op("sort.3", 250, 400), _op("mystery.4", 400, 450),
           _op("all-to-all.5", 600, 700), _op("gather.6", 900, 1000)]
    host = [("dispatch", 0.0, 150.0), ("sync", 150.0, 950.0),
            ("fetch", 950.0, 1100.0)]
    return Trace({"/device:TPU:0": ops}, {}, host)


def test_every_nanosecond_lands_in_one_class():
    (chip,) = xplane.reduce(_hand_built(), RULES)
    assert chip.busy_ns == 400 + 100 + 100
    assert chip.class_ns == {"lookup": 200.0, "dedup": 150.0,
                             "exchange": 100.0,
                             "other": 50.0 + (400 - 100 - 150 - 50)}
    assert sum(chip.class_ns.values()) == chip.busy_ns


def test_window_idle_share_and_gap_labels():
    (chip,) = xplane.reduce(_hand_built(), RULES)
    assert chip.window_ns == 1100.0           # first dispatch .. last fetch
    assert 1 - chip.busy_ns / chip.window_ns == pytest.approx(500 / 1100)
    # gaps: [0,100) dispatch, [500,600) sync, [700,900) sync, [1000,1100) fetch
    assert chip.gaps == [("sync", 200.0), ("dispatch", 100.0),
                         ("sync", 100.0), ("fetch", 100.0)]
    assert xplane.top_gaps(chip, 1) == [["sync", 200.0 * 1e-9]]


def test_exposed_share_counts_only_unhidden_exchange():
    (chip,) = xplane.reduce(_hand_built(), RULES)
    # a blocking collective: nothing ran beside it
    assert (chip.exchange_ns, chip.exposed_ns) == (100.0, 100.0)
    # the same exchange, now asynchronous: its start and done on the core,
    # in flight over [600, 700) beside a gather that hides 80 ns of it
    ops = [_op("all-to-all-start.5", 600, 610), _op("gather.7", 610, 690),
           _op("all-to-all-done.5", 690, 700)]
    flight = [_op("all-to-all-start.5", 600, 700)]
    (chip,) = xplane.reduce(
        Trace({"/device:TPU:0": ops}, {"/device:TPU:0": flight}, []), RULES)
    assert chip.class_ns["exchange"] == 20.0
    assert (chip.exchange_ns, chip.exposed_ns) == (100.0, 20.0)


def test_a_renamed_unknown_op_lands_in_other():
    trace = _hand_built()
    trace.chips["/device:TPU:0"][2] = _op("zort.3", 250, 400)
    (chip,) = xplane.reduce(trace, RULES)
    assert "dedup" not in chip.class_ns
    assert chip.class_ns["other"] == 50.0 + 150.0 + 100.0
    assert sum(chip.class_ns.values()) == chip.busy_ns


def test_top_ops_carry_name_and_class():
    (chip,) = xplane.reduce(_hand_built(), RULES)
    top = xplane.top_ops(chip, 10)
    assert top[0] == ["sort.3 [dedup]", 150.0 * 1e-9]
    assert ["while.1 [other]", 100.0 * 1e-9] in top      # its self time
    assert len(xplane.top_ops(chip, 2)) == 2


def test_op_classes_go_by_kind_first_and_by_source_only_then():
    rules = xplane.load_classes()
    # every class's `patterns`, then every class's `fallback_patterns`
    assert [c for c, _ in rules] == ["exchange", "dedup", "update", "lookup",
                                     "dense", "update", "lookup", "dense",
                                     "layout"]
    src = "src=/x/distributed_embeddings_tpu/"
    for sig, want in [
            ("name=all-to-all.3 cat=all-to-all op= src=", "exchange"),
            ("name=fusion.9 cat=loop fusion op=jit(f)/all_to_all: " + src
             + "layers/dist_model_parallel.py:1900", "exchange"),
            ("name=sort.11 cat=sort op=jit(f)/sort: " + src
             + "ops/sparse_update.py:482", "dedup"),
            # the sort XLA puts before a scatter is a sort
            ("name=sort cat=sort op=jit(f)/scatter-add: " + src
             + "ops/sparse_update.py:535", "dedup"),
            ("name=fusion.15 cat=custom fusion op=jit(f)/scatter-add: " + src
             + "ops/sparse_update.py:489", "update"),
            # a gather is lookup work whoever asked for it: the update's
            # re-read of accumulator rows, and a file that moved
            ("name=fusion.5 cat=custom fusion op=jit(f)/jit(_take)/gather: "
             + src + "ops/sparse_update.py:696", "lookup"),
            ("name=fusion.2 cat=custom fusion op=jit(f)/jvp(jit(_take))/gather: "
             + src + "layers/dist_model_parallel.py:1426", "lookup"),
            ("name=fusion.2 cat=custom fusion op=jit(f)/jvp(jit(_take))/gather: "
             "src=/x/elsewhere/moved.py:7", "lookup"),
            # ... and a scatter is update work, the interaction's too
            ("name=fusion.3 cat=custom fusion op=jit(f)/transpose(jvp())/"
             "scatter-add: " + src + "models/dlrm.py:51", "update"),
            ("name=fusion.37 cat=convolution fusion op= src=", "dense"),
            ("name=fusion.37 cat=convolution fusion op=jit(f)/dot_general: "
             + src + "ops/sparse_update.py:1", "dense"),
            # no class claims these by their kind: the source decides
            ("name=select_reduce_fusion cat=loop fusion op=jit(f)/jvp(bk,bkw->"
             "bw)/dot_general: " + src + "ops/pallas_lookup.py:295", "lookup"),
            ("name=pad.3 cat=pad op=jit(f)/concatenate: " + src
             + "ops/sparse_update.py:529", "update"),
            ("name=copy.22 cat=data formatting op=jit(f)/mul: " + src
             + "ops/sparse_update.py:625", "update"),
            ("name=add_any.33 cat=non-fusion elementwise op=jit(f)/add_any: "
             + src + "models/dlrm.py:46", "dense"),
            ("name=copy.23 cat=data formatting op= src=", "layout"),
            ("name=copy_bitcast_fusion cat=loop fusion op= src=", "layout"),
            ("name=while.14 cat=while op= src=", "other")]:
        assert xplane.classify(sig, rules) == want, sig


FIXTURE = os.path.join(spec.ROOT, "benchmark", "fixtures",
                       "dlrm-mlperf.zipf.3steps.xplane.pb")


def test_recorded_trace_classes_sum_to_busy_time():
    """Three steps of dlrm-mlperf.zipf cut from PR 23's first chip trace,
    the loss fetch of step 32 between the first and the second (looked at by
    hand: benchmark/fixtures/README.md)."""
    trace = xplane.load(FIXTURE)
    assert list(trace.chips) == ["/device:TPU:0"]
    assert len(trace.chips["/device:TPU:0"]) == 3 * 275
    assert [h[0] for h in trace.host].count("dispatch") == 32
    (chip,) = xplane.reduce(trace, xplane.load_classes())
    assert chip.window_ns == pytest.approx(145.72e6)
    assert chip.busy_ns == pytest.approx(142.6845e6, rel=1e-6)
    assert sum(chip.class_ns.values()) == pytest.approx(chip.busy_ns,
                                                         rel=1e-12)
    per_step = {k: v / 3e6 for k, v in chip.class_ns.items()}
    assert per_step == pytest.approx(
        {"layout": 36.917, "update": 8.219, "dense": 1.375, "lookup": 0.993,
         "other": 0.058}, abs=1e-3)
    # the device waited 3.0 ms while the host fetched the loss of step 32
    assert 100 * (1 - chip.busy_ns / chip.window_ns) == pytest.approx(
        2.0831, abs=1e-4)
    assert chip.gaps[0] == ("sync", pytest.approx(3000090.0))
    assert chip.exchange_ns == 0 and "exchange" not in chip.class_ns
    assert xplane.top_ops(chip, 2)[0][0] == "copy.23 [layout]"


def test_recorded_trace_renamed_op_lands_in_other():
    trace = xplane.load(FIXTURE)
    for op in trace.chips["/device:TPU:0"]:
        if op.name == "copy.23":
            op.signature = "name=novel.23 cat=novel op= src="
    (chip,) = xplane.reduce(trace, xplane.load_classes())
    assert chip.class_ns["other"] / 3e6 == pytest.approx(0.058 + 18.452,
                                                          abs=2e-3)
    assert chip.class_ns["layout"] / 3e6 == pytest.approx(36.917 - 18.452,
                                                           abs=2e-3)
    assert sum(chip.class_ns.values()) == pytest.approx(chip.busy_ns,
                                                         rel=1e-12)


def test_layer_metrics_read_from_the_recorded_trace():
    """The cell's per-layer metrics, each by its own reader, from the
    recorded steps at the cell's real sizes."""
    from benchmark.builders import dlrm
    from benchmark.harness import layers

    cell = spec.load_cell("dlrm-mlperf.zipf")
    built = dlrm.build(cell.config, None, False)      # the plan, no arrays
    chips = xplane.reduce(xplane.load(FIXTURE), xplane.load_classes())
    ctx = layers.Context(chips, 3, built, cell, "TPU v5 lite", 12157459456)
    got = {k: v["value"] for k, v in layers.read_all(ctx).items()}
    assert got == pytest.approx({
        "device.idle_share": 2.0831, "device.hbm_peak_gib": 11.3225,
        "step.device_ms": 47.5615, "step_roofline": 0.64485,
        "lookup.device_ms": 0.9929, "update.device_ms": 8.2190,
        "dense.device_ms": 1.3748, "layout.device_ms": 36.9169,
        "other.device_ms": 0.0579}, abs=1e-4)
    # 4,096 samples x 14.75 MFLOP over 197 TFLOP/s against 47.56 ms
    assert ctx.notes == ["step_roofline: the least step is 0.3067 ms, bound "
                         "by mxu"]
    assert sum(got[k] for k in got if k.endswith(".device_ms")
               and k != "step.device_ms") == pytest.approx(
                   got["step.device_ms"], rel=1e-9)


def test_recorded_four_chip_step_exchange_is_all_exposed():
    """One step on two of the four chips (benchmark/fixtures/README.md):
    five blocking collectives per chip, summed by hand for chip 0."""
    path = os.path.join(spec.ROOT, "benchmark", "fixtures",
                        "dlrm-mlperf.zipf-4chip.1step.xplane.pb")
    chips = xplane.reduce(xplane.load(path), xplane.load_classes())
    assert [c.plane for c in chips] == ["/device:TPU:0", "/device:TPU:3"]
    first = chips[0]
    assert sorted(round(o.end - o.start) for o in first.ops
                  if o.cls == "exchange") == [380, 10479, 162006, 328800,
                                              634632]
    assert first.class_ns["exchange"] == pytest.approx(1136297.0, abs=3)
    for chip in chips:
        assert chip.window_ns == pytest.approx(37.687e6)
        assert chip.exchange_ns == pytest.approx(chip.class_ns["exchange"])
        assert chip.exposed_ns == pytest.approx(chip.exchange_ns)
        assert sum(chip.class_ns.values()) == pytest.approx(chip.busy_ns,
                                                             rel=1e-12)
        assert chip.class_ns["layout"] / chip.busy_ns > 0.6
    # exchange.device_ms is the chip that spent most, the other classes'
    # metrics the chips' mean
    from benchmark.harness import layers
    from benchmark.readers import class_device_ms

    ctx = layers.Context(chips, 1, None, None, "TPU v5 lite", None)
    per_chip = [c.class_ns["exchange"] * 1e-6 for c in chips]
    assert class_device_ms.read(ctx, spec.load_json(
        "benchmark/layer_metrics/exchange.device_ms.json")) == max(per_chip)
    assert class_device_ms.read(ctx, {"class": "exchange"}) == pytest.approx(
        sum(per_chip) / 2)


KNOWN_SOURCES = ["ops/sparse_update", "layers/dist_model_parallel", "models/",
                 "optax"]


def test_known_source_patterns_match_the_recorded_traces():
    """The fallback patterns name files of the program. One that matches
    no recorded operation is a typing error here, or a file that has moved
    since the trace was cut: then cut the fixtures anew and look again."""
    signatures = {op.signature
                  for name in ("dlrm-mlperf.zipf.3steps.xplane.pb",
                               "dlrm-mlperf.zipf-4chip.1step.xplane.pb")
                  for ops in xplane.load(os.path.join(
                      spec.ROOT, "benchmark", "fixtures", name)).chips.values()
                  for op in ops}
    fallbacks = [p for path in sorted(os.listdir(os.path.join(
                     spec.ROOT, "benchmark", "op_classes")))
                 for p in spec.load_json("benchmark", "op_classes", path)[
                     "fallback_patterns"]]
    for known in KNOWN_SOURCES:
        (pattern,) = [p for p in fallbacks if known in p.replace("\\", "")]
        assert any(re.search(pattern, s) for s in signatures), pattern
    # and a pattern for a file that is not there matches nothing
    assert xplane.idle_patterns(
        xplane.reduce(xplane.load(FIXTURE), [("x", [re.compile("src=.*gone")])]),
        [("x", [re.compile("src=.*gone")])]) == ["src=.*gone"]
