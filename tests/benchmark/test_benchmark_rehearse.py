"""Every cell end to end on the CPU at its configuration's cut sizes: the
control flow of a chip run, the reference check's own outcome, and the
last line the driver reads."""

import json

import pytest

from benchmark import run
from benchmark.harness import check as held
from benchmark.harness import spec

CELLS = [w["name"] for w in spec.load_json("BENCHMARK.json")["workloads"]]


def _rehearse(capsys, workload, trace):
    code = run.main(["--workload", workload, "--seed", "3", "--seconds", "0.2",
                     "--trace", str(trace), "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    return lines, json.loads(lines[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_and_agrees_with_the_reference(workload, capsys):
    lines, last = _rehearse(capsys, workload, 0)
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    # a rehearsal reports no timing and no device metric, and is never
    # `correct`; the reference check's own outcome is on an earlier line
    assert last["correct"] is False and last["metrics"] == {}
    assert isinstance(last["attempted"], int) and last["attempted"] > 0
    assert last["failed"] == 0
    device = last["device"]
    assert device["platform"] == "cpu" and isinstance(device["kind"], str)
    assert isinstance(device["count"], int)
    assert isinstance(device["memory_peak_bytes"], int)
    check = json.loads(next(ln for ln in lines if ln.startswith(
        "REFERENCE_CHECK ")).split(" ", 1)[1])
    assert check["ok"] is True, check
    assert "reference_peak_bytes" in check     # None: the CPU keeps no count
    # every number the check compared, beside its limit: a loss for each
    # step the check holds under the cell's optimizer (three under adam)
    steps = held.check_steps(spec.load_cell(workload).config["optimizer"])
    assert {"emb_err_beyond_rtol_over_largest", "row_err_over_tolerance",
            "untouched_rows_changed"} | {
                f"loss{i}_off" for i in range(steps)} == set(check["compared"])
    assert len(check["loss"]) == steps
    assert all(number <= limit for number, limit in check["compared"].values())
    assert check["touched_rows"] > 0 and check["untouched_rows"] > 0
    assert check["touched_rows_moved"] > 0
    assert all(abs(s["system"] - s["reference"]) <= s["tolerance"]
               for s in check["loss"])
    window = next(ln for ln in lines if ln.startswith("WINDOW "))
    assert "backend compiles inside 0" in window


def test_traced_rehearsal_reports_no_device_metric(capsys):
    lines, last = _rehearse(capsys, "dlrm-mlperf.zipf", 1)
    steps = spec.load_cell("dlrm-mlperf.zipf").config["trace_steps"]
    assert last["attempted"] == steps and last["metrics"] == {}
    assert "breakdown" not in last and "busy_s" not in last["device"]


def test_a_stalled_block_moves_the_rate_and_not_the_median():
    """One slow loss fetch among a window's sync blocks lowers
    `samples_per_s` and leaves `step_ms_p50`: the rate is taken over all the
    window's time (PERF.md section 2: four such windows in 36 on one machine,
    cause not found)."""
    from types import SimpleNamespace

    cell = spec.load_cell("tiny-v3.zipf")
    win = SimpleNamespace(attempted=4, elapsed_s=9.7155,
                          block_ms=[1238.0, 1239.0, 6000.0, 1238.5])
    got = run.timed_metrics(cell, SimpleNamespace(global_batch=65536), win,
                            35.0)
    assert got["step_ms_p50"]["value"] == 1238.75
    assert got["samples_per_s"]["value"] == 4 * 65536 / 9.7155
