"""Every cell end to end on the CPU at its configuration's cut sizes: the
control flow of a chip run, the reference check's own outcome, and the
last line the driver reads."""

import json

import pytest

from benchmark import run
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
