"""Every cell of BENCHMARK.json runs end to end at its rehearsal size on
CPU devices: its files resolve, its answers compare as correct, and no
device metric is printed.  Without a TPU a real run prints no result."""

import os

import pytest

from _sub import BENCH, last_json, run
from harness import spec

RUN = os.path.join(BENCH, "run.py")
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2 ** 31 + 12345  # larger than 32 signed bits hold


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_each_cell_without_device_metrics(cell):
    res = last_json(run(RUN, "--workload", cell, "--seed", SEED,
                        "--seconds", 1, "--trace", 0, "--rehearsal"))
    assert res["correct"] is True, res
    assert res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "metrics" not in res and "device" not in res
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    names = {m["name"] for m in spec.find_cell(cell).end_to_end}
    assert set(res["would_report"]) == names and "setup_s" in names


def test_traced_rehearsal_names_the_per_layer_metrics():
    cell = "heat3d-256.hide"
    res = last_json(run(RUN, "--workload", cell, "--seed", 3,
                        "--seconds", 1, "--trace", 1, "--rehearsal"))
    assert res["correct"] is True
    assert "metrics" not in res and "breakdown" not in res
    assert set(res["would_report"]) == {
        m["name"] for m in spec.find_cell(cell).per_layer}


def test_without_a_tpu_a_run_exits_1_and_prints_no_result():
    proc = run(RUN, "--workload", CELLS[0], "--seed", 1, "--seconds", 1,
               "--trace", 0, check=False)
    assert proc.returncode == 1
    assert "no TPU" in proc.stderr
    assert not any(x.startswith("{") for x in proc.stdout.splitlines())
