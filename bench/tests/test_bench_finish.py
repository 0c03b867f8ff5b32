"""A program too slow to reach its drawn steps inside the window is held
to the same answers as a fast one.  Each run below is a rehearsal whose
timed step sleeps 0.25 s, so its 0.1-s window makes one step; seed 4
draws the early pairs at steps 3 and 4 (of 1..4 at the rehearsal size).
``finish`` keeps those pairs after the window, and the window's own
numbers do not see its steps."""

import math
import os

import pytest

from _sub import BENCH, last_json, run

SLOW = os.path.join(BENCH, "tests", "_slow_run.py")
CELL = "heat3d-256.plain"


def slow_run(fault="none", finish="finish"):
    proc = run(SLOW, 0.25, fault, finish, "--workload", CELL, "--seed", 4,
               "--seconds", 0.1, "--trace", 0, "--rehearsal")
    readings = {}
    for line in proc.stdout.splitlines():
        if line.startswith("[reading] "):
            key, _, value = line[len("[reading] "):].partition("=")
            readings[key] = float(value)
    return last_json(proc), readings


def early_steps(readings):
    return sorted(int(k.split(".")[1]) for k in readings
                  if k.startswith("increment_error."))


def test_without_finish_an_unreached_pair_reads_nan():
    res, readings = slow_run(finish="no-finish")
    assert res["attempted"] == 1
    assert early_steps(readings) == []
    assert math.isnan(res["checks"]["increment_error"]["value"])
    assert res["correct"] is False


def test_finish_keeps_the_drawn_pairs_outside_the_window():
    res, readings = slow_run()
    assert res["correct"] is True, res
    assert res["attempted"] == 1 and res["failed"] == 0
    assert early_steps(readings) == [3, 4]
    limits = {k: c["limit"] for k, c in res["checks"].items()}
    for k, v in readings.items():
        assert math.isfinite(v) and v <= limits[k.split(".")[0]], readings
    # step_error is read at the window's last step, not the last one kept.
    assert [k for k in readings if k.startswith("step_error.")] == [
        "step_error.1"]
    assert res["checks"]["step_error"]["value"] == readings["step_error.1"]


@pytest.mark.parametrize("fault", ["unchanged", "coarse_increment"])
def test_a_slowed_faulty_step_is_still_not_correct(fault):
    res, readings = slow_run(fault=fault)
    assert early_steps(readings) == [3, 4]
    assert res["correct"] is False, res
    assert res["checks"]["increment_error"]["value"] > \
        res["checks"]["increment_error"]["limit"]
