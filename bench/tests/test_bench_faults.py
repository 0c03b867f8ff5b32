"""The harness's comparison catches a broken timed path: each run below
skips the look for a chip (rehearsal), drives the rest of a run with one
fault planted under the timed path, and must come out not correct."""

import os

import pytest

from _sub import BENCH, last_json, run

FAULTY = os.path.join(BENCH, "tools", "faulty_run.py")

CASES = [(cell, fault)
         for cell in ("heat3d-256.plain", "heat3d-256.hide")
         for fault in ("unchanged", "altered", "coarse_increment")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_makes_the_run_not_correct(cell, fault):
    res = last_json(run(FAULTY, fault, "--workload", cell, "--seed", 11,
                        "--seconds", 1, "--trace", 0, "--rehearsal"))
    assert res["correct"] is False, res
    assert any(c["value"] > c["limit"] or c["value"] != c["value"]
               for c in res["checks"].values())
