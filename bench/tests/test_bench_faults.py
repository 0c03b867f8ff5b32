"""The harness's comparison catches a broken timed path: each run below
skips the look for a chip (rehearsal), drives the rest of a run with one
fault planted under the timed path, and must come out not correct."""

import os

import pytest

from _sub import BENCH, last_json, run

FAULTY = os.path.join(BENCH, "tools", "faulty_run.py")

# At 512^3 the plain mix's step changes the field little against float32's
# rounding of it, so increment_error's limit there is set from the control
# and lies above what an increment rounded to bfloat16 reads (PERF.md
# section 4): that cell plants the faults its limits catch.
CASES = [(cell, fault)
         for cell in ("heat3d-256.plain", "heat3d-256.hide")
         for fault in ("unchanged", "altered", "coarse_increment")] + [
    ("heat3d-512.plain", fault) for fault in ("unchanged", "altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_makes_the_run_not_correct(cell, fault):
    res = last_json(run(FAULTY, fault, "--workload", cell, "--seed", 11,
                        "--seconds", 1, "--trace", 0, "--rehearsal"))
    assert res["correct"] is False, res
    assert any(c["value"] > c["limit"] or c["value"] != c["value"]
               for c in res["checks"].values())
