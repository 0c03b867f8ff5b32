"""The control fails and the program passes: at the rehearsal sizes, the
plain reference computed in bfloat16 (one precision below the
configurations' float32) in the program's place reads above each cell's
limit, and the program itself reads below it.  ``tools/readings.py`` runs
the same at the cells' own sizes on the chip."""

import os

import pytest

from _sub import BENCH, json_lines, run

READINGS = os.path.join(BENCH, "tools", "readings.py")


@pytest.mark.parametrize("cell", ["heat3d-256.plain", "heat3d-256.hide",
                                  "heat3d-512.plain"])
def test_control_reads_above_the_limit_and_the_program_below(cell):
    rows = json_lines(run(READINGS, "--workload", cell, "--seeds", 7,
                          "--control-seeds", 8, "--seconds", 1,
                          "--rehearsal"))
    limits = rows[-1]["limits"]
    program = [r for r in rows if r.get("kind") == "program"]
    control = [r for r in rows if r.get("kind") == "control"]
    assert program and control
    for name, limit in limits.items():
        assert all(r["compared"][name] <= limit for r in program), program
        assert all(r["compared"][name] > limit for r in control), control
