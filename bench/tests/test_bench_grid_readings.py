"""``tools/grid_readings.py`` on the four-chip cell at its rehearsal size:
the program reads below each limit; the control (the plain reference in
bfloat16, stepped on the global field and laid back out as blocks) and
the exchange faults read above one; and an exchange fault holds for its
own reading only."""

import os

from _sub import BENCH, json_lines, run

GRID_READINGS = os.path.join(BENCH, "tools", "grid_readings.py")


def test_program_below_and_control_and_exchange_faults_above():
    rows = json_lines(run(GRID_READINGS, "--workload", "heat3d-256x4.hide",
                          "--seeds", 7, "--control-seeds", 8,
                          "--fault", "exchange_none", "exchange_x_only",
                          "--fault-seeds", 7, "--seconds", 1,
                          "--rehearsal"))
    limits = rows[-1]["limits"]
    kinds = {r["kind"]: r for r in rows if "kind" in r}
    assert set(kinds) == {"program", "control", "fault.exchange_none",
                          "fault.exchange_x_only"}
    for name, limit in limits.items():
        assert kinds["program"]["compared"][name] <= limit, kinds["program"]
        assert kinds["control"]["compared"][name] > limit, kinds["control"]
    for fault in ("fault.exchange_none", "fault.exchange_x_only"):
        assert any(kinds[fault]["compared"][k] > v for k, v in limits.items())


def test_a_fault_holds_for_its_own_reading_only():
    rows = json_lines(run(GRID_READINGS, "--workload", "heat3d-256x4.hide",
                          "--fault", "exchange_none", "coarse_increment",
                          "--fault-seeds", 7, "--seconds", 1,
                          "--rehearsal"))
    kinds = {r["kind"]: r for r in rows if "kind" in r}
    # Stale halos spoil the increment wholesale; an increment rounded to
    # bfloat16 after a sound exchange, by bfloat16's rounding alone.
    assert kinds["fault.exchange_none"]["compared"]["increment_error"] > 0.5
    assert 1e-03 < kinds["fault.coarse_increment"]["compared"][
        "increment_error"] < 1e-02
