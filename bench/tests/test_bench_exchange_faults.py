"""The four-chip cell's comparison catches a broken halo exchange: each
run below rehearses ``heat3d-256x4.hide`` on four CPU devices with the
hidden step's exchange replaced (``tools/exchange_fault.py``), and must
come out not correct, with a check over its limit."""

import os

import pytest

from _sub import BENCH, last_json, run

EXCHANGE_FAULT = os.path.join(BENCH, "tools", "exchange_fault.py")


@pytest.mark.parametrize("fault", ["exchange_none", "exchange_x_only"])
def test_a_broken_exchange_makes_the_run_not_correct(fault):
    res = last_json(run(EXCHANGE_FAULT, fault, "--workload",
                        "heat3d-256x4.hide", "--seed", 11, "--seconds", 1,
                        "--trace", 0, "--rehearsal"))
    assert res["correct"] is False, res
    assert any(c["value"] > c["limit"] for c in res["checks"].values()), res
