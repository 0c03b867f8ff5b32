"""The reference's comparison, worked out in blocks of x-planes on
several threads, reads the whole field's numbers bitwise: the largest gap
to the float64 step of the whole field, that step's largest value and its
largest change, wherever in the field the gap lies."""

import numpy as np
import pytest

from reference import heat3d as ref

CFG = {"lx": 1.0, "c0": 2.0, "lam": 1.0, "dt_div": 6.1}


def whole_field(prev, out, coef):
    step = ref.step(prev, coef)
    p, o = prev.astype(np.float64), out.astype(np.float64)
    return (float(np.abs(o - step).max()), float(np.abs(step).max()),
            float(np.abs(step - p).max()))


@pytest.mark.parametrize("n", [3, 21, 34])
@pytest.mark.parametrize("where", ["first", "last", "block_edge", "none"])
def test_blocks_read_the_whole_fields_numbers(n, where):
    rng = np.random.default_rng(n)
    prev = (1.7 + rng.uniform(0, 1, (n, n + 1, n + 2))).astype(np.float32)
    coef = ref.coefficients(CFG, prev.shape)
    out = ref.step(prev, coef).astype(np.float32)
    plane = {"first": 0, "last": n - 1, "none": None,
             "block_edge": min(1 + ref.PLANES, n - 2)}[where]
    if plane is not None:
        out[plane, 1, 1] += 0.25
    got = ref.gaps(prev, out, coef)
    assert got == whole_field(prev, out, coef)
    assert ref.step_error(prev, out, coef) == got[0] / got[1]
    assert ref.increment_error(prev, out, coef) == got[0] / got[2]
    if plane is not None:
        assert got[0] >= 0.25 - 1e-6
