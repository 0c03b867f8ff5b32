"""The work model, against bytes counted by hand at the rehearsal size."""

import pytest

from harness import layout, spec


@pytest.fixture(scope="module")
def heat():
    cell = spec.find_cell("heat3d-256.plain")
    return spec.load_module("apps", "heat3d").App(cell, 1, True)


def test_heat_bytes_are_three_fields_per_step(heat):
    # 34^3 f32: T read, Ci read, T written.
    win = {"units": 10, "elapsed_s": 1.0}
    assert heat.required_bytes(win) == {"stencil3d": 10 * 3 * 34 ** 3 * 4}
    assert heat.t_eff_gb_s(win) == pytest.approx(3 * 34 ** 3 * 4 / 0.1 / 1e9)


def test_layout_round_trip():
    local, dims = (6, 5, 4), (2, 3, 1)
    g = layout.global_shape(local, dims)
    assert g == (10, 11, 4)
    import numpy as np

    full = np.arange(np.prod(g)).reshape(g)
    idx = [layout.stacked_to_global(n, d) for n, d in zip(local, dims)]
    stacked = full[np.ix_(*idx)]
    assert stacked.shape == (12, 15, 4)
    assert (layout.dedup(stacked, local, dims) == full).all()
