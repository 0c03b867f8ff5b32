"""Plain reference of one explicit step of 3-D heat diffusion.

    T' = T + dt * lam * Ci * (d2T/dx2 + d2T/dy2 + d2T/dz2)

on the interior of the global grid; the boundary ring holds its values
(Dirichlet).  Second differences are the 3-point ones at spacing
``h = lx / (N - 1)`` per axis, and ``dt = min(h)^2 * c0 / lam / dt_div``,
as the configuration states.  ``xp`` is NumPy (or ``jax.numpy``) and
``dtype`` the precision every operation runs in.
"""

from __future__ import annotations

import numpy as np


def coefficients(cfg: dict, shape) -> dict:
    h = [cfg["lx"] / (n - 1) for n in shape]
    dt = min(h) ** 2 * cfg["c0"] / cfg["lam"] / cfg["dt_div"]
    return {"a": dt * cfg["lam"] / cfg["c0"], "rh2": [1.0 / x ** 2 for x in h]}


def step(T, coef: dict, *, xp=np, dtype=np.float64):
    """One step of the global field ``T``; returns the new field."""
    T = xp.asarray(T).astype(dtype)
    a = xp.asarray(coef["a"], dtype)
    rx, ry, rz = (xp.asarray(r, dtype) for r in coef["rh2"])
    c = T[1:-1, 1:-1, 1:-1]
    two = xp.asarray(2.0, dtype)
    lap = ((T[2:, 1:-1, 1:-1] - two * c + T[:-2, 1:-1, 1:-1]) * rx
           + (T[1:-1, 2:, 1:-1] - two * c + T[1:-1, :-2, 1:-1]) * ry
           + (T[1:-1, 1:-1, 2:] - two * c + T[1:-1, 1:-1, :-2]) * rz)
    new = c + a * lap
    if xp is np:
        out = T.copy()
        out[1:-1, 1:-1, 1:-1] = new
        return out
    return T.at[1:-1, 1:-1, 1:-1].set(new)


def _gap(prev, out, coef: dict):
    """The largest gap between ``out`` and the float64 step from ``prev``,
    with that step and ``prev`` in float64."""
    prev = np.asarray(prev, np.float64)
    ref = step(prev, coef)
    return float(np.abs(np.asarray(out, np.float64) - ref).max()), ref, prev


def step_error(prev, out, coef: dict) -> float:
    """The gap relative to the largest value of the step: about float32's
    rounding for a sound step, whatever the step's change."""
    gap, ref, _ = _gap(prev, out, coef)
    return gap / float(np.abs(ref).max())


def increment_error(prev, out, coef: dict) -> float:
    """The gap relative to the largest change the step makes: an error in
    the increment alone (one computed in a lower precision) reads at that
    precision's rounding, and a step that changes nothing reads 1."""
    gap, ref, prev = _gap(prev, out, coef)
    return gap / float(np.abs(ref - prev).max())
