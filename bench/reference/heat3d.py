"""Plain reference of one explicit step of 3-D heat diffusion.

    T' = T + dt * lam * Ci * (d2T/dx2 + d2T/dy2 + d2T/dz2)

on the interior of the global grid; the boundary ring holds its values
(Dirichlet).  Second differences are the 3-point ones at spacing
``h = lx / (N - 1)`` per axis, and ``dt = min(h)^2 * c0 / lam / dt_div``,
as the configuration states.  ``xp`` is NumPy (or ``jax.numpy``) and
``dtype`` the precision every operation runs in.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

PLANES = 8                             # x-planes per block of the comparison
THREADS = min(8, os.cpu_count() or 1)  # blocks compared at once


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


def _block(prev, out, coef: dict, lo: int, hi: int):
    """``(max |out - ref|, max |ref|, max |ref - prev|)`` over the x-planes
    ``lo .. hi-1`` of the float64 step ``ref`` from ``prev``.  An interior
    plane's step reads only its neighbours, so the block's step is the
    step of the block and one plane on either side; a boundary plane
    holds its values."""
    if lo == 0 or hi == prev.shape[0]:
        ref = np.asarray(prev[lo:hi], np.float64)
        p = ref
    else:
        ref = step(prev[lo - 1:hi + 1], coef)[1:-1]
        p = np.asarray(prev[lo:hi], np.float64)
    o = np.asarray(out[lo:hi], np.float64)
    return (float(np.abs(o - ref).max()), float(np.abs(ref).max()),
            float(np.abs(ref - p).max()))


def gaps(prev, out, coef: dict) -> tuple[float, float, float]:
    """``(gap, value, change)``: the largest gap between ``out`` and the
    float64 step ``ref`` from ``prev``, the largest value of ``ref`` and
    its largest change from ``prev``.  Worked out in blocks of ``PLANES``
    x-planes on ``THREADS`` threads, so that the float64 temporaries stay
    small and a 512^3 field compares in seconds; every number is the
    whole field's, bitwise."""
    prev, out = np.asarray(prev), np.asarray(out)
    n = prev.shape[0]
    blocks = [(0, 1), (n - 1, n)] + [
        (lo, min(lo + PLANES, n - 1)) for lo in range(1, n - 1, PLANES)]
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(lambda b: _block(prev, out, coef, *b), blocks))
    return tuple(max(p[i] for p in parts) for i in range(3))


def step_error(prev, out, coef: dict) -> float:
    """The gap relative to the largest value of the step: about float32's
    rounding for a sound step, whatever the step's change."""
    gap, value, _ = gaps(prev, out, coef)
    return gap / value


def increment_error(prev, out, coef: dict) -> float:
    """The gap relative to the largest change the step makes: an error in
    the increment alone (one computed in a lower precision) reads at that
    precision's rounding, and a step that changes nothing reads 1."""
    gap, _, change = gaps(prev, out, coef)
    return gap / change
