"""The storage layout of a field on the implicit global grid, as the
benchmark needs it to make inputs and to read answers back.

A field is one array of stacked local blocks, ``dims[d] * local[d]``
along each axis, and neighbouring blocks overlap by ``OVERLAP`` planes
(two halo layers of width 1).  Stacked index ``s`` along an axis lies in
block ``s // n`` at local index ``s % n``, which is global index
``(s // n) * (n - OVERLAP) + s % n``.
"""

from __future__ import annotations

import numpy as np

OVERLAP = 2


def global_shape(local, dims) -> tuple:
    return tuple(d * (n - OVERLAP) + OVERLAP for n, d in zip(local, dims))


def stacked_to_global(n: int, d: int) -> np.ndarray:
    """Global index of each stacked index along one axis."""
    s = np.arange(d * n)
    return (s // n) * (n - OVERLAP) + s % n


def dedup(a, local, dims) -> np.ndarray:
    """The global field (each cell once) of a stacked field, on the host."""
    a = np.asarray(a)
    for ax, (n, d) in enumerate(zip(local, dims)):
        keep = np.concatenate(
            [np.arange(n)] + [b * n + np.arange(OVERLAP, n) for b in range(1, d)])
        a = np.take(a, keep, axis=ax)
    return a


def on_device(fn, local, dims, sharding, dtype, count: int = 1, args=()):
    """``count`` stacked fields ``fn(k, ix, iy, iz, *args)`` of global
    index arrays (broadcast along their axes), made on the devices in one
    jitted call and placed with ``sharding``.  What the seed draws goes in
    ``args``, so that every seed runs the one compiled program."""
    import jax
    import jax.numpy as jnp

    idx = [stacked_to_global(n, d) for n, d in zip(local, dims)]

    def make(*args):
        ix = jnp.asarray(idx[0])[:, None, None]
        iy = jnp.asarray(idx[1])[None, :, None]
        iz = jnp.asarray(idx[2])[None, None, :]
        return tuple(fn(k, ix, iy, iz, *args).astype(dtype)
                     for k in range(count))

    return jax.jit(make, out_shardings=(sharding,) * count)(*args)
