"""Cartesian process topology on a JAX device mesh.

The paper (ImplicitGlobalGrid.jl) creates a Cartesian MPI communicator with
``MPI_Cart_create`` / ``MPI_Dims_create``.  On TPU the ICI network *is* a
2-D/3-D torus, so a Cartesian topology maps onto physical neighbor links;
here a topology is simply an ordered set of named mesh axes (one per
distributed grid dimension) plus periodicity flags.

All neighbor communication is expressed as ``jax.lax.ppermute`` permutations
(compiled to ``collective-permute``, the direct neighbor-DMA primitive on
ICI).  Helpers below build the shift permutations used by halo updates.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def dims_create(nprocs: int, ndims: int) -> tuple[int, ...]:
    """Factor ``nprocs`` into ``ndims`` near-equal factors (MPI_Dims_create).

    Returns dims sorted descending (largest first), matching MPI semantics.
    """
    if nprocs <= 0:
        raise ValueError(f"nprocs must be positive, got {nprocs}")
    dims = [1] * ndims
    remaining = nprocs
    # Greedy: repeatedly assign the smallest prime factor to the smallest dim.
    primes = []
    n = remaining
    f = 2
    while f * f <= n:
        while n % f == 0:
            primes.append(f)
            n //= f
        f += 1
    if n > 1:
        primes.append(n)
    for p in sorted(primes, reverse=True):
        i = int(np.argmin(dims))
        dims[i] *= p
    return tuple(sorted(dims, reverse=True))


def make_grid_mesh(
    ndims: int = 3,
    dims: Sequence[int] | None = None,
    axes: Sequence[str] = ("gx", "gy", "gz"),
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Cartesian device mesh for an implicit global grid.

    ``dims=None`` reproduces the paper's automatic topology selection from
    the process count (here: the device count).
    """
    devices = list(devices if devices is not None else jax.devices())
    if dims is None:
        dims = dims_create(len(devices), ndims)
    dims = tuple(int(d) for d in dims)
    if math.prod(dims) != len(devices):
        raise ValueError(f"dims {dims} do not multiply to device count {len(devices)}")
    dev_array = np.asarray(devices).reshape(dims)
    return Mesh(dev_array, tuple(axes[:ndims]))


@dataclasses.dataclass(frozen=True)
class CartesianTopology:
    """A Cartesian topology over (a subset of) mesh axes.

    axes[d] is the mesh axis name for grid dimension ``d`` or ``None`` for a
    non-distributed dimension.  ``periodic[d]`` selects wraparound halos.
    """

    mesh: Mesh
    axes: tuple[str | None, ...]
    periodic: tuple[bool, ...]

    def __post_init__(self):
        if len(self.axes) != len(self.periodic):
            raise ValueError("axes and periodic must have the same length")
        for ax in self.axes:
            if ax is not None and ax not in self.mesh.axis_names:
                raise ValueError(f"axis {ax!r} not in mesh {self.mesh.axis_names}")

    @property
    def ndims(self) -> int:
        return len(self.axes)

    @property
    def dims(self) -> tuple[int, ...]:
        return tuple(
            1 if ax is None else self.mesh.shape[ax] for ax in self.axes
        )

    def exchanges(self, dim: int) -> bool:
        """Whether grid dim ``dim`` has a halo to exchange: it is split
        over more than one rank, or it wraps around."""
        return self.dims[dim] > 1 or self.periodic[dim]

    def spec(self, extra_leading: int = 0) -> P:
        """PartitionSpec sharding grid dims over their mesh axes."""
        return P(*([None] * extra_leading), *self.axes)

    # ---- permutations (used inside shard_map) -------------------------

    def shift_perm(self, dim: int, shift: int) -> list[tuple[int, int]]:
        """(source, dest) pairs moving data ``shift`` ranks along ``dim``."""
        n = self.dims[dim]
        pairs = []
        for src in range(n):
            dst = src + shift
            if self.periodic[dim]:
                pairs.append((src, dst % n))
            elif 0 <= dst < n:
                pairs.append((src, dst))
        return pairs

    def coord(self, dim: int):
        """Rank coordinate along grid dim (traced; inside shard_map)."""
        ax = self.axes[dim]
        if ax is None:
            import jax.numpy as jnp

            return jnp.int32(0)
        return jax.lax.axis_index(ax)

    def is_first(self, dim: int):
        return self.coord(dim) == 0

    def is_last(self, dim: int):
        return self.coord(dim) == self.dims[dim] - 1
