"""The implicit global grid — the paper's core abstraction, in JAX.

The user writes a *single-device* stencil code on a local grid of shape
``(nx, ny, nz)`` (including halo cells).  The global computational grid is
created implicitly from the device count and a Cartesian topology:

    nx_g = dims_x * (nx - overlap) + overlap        (overlap = 2 * halo)

A *field* is one global ``jax.Array`` of stacked local blocks (shape
``dims * local``), sharded so each device holds exactly its local block
INCLUDING halo cells — neighboring blocks logically overlap, which is
exactly the paper's distributed memory model.  All computation runs in the
``shard_map`` local view; :func:`repro.core.halo.update_halo` and
:func:`repro.core.hide.hide_communication` provide the paper's
``update_halo!`` and ``@hide_communication``.

Three calls turn a single-device solver into a multi-device one, mirroring
the paper's Fig. 1:

    grid = init_global_grid(nx, ny, nz)            # 1. implicit global grid
    ...  grid.update_halo(T2) / grid.hide(...)     # 2. halo update
    grid.finalize()                                # 3. finalize (no-op; GC)
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.telemetry.timers import trace_span

from . import halo as _halo
from . import hide as _hide
from .topology import CartesianTopology, make_grid_mesh


class ImplicitGlobalGrid:
    """Implicit global grid over a Cartesian device mesh."""

    def __init__(
        self,
        nx: int,
        ny: int = 1,
        nz: int = 1,
        *,
        overlap: int = 2,
        periodic: Sequence[bool] = (False, False, False),
        mesh: Mesh | None = None,
        dims: Sequence[int] | None = None,
        axes: Sequence[str] = ("gx", "gy", "gz"),
        dtype=jnp.float32,
    ):
        local = [n for n in (nx, ny, nz) if n is not None]
        self.ndims = len(local)
        self.local_shape = tuple(int(n) for n in local)
        if overlap % 2 != 0:
            raise ValueError("overlap must be even (two halo layers of width h)")
        self.overlap = int(overlap)
        self.halo = self.overlap // 2
        if mesh is None:
            mesh = make_grid_mesh(self.ndims, dims=dims, axes=axes)
        self.mesh = mesh
        axes = tuple(axes[: self.ndims])
        self.topo = CartesianTopology(
            mesh=mesh, axes=axes, periodic=tuple(bool(p) for p in periodic[: self.ndims])
        )
        self.dtype = dtype
        self._jit_cache: dict = {}
        for n in self.local_shape:
            if n <= self.overlap:
                raise ValueError(
                    f"local extent {n} must exceed overlap {self.overlap}"
                )

    # ------------------------------------------------------------------
    # sizes & coordinates (paper: nx_g(), x_g(), ...)
    # ------------------------------------------------------------------
    @property
    def dims(self) -> tuple[int, ...]:
        return self.topo.dims

    def n_g(self, dim: int) -> int:
        n = self.local_shape[dim]
        return self.dims[dim] * (n - self.overlap) + self.overlap

    def nx_g(self) -> int:
        return self.n_g(0)

    def ny_g(self) -> int:
        return self.n_g(1)

    def nz_g(self) -> int:
        return self.n_g(2)

    @property
    def global_shape(self) -> tuple[int, ...]:
        """True global grid shape (deduplicated)."""
        return tuple(self.n_g(d) for d in range(self.ndims))

    def span(self, dim: int) -> int:
        """Domain span of ``dim`` in cells: ``N - 1`` node intervals
        bracket a Dirichlet dim; a periodic dim covers its ``N - overlap``
        unique cells per period (the ring planes are wrap duplicates,
        ``i == i +- (N - overlap)``).  The single source of truth for
        spacing denominators and (all-periodic) unknown counts."""
        n = self.n_g(dim)
        return n - self.overlap if self.topo.periodic[dim] else n - 1

    @property
    def stacked_shape(self) -> tuple[int, ...]:
        """Shape of the stacked-blocks array (the storage layout)."""
        return tuple(
            self.dims[d] * self.local_shape[d] for d in range(self.ndims)
        )

    @property
    def spec(self) -> P:
        return self.topo.spec()

    @property
    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec)

    def local_global_indices(self):
        """Global index arrays for the local block (inside shard_map).

        Returns ``ndims`` arrays, each shaped to broadcast along its dim
        (e.g. ``(nx,1,1), (1,ny,1), (1,1,nz)`` in 3-D).
        """
        out = []
        for d in range(self.ndims):
            n = self.local_shape[d]
            g = self.topo.coord(d) * (n - self.overlap) + jnp.arange(n)
            shape = [1] * self.ndims
            shape[d] = n
            out.append(g.reshape(shape))
        return tuple(out)

    # ------------------------------------------------------------------
    # field allocation (paper: @zeros, @ones)
    # ------------------------------------------------------------------
    def zeros(self, dtype=None):
        return jnp.zeros(self.stacked_shape, dtype or self.dtype, device=self.sharding)

    def ones(self, dtype=None):
        return jnp.ones(self.stacked_shape, dtype or self.dtype, device=self.sharding)

    def full(self, value, dtype=None):
        return jnp.full(self.stacked_shape, value, dtype or self.dtype, device=self.sharding)

    def from_global_fn(self, fn: Callable, dtype=None):
        """Field initialized as ``fn(ix, iy, iz)`` of *global* indices."""
        dtype = dtype or self.dtype

        def local():
            return fn(*self.local_global_indices()).astype(dtype)

        shard = jax.shard_map(
            local, mesh=self.mesh, in_specs=(), out_specs=self.spec
        )
        return jax.jit(shard)()

    def coords(self, dim: int, spacing: float = 1.0, origin: float = 0.0):
        """Global coordinate field along ``dim`` (broadcast to grid shape)."""

        def fn(*idx):
            return jnp.broadcast_to(
                origin + spacing * idx[dim], self.local_shape
            )

        return self.from_global_fn(fn)

    # ------------------------------------------------------------------
    # local-view execution
    # ------------------------------------------------------------------
    def _is_field(self, a) -> bool:
        return hasattr(a, "ndim") and a.ndim >= self.ndims and (
            a.shape[-self.ndims:] == self.stacked_shape
            or a.shape[-self.ndims:] == self.local_shape
        )

    def parallel(self, fn: Callable) -> Callable:
        """Decorator: run ``fn`` in the shard_map local view (jitted).

        Positional args that look like grid fields (trailing dims equal the
        stacked global shape) are sharded over the grid axes; staggered
        pytrees (``repro.fields`` Field / FieldSet, marked by
        ``_staggered_tree``) are sharded leaf-wise via a spec prefix;
        everything else is replicated.  All outputs are treated as grid
        fields (or pytrees thereof).

        Each call runs inside a profiler span ``grid.parallel.<fn name>``
        (:func:`repro.telemetry.trace_span`), and ``wrapper.lower(*args)``
        lowers the jitted program such a call runs.
        """

        span = f"grid.parallel.{fn.__name__}"

        def jitted(args):
            args = tuple(
                a if hasattr(a, "ndim") or getattr(a, "_staggered_tree", False)
                else jnp.asarray(a)
                for a in args
            )

            def spec_of(a):
                if getattr(a, "_staggered_tree", False) and not hasattr(a, "ndim"):
                    return self.spec  # pytree prefix: every leaf a grid field
                if a.ndim >= self.ndims and a.shape[-self.ndims:] == self.stacked_shape:
                    return P(*([None] * (a.ndim - self.ndims)), *self.topo.axes)
                return P()

            def sig_of(a):
                if getattr(a, "_staggered_tree", False) and not hasattr(a, "ndim"):
                    return jax.tree_util.tree_structure(a)
                return (a.ndim, a.shape[-self.ndims:] == self.stacked_shape
                        if a.ndim >= self.ndims else False)

            key = (fn, tuple(sig_of(a) for a in args))
            if key not in self._jit_cache:
                in_specs = tuple(spec_of(a) for a in args)
                # check_vma=False: pallas_call out_shapes carry no vma info
                sm = jax.shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs, out_specs=self.spec,
                    check_vma=False,
                )
                self._jit_cache[key] = jax.jit(sm)
            return self._jit_cache[key], args

        @functools.wraps(fn)
        def wrapper(*args):
            # One span per call on the profiler's timeline: the host's time
            # in the call, its dispatch and any wait for an execution slot.
            with trace_span(span):
                f, args = jitted(args)
                return f(*args)

        def lower(*args):
            """The lowering of the jitted program a call with ``args``
            runs (``.compile().as_text()`` for its compiled module)."""
            f, args = jitted(args)
            return f.lower(*args)

        wrapper.lower = lower
        return wrapper

    # Local-view operations, re-exported with the grid's topology bound:
    def update_halo(self, *arrays, width: int | None = None, dims=None):
        """Paper's ``update_halo!`` (INSIDE the local view)."""
        return _halo.update_halo(
            self.topo, *arrays, width=self.halo if width is None else width, dims=dims
        )

    def hide(self, step_fn, inputs, width=(16, 2, 2)):
        """Paper's ``@hide_communication`` (INSIDE the local view)."""
        return _hide.hide_communication(
            self.topo, step_fn, inputs, width=width[: self.ndims], halo=self.halo
        )

    # Host-level convenience (wraps shard_map around a lone halo update):
    def update_halo_g(self, A):
        @self.parallel
        def _upd(a):
            return _halo.update_halo(self.topo, a, width=self.halo)

        return _upd(A)

    # ------------------------------------------------------------------
    # gather / scatter (tests, IO, checkpoints)
    # ------------------------------------------------------------------
    def gather(self, A) -> np.ndarray:
        """Reconstruct the deduplicated global field as a NumPy array."""
        a = np.asarray(A)
        ol = self.overlap
        for d in range(self.ndims):
            D = self.dims[d]
            n = self.local_shape[d]
            idx = lambda s: (slice(None),) * d + (s,)
            parts = [a[idx(slice(0, n))]]
            parts += [a[idx(slice(b * n + ol, (b + 1) * n))] for b in range(1, D)]
            a = np.concatenate(parts, axis=d)
        return a

    def scatter(self, G: np.ndarray):
        """Inverse of :meth:`gather`: build the stacked sharded field."""
        G = np.asarray(G)
        if G.shape != self.global_shape:
            raise ValueError(f"expected {self.global_shape}, got {G.shape}")
        a = G
        for d in range(self.ndims):
            D = self.dims[d]
            n = self.local_shape[d]
            stride = n - self.overlap
            idx = lambda s: (slice(None),) * d + (s,)
            parts = [a[idx(slice(b * stride, b * stride + n))] for b in range(D)]
            a = np.concatenate(parts, axis=d)
        return jax.device_put(a.astype(np.dtype(self.dtype)), self.sharding)

    # ------------------------------------------------------------------
    # grid hierarchy (geometric multigrid support)
    # ------------------------------------------------------------------
    def can_coarsen(self) -> bool:
        """True if every local interior extent halves evenly (see coarsen)."""
        return all(
            (n - self.overlap) % 2 == 0 and (n - self.overlap) >= 4
            for n in self.local_shape
        )

    def coarsen(self) -> "ImplicitGlobalGrid":
        """One-level-coarser grid on the SAME mesh/topology.

        Each local interior extent (``n - overlap``) halves; the halo width
        is preserved, so ``update_halo`` works identically at every level.
        Globally the interior cell count halves per dim (cell-centered
        coarsening): ``n_g - overlap`` fine interior cells map 2->1 onto
        ``n_gc - overlap`` coarse cells, which is what the separable
        full-weighting restriction / trilinear prolongation in
        :mod:`repro.solvers.multigrid` assume.
        """
        coarse = []
        for n in self.local_shape:
            inner = n - self.overlap
            if inner % 2 != 0:
                raise ValueError(
                    f"local interior extent {inner} must be even to coarsen"
                )
            if inner < 4:
                raise ValueError(
                    f"local interior extent {inner} too small to coarsen"
                )
            coarse.append(inner // 2 + self.overlap)
        while len(coarse) < 3:
            coarse.append(None)  # constructor drops None dims (2-D grids)
        return ImplicitGlobalGrid(
            *coarse,
            overlap=self.overlap,
            periodic=self.topo.periodic,
            mesh=self.mesh,
            axes=self.topo.axes,
            dtype=self.dtype,
        )

    def hierarchy(self, max_levels: int | None = None) -> list["ImplicitGlobalGrid"]:
        """Fine-to-coarse grid hierarchy, coarsening while possible."""
        levels = [self]
        while levels[-1].can_coarsen() and (
            max_levels is None or len(levels) < max_levels
        ):
            levels.append(levels[-1].coarsen())
        return levels

    def finalize(self):
        """Paper's ``finalize_global_grid()`` — releases cached executables."""
        self._jit_cache.clear()


def init_global_grid(nx, ny=1, nz=1, **kw) -> ImplicitGlobalGrid:
    """Paper-faithful alias for constructing the implicit global grid."""
    return ImplicitGlobalGrid(nx, ny, nz, **kw)
