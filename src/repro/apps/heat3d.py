"""Paper Fig. 1: stencil-based 3-D heat diffusion solver.

The JAX transliteration of the paper's Julia code — three grid calls turn
the single-device solver into a multi-device one:

    grid = init_global_grid(nx, ny, nz)        (line 23 of Fig. 1)
    ...   update_halo / hide_communication     (line 38 / 36)
    grid.finalize()                            (line 43)
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import telemetry as tele
from repro.core import ImplicitGlobalGrid, init_global_grid
from repro.kernels.stencil3d.ops import heat_step
from repro.stencil import fd3d as fd


@dataclasses.dataclass
class Heat3D:
    nx: int = 32
    ny: int = 32
    nz: int = 32
    lam: float = 1.0
    c0: float = 2.0
    lx: float = 1.0
    hide: tuple | None = (16, 2, 2)   # paper's @hide_communication tuple
    use_kernel: str = "auto"          # auto | pallas | interpret | ref
    bx: int | None = None             # kernel x-block (None = auto divisor)
    dims: tuple | None = None
    dtype: object = jnp.float32
    heartbeat: int = 0      # rank-0 heartbeat event every k solver iterations
    flight_dir: str | None = None  # per-rank flight-record dump directory

    def __post_init__(self):
        self.grid = init_global_grid(self.nx, self.ny, self.nz,
                                     dims=self.dims, dtype=self.dtype)
        g = self.grid
        self.dx = self.lx / (g.nx_g() - 1)
        self.dy = self.lx / (g.ny_g() - 1)
        self.dz = self.lx / (g.nz_g() - 1)
        self.dt = min(self.dx, self.dy, self.dz) ** 2 / self.lam / (1.0 / self.c0) / 6.1

        lam, dt, dx, dy, dz = self.lam, self.dt, self.dx, self.dy, self.dz

        def step(T, Ci):
            return heat_step(T, Ci, lam, dt, dx, dy, dz,
                             use_kernel=self.use_kernel, bx=self.bx)

        if self.hide is not None:
            # clamp the shell width so 2*(w+h) fits the local extent
            local = self.grid.local_shape
            hide = tuple(
                max(1, min(w, local[d] // 2 - 1))
                for d, w in enumerate(self.hide)
            )

            @g.parallel
            def dstep(T, Ci):
                return g.hide(step, (T, Ci), width=hide)
        else:
            hide = None

            @g.parallel
            def dstep(T, Ci):
                return g.update_halo(step(T, Ci))

        self._step = dstep
        # Exposed for the static analyzer (repro.analysis.driver), which
        # re-wraps the local step in a fresh shard_map to trace it.
        self._step_fn = step
        self._hide_widths = hide

    def init_fields(self):
        g = self.grid
        T = g.full(1.7)
        Ci = g.full(1.0 / self.c0)
        return T, Ci

    def run(self, nt: int, T=None, Ci=None):
        if T is None:
            T, Ci = self.init_fields()
        with self._observe(), \
                tele.region("heat3d.run", nt=nt, sync=lambda: T):
            for _ in range(nt):
                T = self._step(T, Ci)
            T.block_until_ready()
        return T, Ci

    def _observe(self):
        """Runtime observability per the app's ``heartbeat``/``flight_dir``
        fields (reentrant no-op when both are off/outer-installed)."""
        return tele.observe(heartbeat=self.heartbeat,
                            flight_dir=self.flight_dir,
                            meta={"app": "heat3d", "dims": self.grid.dims})

    def oracle(self, nt: int, T0: np.ndarray | None = None) -> np.ndarray:
        """Single-array NumPy reference on the deduplicated global grid,
        from ``T0`` (a gathered global field; default the constant
        :meth:`init_fields` value)."""
        g = self.grid
        G = (np.full(g.global_shape, 1.7, np.float64) if T0 is None
             else np.asarray(T0, np.float64))
        ci = 1.0 / self.c0
        a = self.dt * self.lam * ci
        for _ in range(nt):
            inn = G[1:-1, 1:-1, 1:-1]
            G2 = G.copy()
            G2[1:-1, 1:-1, 1:-1] = inn + a * (
                (G[2:, 1:-1, 1:-1] - 2 * inn + G[:-2, 1:-1, 1:-1]) / self.dx ** 2
                + (G[1:-1, 2:, 1:-1] - 2 * inn + G[1:-1, :-2, 1:-1]) / self.dy ** 2
                + (G[1:-1, 1:-1, 2:] - 2 * inn + G[1:-1, 1:-1, :-2]) / self.dz ** 2
            )
            G = G2
        return G

    # --- roofline bookkeeping (memory-bound stencil) --------------------
    def bytes_per_step_per_cell(self) -> int:
        # read T (7 pts but perfect reuse -> 1x), read Ci, write T2 @ dtype
        return 3 * np.dtype(self.dtype).itemsize

    def halo_bytes_per_step(self) -> int:
        """Bytes sent per device per halo update (6 faces, width 1)."""
        n = np.dtype(self.dtype).itemsize
        return 2 * n * (self.nx * self.ny + self.ny * self.nz + self.nx * self.nz)

    # --- paper's T_eff convention --------------------------------------
    def a_eff_per_step(self) -> int:
        """Effective bytes per time step: T read+written, Ci read once —
        ``(2 * 1 + 1) * n_cells * itemsize`` (identical to
        ``bytes_per_step_per_cell * n_cells``)."""
        n = int(np.prod(self.grid.global_shape))
        return tele.a_eff(n, n_unknown_fields=1, n_known_fields=1,
                          itemsize=np.dtype(self.dtype).itemsize)

    def t_eff(self, t_step_s: float) -> float:
        """T_eff in GB/s at a measured seconds-per-step."""
        return tele.t_eff(self.a_eff_per_step(), t_step_s)
