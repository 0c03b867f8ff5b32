"""3-D variable-coefficient Poisson, solved three ways.

    -div( c(x) grad u ) = f

on the implicit global grid, with the three solvers of
:mod:`repro.solvers` — CG, accelerated pseudo-transient, and geometric
multigrid — all judged on the same deduplicated global relative residual,
and validated against a single-array NumPy oracle (matrix-free CG on the
gathered global grid).

Boundary conditions per dim follow ``periodic``: ``u = 0`` on the
boundary ring of non-periodic dims, wraparound on periodic dims (the
coefficient and rhs are built wrap-consistent there).  With EVERY dim
periodic the operator is singular — ``cg``/``mgcg`` run with
``project_nullspace="constant"`` and ``mg`` projects internally, all
returning the mean-zero representative; ``pt`` is rejected (its optimal
damping needs ``lam_min > 0``).

This is the template for every future implicit/steady-state app: build a
grid, define the local-view operator, pick a solver.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import init_global_grid
from repro import solvers
from repro import telemetry as tele
from repro.solvers.multigrid import poisson_apply


@dataclasses.dataclass
class Poisson3D:
    nx: int = 10            # local extents INCLUDING the halo cells
    ny: int = 10
    nz: int = 10
    lx: float = 1.0         # domain edge length along x (y/z scale with N)
    coef_amp: float = 0.5   # c = 1 + amp * (smooth); keep < 1 for SPD
    periodic: tuple = (False, False, False)
    dims: tuple | None = None
    mesh: object = None     # optional explicit device mesh (subset runs)
    dtype: object = jnp.float64
    heartbeat: int = 0      # rank-0 heartbeat event every k solver iterations
    flight_dir: str | None = None  # per-rank flight-record dump directory
    use_kernel: str = "auto"  # fused Pallas hot path: auto|pallas|interpret|ref
    bx: int | None = None   # kernel x-block size (None = auto, fits VMEM)

    def __post_init__(self):
        if self.dtype == jnp.float64 and not jax.config.jax_enable_x64:
            raise ValueError(
                "Poisson3D(dtype=float64) needs jax x64 enabled first: "
                'jax.config.update("jax_enable_x64", True) '
                "(or pass dtype=jnp.float32)"
            )
        self.grid = init_global_grid(self.nx, self.ny, self.nz,
                                     dims=self.dims, mesh=self.mesh,
                                     periodic=self.periodic,
                                     dtype=self.dtype)
        g = self.grid
        self.singular = all(g.topo.periodic)  # shift-free + all-periodic

        # Uniform spacing, set by the x extent (y/z edges scale with N,
        # preserving the lx contract above); grid.span is periodic-aware
        # (N-1 node intervals for Dirichlet, N-overlap cells per period).
        self.dx = self.lx / g.span(0)
        self.spacing = (self.dx, self.dx, self.dx)
        N = g.global_shape

        amp = self.coef_amp
        per = g.topo.periodic
        h = g.halo

        # Normalized coordinate per dim: periodic dims use x = (i-h)/P so
        # any period-1 function of x is automatically wrap-consistent on
        # the ring duplicates (i == i +- P); Dirichlet dims keep i/(N-1).
        def coords(ix, iy, iz):
            out = []
            for d, i in enumerate((ix, iy, iz)):
                if per[d]:
                    out.append((i - h) / g.span(d))
                else:
                    out.append(i / (N[d] - 1))
            return out

        def c_fn(ix, iy, iz):
            x, y, z = coords(ix, iy, iz)
            return 1.0 + amp * jnp.sin(2 * jnp.pi * x) \
                * jnp.sin(2 * jnp.pi * y) * jnp.sin(2 * jnp.pi * z)

        def f_fn(ix, iy, iz):
            x, y, z = coords(ix, iy, iz)
            if not any(per):
                bump = jnp.exp(-((x - 0.5) ** 2 + (y - 0.5) ** 2
                                 + (z - 0.5) ** 2) / 0.02)
                return bump * jnp.sin(jnp.pi * x)
            # periodic dims need a wrap-consistent (period-1) rhs; the
            # product of sines is also mean-zero, keeping the singular
            # all-periodic system consistent.
            parts = [
                jnp.sin(2 * jnp.pi * v) if per[d] else jnp.sin(jnp.pi * v)
                for d, v in enumerate((x, y, z))
            ]
            return parts[0] * parts[1] * parts[2]

        self.c = g.from_global_fn(c_fn)
        self.b = g.from_global_fn(f_fn)

    # ------------------------------------------------------------------
    # operator (local view)
    # ------------------------------------------------------------------
    def apply_A(self, u, c):
        return poisson_apply(self.grid, u, c, self.spacing,
                             use_kernel=self.use_kernel, bx=self.bx)

    def apply_A_overlap(self, u, c):
        """Same operator with the halo exchange overlapped against the
        bulk stencil (``hide_apply``); identical arithmetic (shell cells
        may round differently by ~1 ulp).  The overlapped split is not
        kernelized — ``use_kernel="auto"`` quietly keeps the ref path
        here (an explicit kernel request raises)."""
        return poisson_apply(self.grid, u, c, self.spacing, hide=True,
                             use_kernel=self.use_kernel, bx=self.bx)

    def spectral_bounds(self) -> tuple[float, float]:
        """(lam_min, lam_max) estimates for the pseudo-transient solver.

        Gershgorin upper bound; lowest-Fourier-mode lower bound (exact
        for constant coefficients, a safe underestimate for smooth ones).
        Periodic dims admit modes constant along them, so only Dirichlet
        dims contribute to ``lam_min`` — all-periodic gives 0 (singular).
        """
        g = self.grid
        c_min = float(solvers.field_min_g(g, self.c))
        c_max = float(solvers.field_max_g(g, self.c))
        lam_max = c_max * sum(4.0 / h ** 2 for h in self.spacing)
        lam_min = c_min * sum(
            (np.pi / ((n - 1) * h)) ** 2
            for d, (n, h) in enumerate(zip(g.global_shape, self.spacing))
            if not g.topo.periodic[d]
        )
        return lam_min, lam_max

    # ------------------------------------------------------------------
    # telemetry (paper's effective-memory-throughput convention)
    # ------------------------------------------------------------------
    def a_eff_per_iteration(self) -> int:
        """Effective bytes per solver iteration: the unknown ``u`` is
        read and written once, the known coefficient ``c`` and rhs ``b``
        read once — ``(2 * 1 + 2) * n_cells * itemsize``."""
        n = int(np.prod(self.grid.global_shape))
        return tele.a_eff(n, n_unknown_fields=1, n_known_fields=2,
                          itemsize=jnp.dtype(self.dtype).itemsize)

    def t_eff(self, info) -> float:
        """T_eff in GB/s for a recorded solve (NaN before timing)."""
        return tele.t_eff(self.a_eff_per_iteration(), info.s_per_iter())

    # ------------------------------------------------------------------
    # solves
    # ------------------------------------------------------------------
    def solve(self, method: str = "cg", tol: float = 1e-6,
              maxiter: int | None = None, overlap: bool = False, **kw):
        """Solve with ``method`` in {"cg", "pipecg", "mgcg", "pipemgcg",
        "pt", "mg"}.

        ``pipecg``/``pipemgcg`` are the Ghysels–Vanroose pipelined
        schedules of cg/mgcg (``solvers.cg(variant="pipelined")``): one
        fused all-reduce per iteration, overlapped with the operator and
        preconditioner applies.  ``overlap=True`` (cg family) switches
        the operator to the communication-hiding application.  Returns
        ``(u, info)``.
        """
        with self._observe(), \
                tele.region(f"poisson.solve.{method}",
                            singular=self.singular, overlap=overlap):
            return self._solve(method, tol, maxiter, overlap, **kw)

    def _observe(self):
        """Runtime observability per the app's ``heartbeat``/``flight_dir``
        fields (reentrant no-op when both are off/outer-installed)."""
        return tele.observe(heartbeat=self.heartbeat,
                            flight_dir=self.flight_dir,
                            meta={"app": "poisson", "dims": self.grid.dims})

    def _solve(self, method, tol, maxiter, overlap, **kw):
        apply_A = self.apply_A_overlap if overlap else self.apply_A
        project = "constant" if self.singular else None
        if method in ("pipecg", "pipemgcg"):
            kw.setdefault("variant", "pipelined")
            method = "cg" if method == "pipecg" else "mgcg"
        if method == "cg":
            return solvers.cg(
                self.grid, apply_A, self.b, tol=tol,
                maxiter=maxiter or 2000, args=(self.c,),
                project_nullspace=project, **kw)
        if method == "mgcg":
            if not hasattr(self, "_mg_precond"):
                self._mg_precond = solvers.CyclePreconditioner(
                    self.grid, self.spacing,
                    use_kernel=self.use_kernel, bx=self.bx)
            return solvers.cg(
                self.grid, apply_A, self.b, tol=tol,
                maxiter=maxiter or 2000, args=(self.c,),
                apply_M=self._mg_precond,
                project_nullspace=project, **kw)
        if method == "pt":
            if self.singular:
                raise ValueError(
                    "method='pt' needs lam_min > 0, but the all-periodic "
                    "Poisson operator is singular — use 'cg'/'mgcg' "
                    "(nullspace-projected) or 'mg', or pin one dim "
                    "non-periodic")
            lam_min, lam_max = self.spectral_bounds()
            return solvers.pseudo_transient(
                self.grid, apply_A, self.b, tol=tol,
                maxiter=maxiter or 20000, args=(self.c,),
                lam_min=lam_min, lam_max=lam_max, **kw)
        if method == "mg":
            if overlap:
                raise ValueError(
                    "overlap=True is not supported for 'mg' (the V-cycle "
                    "manages its own halo updates)")
            kw.setdefault("use_kernel", self.use_kernel)
            kw.setdefault("bx", self.bx)
            return solvers.multigrid_solve(
                self.grid, self.c, self.b, self.spacing, tol=tol,
                maxiter=maxiter or 100, **kw)
        raise ValueError(f"unknown method {method!r}")

    def residual_norm(self, u) -> float:
        """Relative residual over the unknowns — same mask and zero-rhs
        guard as the solvers' convergence test, so it matches
        ``SolveInfo.relres`` (for the singular all-periodic system both
        are judged against the mean-zero projection of the rhs)."""
        g = self.grid

        def _rel(b, u, c):
            mask = solvers.solve_mask(g, b.dtype)
            if self.singular:
                b = b - solvers.reductions.masked_mean(
                    g, b, mask).astype(b.dtype)
            r = b - self.apply_A(u, c)
            return solvers.norm_l2(g, r, mask) \
                / solvers.reductions.rhs_norm(g, b, mask)

        return float(solvers.reductions.host_reduce(
            g, _rel, self.b, u, self.c))

    # ------------------------------------------------------------------
    # NumPy oracle (single global array, matrix-free CG)
    # ------------------------------------------------------------------
    def oracle(self, tol: float = 1e-10, maxiter: int = 20000) -> np.ndarray:
        """Matrix-free NumPy CG on the gathered global arrays.

        Mirrors the distributed algorithm exactly: the ring planes of
        periodic dims are ghost cells refreshed by a wrap copy before
        each operator application (the single-array analogue of the
        wraparound halo exchange), and the singular all-periodic system
        is projected onto mean-zero (rhs and returned solution).
        """
        g = self.grid
        per = g.topo.periodic
        c = g.gather(self.c).astype(np.float64)
        b = g.gather(self.b).astype(np.float64)
        h2 = np.asarray(self.spacing, np.float64) ** 2
        inner = (slice(1, -1),) * 3

        def wrap(u):
            # periodic ghost update (h = 1): ring == opposite interior
            for d in range(3):
                if not per[d]:
                    continue
                lo = [slice(None)] * 3
                hi = [slice(None)] * 3
                lo[d], hi[d] = 0, -2
                u[tuple(lo)] = u[tuple(hi)]
                lo[d], hi[d] = -1, 1
                u[tuple(lo)] = u[tuple(hi)]
            return u

        wrap(c)

        def demean(u):
            if self.singular:
                u[inner] -= u[inner].mean()
            return u

        def apply_A(u):
            u = wrap(u.copy())
            out = np.zeros_like(u)
            u0 = u[1:-1, 1:-1, 1:-1]
            c0 = c[1:-1, 1:-1, 1:-1]
            acc = np.zeros_like(u0)
            for d in range(3):
                sl_p = [slice(1, -1)] * 3
                sl_m = [slice(1, -1)] * 3
                sl_p[d] = slice(2, None)
                sl_m[d] = slice(None, -2)
                cf_p = 0.5 * (c0 + c[tuple(sl_p)])
                cf_m = 0.5 * (c0 + c[tuple(sl_m)])
                acc += (cf_p * (u[tuple(sl_p)] - u0)
                        - cf_m * (u0 - u[tuple(sl_m)])) / h2[d]
            out[1:-1, 1:-1, 1:-1] = -acc
            return out

        b = demean(b.copy())
        x = np.zeros_like(b)
        r = np.zeros_like(b)
        r[inner] = b[inner]
        p = r.copy()
        rs = float((r[inner] ** 2).sum())
        bnorm = rs ** 0.5 or 1.0
        for _ in range(maxiter):
            if rs ** 0.5 <= tol * bnorm:
                break
            Ap = apply_A(p)
            alpha = rs / float((p[inner] * Ap[inner]).sum())
            x += alpha * p
            r[inner] -= alpha * Ap[inner]
            rs_new = float((r[inner] ** 2).sum())
            p = r + (rs_new / rs) * p
            rs = rs_new
        return wrap(demean(x))
