"""Geometric multigrid (V-cycle) on the implicit global grid.

Levels come from :meth:`ImplicitGlobalGrid.hierarchy`: every level shares
the SAME device mesh and Cartesian topology, halo width preserved, so the
one ``update_halo`` works at every depth — only the local block shrinks
(fine interior extent ``n - overlap`` halves per level).  With the
blocks' interiors halving uniformly, the grid-transfer operators are
block-local stencils followed by one halo exchange — and the whole cycle
is LOCATION-GENERIC: ``make_v_cycle(loc=...)`` smooths/transfers a field
at any staggering location with the per-location transfer pairs of
:mod:`repro.solvers.transfers` (cell-centered full weighting +
(tri)linear prolongation on non-staggered dims; vertex-weighted
transfers on the staggered dim of a face field, where coarse faces
coincide with every other fine face), location-aware interior masks
(pinned boundary faces and the dead plane stay zero at every level) and
the matching operator — :func:`_poisson_stencil` at centers,
:func:`face_stencil` on faces.  :func:`make_tree_v_cycle` extends this
to COUPLED tuples of staggered components smoothed against one operator
(the full-stress Stokes velocity block).

The level mapping (derived from the stacked-block layout): coarse local
cell ``i`` has fine children ``2i-1, 2i`` per dim (the cell-centered
``I_f = 2 I_c`` coarsening), while on a staggered dim coarse face ``i``
coincides with fine face ``2i`` — either way the fine points a transfer
reads always live in the local fine block and its halo, so restriction
and prolongation need NO communication beyond the one halo update, at
every location.

Two smoothers are available on the flux-form variable-coefficient Poisson
operator ``A u = -div(c grad u)`` (also exported here for the CG /
pseudo-transient solvers):

* ``"jacobi"`` — damped Jacobi (default damping 6/7);
* ``"chebyshev"`` — a 3-term-recurrence Chebyshev iteration on the
  Jacobi-preconditioned operator ``D^-1 A`` over the upper-spectrum
  interval ``[lam_max/4, lam_max]`` with the Gershgorin bound
  ``lam_max = 2`` (flux form: the off-diagonal row sum equals the
  diagonal).  NO extra global reductions — the bounds are analytic, and
  the residual polynomial is ``<= 1`` below the interval, so smooth modes
  are never amplified.  Better variable-coefficient smoothing at scale.

The coarsest level is always solved with damped-Jacobi sweeps (a
Chebyshev *solver* would need a lower spectral bound).

The V-cycle is exposed two ways: :func:`multigrid_solve` iterates cycles
to tolerance (one ``lax.while_loop`` under one ``shard_map``, like the
other solvers), and :func:`make_v_cycle` builds the cycle as a reusable
local-view closure — e.g. as the preconditioner inside
:func:`repro.solvers.cg.cg` (see
:class:`repro.solvers.preconditioner.CyclePreconditioner`).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import telemetry as tele
from repro.analysis import capture as _ana
from repro.core import hide as _hide
from repro.core import locations as _loc
from repro.core.grid import ImplicitGlobalGrid
from repro.kernels import dispatch as _dispatch
from repro.kernels.solver3d import kernel as _sk
from repro.kernels.solver3d.ref import poisson_diag, poisson_stencil
from repro.stencil import mac as _mac
from repro.telemetry.flight import note_solve as _note_solve
from repro.telemetry import health as _health
from . import reductions as red
from . import transfers
from .cg import SolveInfo

# Historical name: the canonical spelling now lives in
# repro.kernels.solver3d.ref so the solver ref path and the fused-kernel
# oracle are literally the same function (they cannot drift apart).
_poisson_stencil = poisson_stencil

SMOOTHERS = ("jacobi", "chebyshev")


def _sl(nd: int, d: int, start, stop, step=None) -> tuple:
    """Slice dim ``d``, interior (``1:-1``) of every other dim."""
    s = [slice(1, -1)] * nd
    s[d] = slice(start, stop, step)
    return tuple(s)


def _sd(nd: int, d: int, start, stop, step=None) -> tuple:
    """Slice dim ``d`` only; other dims stay full (separable passes)."""
    s: list = [slice(None)] * nd
    s[d] = slice(start, stop, step)
    return tuple(s)


def _inner(nd: int) -> tuple:
    return (slice(1, -1),) * nd


def _shift(a, d: int, s: int):
    """Interior-of-other-dims slab shifted by ``s`` along dim ``d``."""
    n = a.shape[d]
    return a[_sl(a.ndim, d, 1 + s, n - 1 + s)]


# ---------------------------------------------------------------------------
# flux-form variable-coefficient Poisson operator (local view)
# ---------------------------------------------------------------------------

def poisson_apply(grid: ImplicitGlobalGrid, u, c, spacing,
                  update_halo=True, hide=False, shift=None,
                  use_kernel: str = "auto", bx: int | None = None):
    """``A u = -div(c grad u)`` on the interior, zero on the ring.

    ``c`` is the cell-centered coefficient (halo-consistent); face
    coefficients are arithmetic averages of the two adjacent cells.
    ``shift`` (optional halo-consistent cell-centered field) makes the
    operator Helmholtz-like: ``A u = shift * u - div(c grad u)`` — e.g.
    an implicit time step's ``1/dt + 1/eta``
    (:mod:`repro.apps.twophase_ops`).

    ``hide=True`` overlaps the halo exchange of ``u`` with the stencil on
    the locally valid bulk via :func:`repro.core.hide.hide_apply` (same
    arithmetic, ~1-ulp shell differences at most): the exchange covers
    only the thin shell of output cells adjacent to the halos, which is
    recomputed after.

    ``use_kernel`` selects the fused Pallas apply kernel
    (:mod:`repro.kernels.solver3d`) behind the shared dispatch contract:
    ``"auto"`` uses it when the capability probe passes (TPU, supported
    dtype, divisible block) and falls back to this reference otherwise;
    the kernel does not implement ``hide`` or Helmholtz ``shift``, so
    those configurations always take the reference path (silently under
    auto, ``ValueError`` under an explicit request).
    """
    unsupported = None
    if hide:
        unsupported = "hide=True (overlapped apply)"
    elif shift is not None:
        unsupported = "Helmholtz shifts"
    elif u.ndim != 3:
        unsupported = f"a {u.ndim}-D field (kernels are 3-D)"
    impl, nbx = _dispatch.resolve(use_kernel, shape=u.shape, dtype=u.dtype,
                                  bx=bx, unsupported=unsupported,
                                  where="multigrid.poisson_apply",
                                  blocks=_dispatch.VMEM_BLOCKS["apply"])
    if impl != "ref":
        if update_halo:
            u = grid.update_halo(u)
        return _sk.apply_pallas(u, c, h2=tuple(float(s) ** 2 for s in spacing),
                                bx=nbx, interpret=impl == "interpret")
    if hide:
        if not update_halo:
            raise ValueError("hide=True already includes the halo update")
        if grid.halo != 1:
            raise ValueError("hide=True requires halo width 1 (3-point stencil)")
        if shift is None:
            return _hide.hide_apply(
                grid.topo, lambda uu, cc: _poisson_stencil(uu, cc, spacing),
                u, c, halo=grid.halo)
        return _hide.hide_apply(
            grid.topo,
            lambda uu, cc, ss: _poisson_stencil(uu, cc, spacing, ss),
            u, c, shift, halo=grid.halo)
    if update_halo:
        u = grid.update_halo(u)
    return _poisson_stencil(u, c, spacing, shift)


# ---------------------------------------------------------------------------
# staggered (face-located) flux-form operator (local view)
# ---------------------------------------------------------------------------

def face_stencil(u, c, spacing, sd: int):
    """``-div(c grad u)`` for ``u`` staggered along ``sd``; ``c`` center.

    Staggered coefficient placement: along the staggered dim the flux
    between like faces ``i`` and ``i + 1`` sits at center ``i + 1``, so
    the coefficient is the CENTER value; across dims the flux sits at an
    edge, so it is the 4-point edge average.  Valid on the local
    interior only — the caller multiplies by the location's interior
    mask (which also keeps pinned boundary faces and the dead plane
    zero).  The arithmetic is the canonical MAC spelling of
    :mod:`repro.stencil.mac` — the same one the Stokes operator and
    oracle use, so the face cycle smooths exactly the operator CG
    iterates on.
    """
    return _mac.stripped_component(jnp, u, c, spacing, sd)


def face_diag(c, spacing, sd: int):
    """Diagonal of :func:`face_stencil` (full local shape, for Jacobi)."""
    return _mac.stripped_diag_component(jnp, c, spacing, sd)


# ---------------------------------------------------------------------------
# grid-transfer operators (canonical per-location pairs in .transfers;
# historical center-only names kept as the public aliases)
# ---------------------------------------------------------------------------

def restrict_full_weighting(fine):
    """Center restriction (see :func:`repro.solvers.transfers.restrict`)."""
    return transfers.restrict(fine, "center")


def prolong_trilinear(coarse):
    """Center prolongation (see :func:`repro.solvers.transfers.prolong`)."""
    return transfers.prolong(coarse, "center")


def coarsen_coefficient(c):
    """Coefficient coarsening (see :mod:`repro.solvers.transfers`)."""
    return transfers.coarsen_coefficient(c)


# ---------------------------------------------------------------------------
# V-cycle construction (shared by the solver and the CG preconditioner)
# ---------------------------------------------------------------------------

def level_spacings(grid: ImplicitGlobalGrid, grids, spacing):
    """Per-level grid spacings from each level's true global node count.

    NOT a naive ``2**level`` — on Dirichlet dims the ring nodes don't
    coarsen, so the exact factor is ``(N_fine-1)/(N_coarse-1)`` per dim;
    getting this wrong mis-scales deep coarse operators by up to ~50% in
    ``1/h^2`` and stalls the cycle.  On periodic dims the unique cell
    count is ``N - overlap`` (the ring is a wrap duplicate), which halves
    exactly per level, so the factor is exactly 2 there.
    """
    spacing = tuple(float(s) for s in spacing)
    lengths = [grid.span(d) * h for d, h in enumerate(spacing)]
    return [
        tuple(L / g.span(d) for d, L in enumerate(lengths))
        for g in grids
    ]


def build_coefficients(grid: ImplicitGlobalGrid, grids, c):
    """Per-level halo-consistent coefficient fields (local view)."""
    cs = [grid.update_halo(c)]
    for _ in grids[1:]:
        cs.append(grid.update_halo(coarsen_coefficient(cs[-1])))
    return cs


# Chebyshev smoothing interval on D^-1 A: Gershgorin gives lam_max = 2 for
# the flux-form operator; the standard upper-spectrum target [b/4, b].
_CHEB_UPPER = 2.0
_CHEB_RATIO = 4.0


def _cheb_rhos(degree: int, upper: float = _CHEB_UPPER,
               ratio: float = _CHEB_RATIO) -> tuple[float, float, list[float]]:
    """(theta, delta, [rho_1..rho_degree]) of the 3-term recurrence."""
    a, b = upper / ratio, upper
    theta, delta = (b + a) / 2.0, (b - a) / 2.0
    sigma1 = theta / delta
    rhos = [1.0 / sigma1]
    for _ in range(degree - 1):
        rhos.append(1.0 / (2.0 * sigma1 - rhos[-1]))
    return theta, delta, rhos


def make_v_cycle(
    grid: ImplicitGlobalGrid,
    grids,
    hs,
    cs,
    *,
    loc: str = "center",
    shifts=None,
    nu_pre: int = 2,
    nu_post: int = 2,
    omega: float = 6.0 / 7.0,
    coarse_sweeps: int = 100,
    smoother: str = "jacobi",
    use_kernel: str = "auto",
    bx: int | None = None,
):
    """Build ``(v_cycle, residual)`` local-view closures over a hierarchy.

    ``grids``/``hs``/``cs`` are the per-level grids, spacings
    (:func:`level_spacings`) and halo-consistent CENTER coefficients
    (:func:`build_coefficients` — one coefficient hierarchy serves every
    location).  ``v_cycle(level, u, f)`` takes a halo-consistent iterate
    and a rhs that is zero outside the location's unknowns;
    ``residual(level, u, f)`` is ``f - A u``, zero outside the unknowns.

    ``loc`` makes the WHOLE cycle location-generic: for a face location
    the level operator is the staggered flux-form stencil
    (:func:`face_stencil`: center coefficient along the staggered dim,
    edge-averaged across), the smoother diagonal, residual and updates
    are masked by the location's interior mask (pinned boundary faces
    and the dead plane stay exactly zero at every level), and the
    transfers are the per-location pairs of
    :mod:`repro.solvers.transfers` — vertex-weighted along the staggered
    dim, where coarse faces coincide with every other fine face.  Every
    level still needs exactly one ``update_halo`` per transfer/sweep,
    for every location.

    ``shifts`` (optional, center only) are per-level halo-consistent
    cell-centered fields ``s >= 0`` turning the operator Helmholtz-like:
    ``A u = s u - div(c grad u)`` — e.g. the ``1/dt + 1/eta`` shift of an
    implicit time step (:mod:`repro.apps.twophase_ops`).  Build them with
    :func:`build_coefficients` like the coefficients; the shift joins the
    smoother diagonal, so the analytic Chebyshev bound ``lam_max = 2`` on
    ``D^-1 A`` still holds (the off-diagonal row sum stays <= the
    unshifted diagonal).

    ``smoother`` selects damped Jacobi or the 3-term Chebyshev smoother
    for the pre/post sweeps (``nu_pre``/``nu_post`` = sweeps resp.
    polynomial degree); the coarsest level always uses Jacobi sweeps.

    ``use_kernel`` routes the smoother sweeps and residuals through the
    fused Pallas kernels of :mod:`repro.kernels.solver3d` (one pass over
    each VMEM tile per sweep: stencil + residual + diagonal scale +
    axpy).  The capability probe runs PER LEVEL — a coarse level whose
    local extent no longer divides into blocks (or a Helmholtz-shifted
    cycle, which the kernels don't implement) falls back to the
    reference spelling under ``"auto"``, so deep hierarchies mix fused
    fine levels with reference coarse levels.  An explicit ``bx``
    applies to the finest level only; deeper levels auto-pick
    (:func:`repro.kernels.dispatch.pick_bx`).  With every level on
    ``"ref"`` the closures are the historical arithmetic, lowering to
    the same HLO as before the kernels existed.

    Periodic dims need no special casing in the cycle itself: every
    level shares the topology (coarse grids inherit ``topo.periodic``),
    so each ``update_halo`` wraps the ring planes and the transfers read
    wrap-consistent halos — the cell-centered identification
    ``i == i +- (N - overlap)`` is preserved exactly under 2:1
    coarsening.  The one genuine difference is the ALL-periodic
    shift-free case, where the operator is singular: the coarse-level
    rhs is projected onto mean-zero before the coarse sweeps (see
    ``_demean``) so the Jacobi solve cannot pump the constant mode.
    """
    if smoother not in SMOOTHERS:
        raise ValueError(f"unknown smoother {smoother!r}; pick from {SMOOTHERS}")
    sd = _loc.stagger_dim(loc)
    if sd is not None and shifts is not None:
        raise ValueError(
            "Helmholtz shifts are only supported for the center cycle "
            f"(got loc={loc!r})")
    nd = grid.ndims

    # Per-level kernel dispatch: one probe per level at build time (the
    # choice is baked into the traced program).  Coarse levels whose
    # local extent has no usable block divisor degrade to "ref"
    # individually under "auto"; shifted cycles are ref everywhere.
    unsupported = None
    if shifts is not None:
        unsupported = "Helmholtz shifts"
    elif nd != 3:
        unsupported = f"a {nd}-D hierarchy (kernels are 3-D)"
    suffix = "" if sd is None else "_face"
    blocks = max(_dispatch.VMEM_BLOCKS[op + suffix]
                 for op in ("residual", "jacobi", "cheb"))
    impls, bxs = [], []
    for k, g in enumerate(grids):
        impl_k, bx_k = _dispatch.resolve(
            use_kernel, shape=g.local_shape, dtype=cs[0].dtype,
            bx=bx if k == 0 else None, unsupported=unsupported,
            where=f"multigrid.v_cycle[level {k}]", blocks=blocks)
        impls.append(impl_k)
        bxs.append(bx_k)
    fused_any = any(i != "ref" for i in impls)
    h2s = [tuple(float(s) ** 2 for s in hk) for hk in hs]

    # All-periodic + shift-free: every level's operator annihilates
    # constants.  The coarse rhs is kept mean-zero (wrap-aware masked
    # mean) so the coarse Jacobi sweeps cannot pump the nullspace mode —
    # without this the correction grows linearly with coarse_sweeps.
    singular = shifts is None and all(grid.topo.periodic)

    def _demean(level, f):
        g = grids[level]
        m = red.loc_solve_mask(g, loc, f.dtype)
        mean = red.masked_mean(g, f, m)
        return f - mean.astype(f.dtype)

    if sd is None:
        # ---- center: interior-slab stencil, updates on the local
        # interior (identical arithmetic to the original cycle) --------
        dias = [poisson_diag(ck, hk) for ck, hk in zip(cs, hs)]
        if shifts is not None:
            dias = [dk + sk[_inner(nd)] for dk, sk in zip(dias, shifts)]
        if fused_any:
            # Full-shape safe-divide diagonals for the fused kernels
            # (ones on the ring, the interior diagonal inside) — only
            # built when some level actually runs fused, so the all-ref
            # cycle traces exactly the historical program.
            fdias = [jnp.ones_like(ck).at[_inner(nd)].set(dk)
                     for ck, dk in zip(cs, dias)]

        def residual(level, u, f):
            """f - A u on the interior, zero ring (u halo-consistent)."""
            if impls[level] != "ref":
                return _sk.residual_pallas(
                    u, cs[level], f, h2=h2s[level], bx=bxs[level],
                    interpret=impls[level] == "interpret")
            Au = poisson_apply(grids[level], u, cs[level], hs[level],
                               update_halo=False, use_kernel="ref",
                               shift=None if shifts is None else shifts[level])
            r = f[_inner(nd)] - Au[_inner(nd)]
            return jnp.zeros_like(u).at[_inner(nd)].set(r)

        def add_scaled(level, u, r, scale):
            return u.at[_inner(nd)].add(scale * r[_inner(nd)] / dias[level])

        def precond_residual(level, u, f):
            return residual(level, u, f)[_inner(nd)] / dias[level]

        def add_corr(u, d):
            return u.at[_inner(nd)].add(d)
    else:
        # ---- staggered: roll-form face stencil, everything masked by
        # the per-level location interior mask (pinned faces + dead
        # plane stay zero at every depth) ------------------------------
        imasks = [_loc.interior_mask(g, loc, ck.dtype)
                  for g, ck in zip(grids, cs)]
        dias = [face_diag(ck, hk, sd) * mk + (1.0 - mk)   # safe to divide
                for ck, hk, mk in zip(cs, hs, imasks)]
        if fused_any:
            fdias = dias  # already full-shape and safe to divide

        def residual(level, u, f):
            """f - A u on the unknowns of ``loc``, zero elsewhere."""
            if impls[level] != "ref":
                return _sk.residual_pallas(
                    u, cs[level], f, h2=h2s[level], sd=sd,
                    imask=imasks[level], bx=bxs[level],
                    interpret=impls[level] == "interpret")
            Au = face_stencil(u, cs[level], hs[level], sd)
            return (f - Au) * imasks[level]

        def add_scaled(level, u, r, scale):
            return u + scale * r / dias[level]

        def precond_residual(level, u, f):
            return residual(level, u, f) / dias[level]

        def add_corr(u, d):
            return u + d

    def jacobi(level, u, f, iters):
        if impls[level] != "ref":
            itp = impls[level] == "interpret"
            mk = None if sd is None else imasks[level]

            def kbody(_, u):
                return grid.update_halo(_sk.jacobi_pallas(
                    u, cs[level], f, fdias[level], omega=omega,
                    h2=h2s[level], sd=sd, imask=mk, bx=bxs[level],
                    interpret=itp))

            return jax.lax.fori_loop(0, iters, kbody, u)

        def body(_, u):
            r = residual(level, u, f)
            return grid.update_halo(add_scaled(level, u, r, omega))

        return jax.lax.fori_loop(0, iters, body, u)

    def chebyshev(level, u, f, degree):
        # 3-term recurrence on D^-1 A over [lam_max/4, lam_max]; the
        # rho_k are analytic constants — no reductions, fully unrolled.
        theta, delta, rhos = _cheb_rhos(degree)
        if impls[level] != "ref":
            # Fused recurrence: residual + diag scale + d-update + axpy
            # in one kernel pass per step (same spelling as below).
            itp = impls[level] == "interpret"
            mk = None if sd is None else imasks[level]
            u, d = _sk.cheb_pallas(u, cs[level], f, fdias[level],
                                   jnp.zeros_like(u), a=None, b=theta,
                                   h2=h2s[level], sd=sd, imask=mk,
                                   bx=bxs[level], interpret=itp)
            u = grid.update_halo(u)
            for k in range(1, degree):
                u, d = _sk.cheb_pallas(u, cs[level], f, fdias[level], d,
                                       a=rhos[k] * rhos[k - 1],
                                       b=2.0 * rhos[k] / delta,
                                       h2=h2s[level], sd=sd, imask=mk,
                                       bx=bxs[level], interpret=itp)
                u = grid.update_halo(u)
            return u
        z = precond_residual(level, u, f)
        d = z / theta
        u = grid.update_halo(add_corr(u, d))
        for k in range(1, degree):
            z = precond_residual(level, u, f)
            d = (rhos[k] * rhos[k - 1]) * d + (2.0 * rhos[k] / delta) * z
            u = grid.update_halo(add_corr(u, d))
        return u

    smooth = jacobi if smoother == "jacobi" else chebyshev

    def restrict_to(level, r):
        fc = transfers.restrict(r, loc)
        if sd is not None:
            fc = fc * imasks[level]
        return fc

    def prolong_to(level, ec):
        e = transfers.prolong(ec, loc)
        if sd is not None:
            e = e * imasks[level]
        return e

    def v_cycle(level, u, f):
        if level == len(grids) - 1:
            if singular:
                f = _demean(level, f)
            return jacobi(level, u, f, coarse_sweeps)
        u = smooth(level, u, f, nu_pre)
        r = grid.update_halo(residual(level, u, f))
        fc = grid.update_halo(restrict_to(level + 1, r))
        ec = v_cycle(
            level + 1,
            jnp.zeros(grids[level + 1].local_shape, u.dtype),
            fc,
        )
        e = grid.update_halo(prolong_to(level, ec))
        u = u + e
        return smooth(level, u, f, nu_post)

    return v_cycle, residual


def make_tree_v_cycle(
    grid: ImplicitGlobalGrid,
    grids,
    locs,
    apply_level,
    diag_level,
    *,
    nu_pre: int = 1,
    nu_post: int = 1,
    omega: float = 0.6,
    coarse_sweeps: int = 50,
    smoother: str = "jacobi",
    cheb_upper: float = 3.0,
):
    """V-cycle over a TUPLE of staggered components coupled by ONE operator.

    The scalar :func:`make_v_cycle` smooths each unknown field against
    its own operator; systems whose components couple through the
    operator itself — the full-stress Stokes velocity block, where the
    symmetric-gradient shear ties ``vx``/``vy``/``vz`` together — need
    the cycle to smooth and transfer the WHOLE tuple at once, each leaf
    on its own staggered grid.  That is what this builds:

    * ``locs`` — per-leaf staggering locations (e.g.
      ``("xface", "yface", "zface")``), fixing each leaf's transfers
      (:mod:`repro.solvers.transfers`) and interior masks at every level;
    * ``apply_level(level, u_tuple) -> tuple`` — the coupled operator on
      halo-consistent leaves, raw/unmasked (the cycle masks);
    * ``diag_level(level) -> tuple`` — full-shape positive per-leaf
      diagonals of that operator (coupling terms never touch a leaf's
      own diagonal, so pointwise Jacobi remains symmetric).

    Smoothing is damped block-pointwise Jacobi or the 3-term Chebyshev
    recurrence on ``D^-1 A``; for a coupled operator the Gershgorin
    row-sum includes the cross-component entries, so the analytic bound
    is ``cheb_upper`` (= 3 for the full-stress block: the coupling adds
    at most one extra diagonal's worth of row sum) and the default
    Jacobi damping is lowered to ``omega = 0.6 < 2/3`` accordingly.

    Per level and sweep/transfer there is still exactly ONE halo
    exchange — of all leaves together (`update_halo` batches them).
    Restriction/prolongation are per-leaf, so ``P = 2**ndims R^T`` holds
    leaf-wise and the cycle with ``nu_pre == nu_post`` is a symmetric
    preconditioner for tree-CG over the same FieldSet.

    Returns ``(v_cycle, residual)``; both take and return tuples of raw
    local arrays (callers wrap/unwrap their FieldSet leaves).
    """
    if smoother not in SMOOTHERS:
        raise ValueError(f"unknown smoother {smoother!r}; pick from {SMOOTHERS}")
    locs = tuple(locs)
    imasks = [
        tuple(_loc.interior_mask(g, loc, grid.dtype) for loc in locs)
        for g in grids
    ]
    dias = [
        tuple(dk * mk + (1.0 - mk)          # safe to divide everywhere
              for dk, mk in zip(diag_level(level), imasks[level]))
        for level in range(len(grids))
    ]

    def _halo(u):
        out = grid.update_halo(*u)
        return out if isinstance(out, tuple) else (out,)

    def residual(level, u, f):
        """f - A u on each leaf's unknowns, zero elsewhere."""
        Au = apply_level(level, u)
        return tuple((fi - ai) * mi
                     for fi, ai, mi in zip(f, Au, imasks[level]))

    def jacobi(level, u, f, iters):
        def body(_, u):
            r = residual(level, u, f)
            return _halo(tuple(
                ui + omega * ri / di
                for ui, ri, di in zip(u, r, dias[level])))

        return jax.lax.fori_loop(0, iters, body, u)

    def chebyshev(level, u, f, degree):
        theta, delta, rhos = _cheb_rhos(degree, upper=cheb_upper)
        z = tuple(ri / di
                  for ri, di in zip(residual(level, u, f), dias[level]))
        d = tuple(zi / theta for zi in z)
        u = _halo(tuple(ui + di for ui, di in zip(u, d)))
        for k in range(1, degree):
            z = tuple(ri / di
                      for ri, di in zip(residual(level, u, f), dias[level]))
            d = tuple((rhos[k] * rhos[k - 1]) * di + (2.0 * rhos[k] / delta) * zi
                      for di, zi in zip(d, z))
            u = _halo(tuple(ui + di for ui, di in zip(u, d)))
        return u

    smooth = jacobi if smoother == "jacobi" else chebyshev

    def v_cycle(level, u, f):
        if level == len(grids) - 1:
            return jacobi(level, u, f, coarse_sweeps)
        u = smooth(level, u, f, nu_pre)
        r = _halo(residual(level, u, f))
        fc = _halo(tuple(
            transfers.restrict(ri, loc) * mi
            for ri, loc, mi in zip(r, locs, imasks[level + 1])))
        zeros = tuple(
            jnp.zeros(grids[level + 1].local_shape, ui.dtype) for ui in u)
        ec = v_cycle(level + 1, zeros, fc)
        e = _halo(tuple(
            transfers.prolong(eci, loc) * mi
            for eci, loc, mi in zip(ec, locs, imasks[level])))
        u = tuple(ui + ei for ui, ei in zip(u, e))
        return smooth(level, u, f, nu_post)

    return v_cycle, residual


# ---------------------------------------------------------------------------
# V-cycle solver
# ---------------------------------------------------------------------------

def multigrid_solve(
    grid: ImplicitGlobalGrid,
    c,
    b,
    spacing,
    x0=None,
    *,
    loc: str | None = None,
    tol: float = 1e-6,
    maxiter: int = 100,
    nu_pre: int = 2,
    nu_post: int = 2,
    omega: float = 6.0 / 7.0,
    coarse_sweeps: int = 100,
    max_levels: int | None = None,
    smoother: str = "jacobi",
    use_kernel: str = "auto",
    bx: int | None = None,
):
    """Solve ``-div(c grad x) = b`` by V-cycles, at any staggering location.

    ``b``/``x0`` may be raw center arrays (the original contract) or
    ``repro.fields.Field``s at any location — a face-located ``b`` gets
    the staggered operator/transfers/masks of
    ``make_v_cycle(loc=...)`` and a Field of the same location back.
    ``loc`` overrides the location for raw arrays; ``c`` is always the
    CENTER coefficient (a Field or raw array).

    Boundary conditions per dim follow ``grid.topo.periodic``:
    homogeneous Dirichlet on non-periodic dims (the ring holds the BC;
    for the staggered dim of a face field the pinned planes are the
    boundary faces and the dead plane), wraparound on periodic dims (the
    halo exchange maintains the ring duplicates).  With EVERY dim
    periodic the operator is singular; the rhs is projected onto
    mean-zero and the mean-zero representative of the solution is
    returned.  Convergence is the deduplicated global relative residual
    over the location's unknowns on the FINE level, so the solution
    matches a single-device solve regardless of how crude the
    coarse-level operators are.  ``smoother`` picks damped Jacobi or the
    3-term Chebyshev smoother for the pre/post sweeps.
    Returns ``(x, SolveInfo)``.
    """
    if grid.halo != 1:
        raise ValueError("multigrid assumes halo width 1 (overlap=2)")
    if smoother not in SMOOTHERS:
        raise ValueError(f"unknown smoother {smoother!r}; pick from {SMOOTHERS}")
    loc = _loc.loc_of(b) if loc is None else loc
    wrap = None
    if hasattr(b, "with_data"):
        wrap, b = b.with_data, b.data
    c = _loc.data_of(c)
    x0 = _loc.data_of(x0) if x0 is not None else None
    grids = grid.hierarchy(max_levels=max_levels)
    if len(grids) < 2:
        raise ValueError(
            f"grid {grid.local_shape} cannot coarsen; multigrid needs >= 2 levels"
        )
    if x0 is None:
        x0 = jnp.zeros_like(b)
    spacing = tuple(float(s) for s in spacing)
    hs = level_spacings(grid, grids, spacing)

    singular = all(grid.topo.periodic)
    cfg = _health.current()  # trace-time opt-in, joins the jit-cache key

    def _local(b, c, x):
        cs = build_coefficients(grid, grids, c)
        v_cycle, residual = make_v_cycle(
            grid, grids, hs, cs, loc=loc, nu_pre=nu_pre, nu_post=nu_post,
            omega=omega, coarse_sweeps=coarse_sweeps, smoother=smoother,
            use_kernel=use_kernel, bx=bx,
        )
        mask = red.loc_solve_mask(grid, loc, b.dtype)

        def demean(a):
            # operator is singular: keep rhs and iterate on the
            # mean-zero complement (wrap-aware masked mean)
            return a - red.masked_mean(grid, a, mask).astype(a.dtype)

        if singular:
            b = demean(b)
        bnorm = red.rhs_norm(grid, b, mask)
        x = grid.update_halo(x)
        r0 = residual(0, x, b)
        res0 = jnp.sqrt(red.dot(grid, r0, r0, mask))

        hist0 = jnp.zeros((maxiter,), res0.dtype)

        def cond(carry):
            res, k = carry[1], carry[2]
            go = (res > tol * bnorm) & (k < maxiter)
            if cfg is not None:
                go = go & _health.carry_ok(carry[4])
            return go

        def body(carry):
            x, _, k, hist = carry[:4]
            with tele.tag("iteration"):
                x = v_cycle(0, x, b)
                r = residual(0, x, b)
                res = jnp.sqrt(red.dot(grid, r, r, mask))
                hist = jax.lax.dynamic_update_index_in_dim(
                    hist, (res / bnorm).astype(hist.dtype), k, 0)
            out = (x, res, k + 1, hist)
            if cfg is not None:
                hc = _health.probe(cfg, carry[4], res, res0)
                _health.maybe_heartbeat(cfg, "mg", grid.topo, k + 1,
                                        res / bnorm)
                out = out + (hc,)
            return out

        carry0 = (x, res0, jnp.zeros((), jnp.int32), hist0)
        if cfg is not None:
            carry0 = carry0 + (_health.carry_init(res0),)
        final = jax.lax.while_loop(cond, body, carry0)
        x, res, k, hist = final[0], final[1], final[2], final[3]
        if singular:
            x = grid.update_halo(demean(x))
        if cfg is None:
            return x, k, res / bnorm, hist
        status = _health.finalize(final[4], res, bnorm, tol)
        _health.emit_final("mg", grid.topo, k, res / bnorm, status, hist,
                           maxiter)
        return x, k, res / bnorm, hist, status

    def _build():
        n_out = 4 if cfg is None else 5
        return jax.shard_map(
            _local, mesh=grid.mesh,
            in_specs=(grid.spec, grid.spec, grid.spec),
            out_specs=(grid.spec,) + tuple(P() for _ in range(n_out - 1)),
            check_vma=False,
        )

    # Static-analysis capture hook (no-op in production; see solvers.cg).
    _ana.maybe_capture("mg", _build, (b, c, x0), grid=grid)

    key = ("solvers.mg", loc, tol, maxiter, nu_pre, nu_post, omega,
           coarse_sweeps, max_levels, smoother, spacing, b.shape, b.dtype,
           cfg, use_kernel, bx)
    if key not in grid._jit_cache:
        grid._jit_cache[key] = jax.jit(_build())

    comm = None
    if tele.enabled():
        ckey = ("solvers.mg.comm",) + key[1:]
        if ckey not in grid._jit_cache:
            grid._jit_cache[ckey] = tele.count_comm(_build(), b, c, x0)
        comm = grid._jit_cache[ckey]

    t0 = time.perf_counter()
    outs = grid._jit_cache[key](b, c, x0)
    x, k, relres, hist = outs[:4]
    k, relres = int(k), float(relres)
    wall = time.perf_counter() - t0
    if wrap is not None:
        x = wrap(x)
    dstatus = None
    if cfg is not None:
        dstatus = int(outs[4])
        jax.effects_barrier()  # flush heartbeat/final-health callbacks
    status = _health.classify(dstatus, relres, tol, k, maxiter)
    info = SolveInfo(iterations=k, relres=relres, converged=relres <= tol,
                     residuals=np.asarray(hist)[:k], wall_s=wall,
                     comm=comm, status=status)
    _note_solve("mg", info)
    return x, info
