"""Public fused solver ops behind the shared ``use_kernel`` dispatch.

Every entry point takes ``use_kernel="auto"|"pallas"|"interpret"|"ref"``
and an optional x-block size ``bx`` (None auto-picks the largest divisor
``<= 8`` of the local x extent whose working set fits VMEM), resolves
them once through
:func:`repro.kernels.dispatch.resolve` (graceful ``ref`` fallback on
auto, hard error on an explicit kernel request that cannot run) and
calls either the Pallas kernel (:mod:`.kernel`) or the canonical
reference spelling (:mod:`.ref`).

Conventions (exactly those of ``repro.solvers.multigrid``):

* fields are local views INCLUDING the halo ring; the caller owns halo
  exchange (one ``update_halo`` per sweep);
* ``loc`` in {"center", "xface", "yface", "zface"}; face locations need
  the location's ``imask`` for residual/smoother ops;
* diagonals are FULL-SHAPE and safe to divide (``ref.full_diag``);
* the kernel block arithmetic is bitwise-identical to the reference
  spellings (pinned eagerly through ``kernel.blocked_ref`` by
  ``tests/test_kernel_solver3d.py``); the compiled paths agree to
  within compiler instruction selection (an ulp or two on XLA CPU).
"""

from __future__ import annotations

from repro.core import locations as _loc
from repro.kernels import dispatch as _dispatch

from . import kernel as _k
from . import ref


def _h2(spacing) -> tuple:
    return tuple(float(s) ** 2 for s in spacing)


def _resolve(use_kernel, u, bx, loc, imask, where, op, needs_mask=True):
    sd = _loc.stagger_dim(loc)
    if sd is not None and needs_mask and imask is None:
        raise ValueError(f"{where}: loc={loc!r} needs the interior mask "
                         f"(imask=...)")
    unsupported = None
    if u.ndim != 3:
        unsupported = f"a {u.ndim}-D field (kernels are 3-D)"
    impl, nbx = _dispatch.resolve(use_kernel, shape=u.shape, dtype=u.dtype,
                                  bx=bx, unsupported=unsupported, where=where,
                                  blocks=_dispatch.VMEM_BLOCKS[
                                      op if sd is None else op + "_face"])
    return sd, impl, nbx


def apply_op(u, c, *, spacing, loc: str = "center", use_kernel: str = "auto",
             bx: int | None = None):
    """Fused ``A u`` (center: interior stencil, zero ring; face: raw
    unmasked roll-form stencil — callers mask, as in the cycle)."""
    sd, impl, nbx = _resolve(use_kernel, u, bx, loc, None,
                             "solver3d.apply_op", "apply", needs_mask=False)
    if impl == "ref":
        return ref.apply_op_ref(u, c, spacing, loc)
    return _k.apply_pallas(u, c, h2=_h2(spacing), sd=sd, bx=nbx,
                           interpret=impl == "interpret")


def residual_op(u, c, f, *, spacing, loc: str = "center", imask=None,
                use_kernel: str = "auto", bx: int | None = None):
    """Fused ``f - A u`` on the location's unknowns, zero elsewhere."""
    sd, impl, nbx = _resolve(use_kernel, u, bx, loc, imask,
                             "solver3d.residual_op", "residual")
    if impl == "ref":
        return ref.residual_op_ref(u, c, f, spacing, loc, imask)
    return _k.residual_pallas(u, c, f, h2=_h2(spacing), sd=sd, imask=imask,
                              bx=nbx, interpret=impl == "interpret")


def jacobi_sweep(u, c, f, dia, *, omega, spacing, loc: str = "center",
                 imask=None, use_kernel: str = "auto", bx: int | None = None):
    """One fused damped-Jacobi sweep ``u + omega * D^-1 (f - A u)``
    (stencil + residual + diagonal scale + axpy in one kernel pass; no
    halo update — the caller owns communication)."""
    sd, impl, nbx = _resolve(use_kernel, u, bx, loc, imask,
                             "solver3d.jacobi_sweep", "jacobi")
    if impl == "ref":
        return ref.jacobi_sweep_ref(u, c, f, dia, omega=omega,
                                    spacing=spacing, loc=loc, imask=imask)
    return _k.jacobi_pallas(u, c, f, dia, omega=omega, h2=_h2(spacing),
                            sd=sd, imask=imask, bx=nbx,
                            interpret=impl == "interpret")


def cheb_sweep(u, c, f, dia, d, *, a, b, spacing, loc: str = "center",
               imask=None, use_kernel: str = "auto", bx: int | None = None):
    """One fused Chebyshev recurrence step -> ``(u, d)``.

    ``a=None`` is the FIRST step (``d = z / b`` with ``b = theta``);
    otherwise ``d = a * d + b * z`` with ``a = rho_k rho_{k-1}`` and
    ``b = 2 rho_k / delta`` — matching ``make_v_cycle`` exactly.
    """
    sd, impl, nbx = _resolve(use_kernel, u, bx, loc, imask,
                             "solver3d.cheb_sweep", "cheb")
    if impl == "ref":
        return ref.cheb_sweep_ref(u, c, f, dia, d, a=a, b=b, spacing=spacing,
                                  loc=loc, imask=imask)
    return _k.cheb_pallas(u, c, f, dia, d, a=a, b=b, h2=_h2(spacing), sd=sd,
                          imask=imask, bx=nbx, interpret=impl == "interpret")
