"""Public wrapper for the heat-diffusion stencil step.

Dispatches through :mod:`repro.kernels.dispatch` — the shared
``use_kernel`` contract of every kernel family: ``"auto"`` probes the
backend, dtype, rank and block divisibility and gracefully falls back
to the pure-jnp reference when the Pallas kernel cannot run (one-time
warning; never a crash), while an explicit ``"pallas"``/``"interpret"``
request raises on a failed probe.  Both paths are drop-in replacements
for the ``step!`` in the paper's Fig. 1 and obey the pass-through ring
convention, so they compose with ``update_halo`` and
``hide_communication`` unchanged.
"""

from __future__ import annotations

from repro.analysis import markers as _an
from repro.kernels import dispatch as _dispatch

from .kernel import heat_step_pallas
from .ref import heat_step_ref


def heat_step(T, Ci, lam, dt, dx, dy, dz, *, use_kernel: str = "auto",
              bx: int | None = None):
    """One stencil step. ``use_kernel``: 'auto' | 'pallas' | 'interpret' |
    'ref'; ``bx`` is the x-block extent (None auto-picks the largest
    divisor of the local extent ``<= 8`` that fits VMEM)."""
    unsupported = None
    if T.ndim != 3:
        unsupported = f"a {T.ndim}-D field (kernels are 3-D)"
    impl, nbx = _dispatch.resolve(use_kernel, shape=T.shape, dtype=T.dtype,
                                  bx=bx, unsupported=unsupported,
                                  where="stencil3d.heat_step",
                                  blocks=_dispatch.VMEM_BLOCKS["heat"])
    # Ghost-demand contract for the static analyzer (identity; binds
    # only under an analysis trace).  Marked HERE — outside the jitted
    # kernel wrapper — so the pjit cache never sees a marker trace.
    T = _an.consume(T, radius=1, site="kernels.stencil3d.heat_step")
    if impl == "ref":
        return heat_step_ref(T, Ci, lam, dt, dx, dy, dz)
    return heat_step_pallas(T, Ci, lam, dt, dx, dy, dz, bx=nbx,
                            interpret=impl == "interpret")
