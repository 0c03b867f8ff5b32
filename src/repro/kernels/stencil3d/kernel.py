"""Pallas TPU kernel for the 7-point 3-D heat-diffusion stencil.

TPU adaptation of the paper's GPU stencil kernel (ParallelStencil's CUDA
codegen): instead of a thread-per-cell CUDA launch with shared-memory
halos, we tile the local field along the leading (x) dimension into VMEM
blocks.  The full y–z plane of a block resides in VMEM (plane-major layout
feeds the VPU with stride-1 vectors along z); the x-halo between VMEM
blocks is obtained by mapping the SAME input array through three
BlockSpecs shifted by -1/0/+1 block — the Pallas analogue of the
shared-memory ghost ring, with all HBM→VMEM movement expressed as block
copies the compiler can double-buffer.

Arithmetic intensity of the 7-point stencil is ~0.23 FLOP/B (8 FLOP per
8 B of traffic at fp32 with perfect reuse) — firmly memory-bound, so the
kernel's only job is to touch each input byte once; blocking guarantees
that (T is read once per block triple, amortized 1.0–1.2x).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _heat_kernel(prev_ref, cur_ref, nxt_ref, ci_ref, coef_ref, out_ref, *, bx: int, nx: int):
    """One x-block of the stencil.

    prev/cur/nxt: (bx, ny, nz) blocks i-1, i, i+1 of T (wrap-mapped at the
    edges, so a boundary block's ghost row is the wrap row — never its own
    edge row).  ci: (bx, ny, nz) block of 1/heat-capacity. coef: (4,)
    scalars in SMEM: [dt*lam, 1/dx^2, 1/dy^2, 1/dz^2].
    """
    i = pl.program_id(0)
    cur = cur_ref[...]
    ci = ci_ref[...]
    a = coef_ref[0]
    rdx2, rdy2, rdz2 = coef_ref[1], coef_ref[2], coef_ref[3]

    # Extended block (bx+2, ny, nz): one ghost row from each neighbor block.
    up = jnp.concatenate([prev_ref[bx - 1 :, :, :], cur[:-1, :, :]], axis=0)
    dn = jnp.concatenate([cur[1:, :, :], nxt_ref[:1, :, :]], axis=0)

    c = cur[:, 1:-1, 1:-1]
    d2x = (up[:, 1:-1, 1:-1] - 2.0 * c + dn[:, 1:-1, 1:-1]) * rdx2
    d2y = (cur[:, 2:, 1:-1] - 2.0 * c + cur[:, :-2, 1:-1]) * rdy2
    d2z = (cur[:, 1:-1, 2:] - 2.0 * c + cur[:, 1:-1, :-2]) * rdz2
    new = c + a * ci[:, 1:-1, 1:-1] * (d2x + d2y + d2z)

    # Interior mask along x (global first/last row pass through).
    gx = i * bx + jax.lax.broadcasted_iota(jnp.int32, (bx, 1, 1), 0)
    interior = (gx >= 1) & (gx <= nx - 2)
    new = jnp.where(interior, new, c)

    # Pass-through ring, then the interior: two stores, because a value
    # scatter (``.at[].set``) has no Mosaic lowering.
    out_ref[...] = cur
    out_ref[:, 1:-1, 1:-1] = new.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def heat_step_pallas(T, Ci, lam, dt, dx, dy, dz, *, bx: int = 8, interpret: bool = False):
    """Pallas heat step on a local field (same contract as ``heat_step_ref``)."""
    nx, ny, nz = T.shape
    if nx % bx != 0:
        raise ValueError(f"nx={nx} must be divisible by block bx={bx}")
    nb = nx // bx
    coef = jnp.stack(
        [
            jnp.asarray(dt * lam, T.dtype),
            jnp.asarray(1.0 / (dx * dx), T.dtype),
            jnp.asarray(1.0 / (dy * dy), T.dtype),
            jnp.asarray(1.0 / (dz * dz), T.dtype),
        ]
    )

    # Wrap-mapped neighbors: a boundary block's ghost row is the row a
    # jnp.roll wrap would read.  The global first/last x-rows pass
    # through unchanged either way (the interior mask below), but the
    # ghost CONTENT is now well-defined instead of silently aliasing the
    # block's own edge row as the old clamped specs did.
    block = (bx, ny, nz)
    prev_spec = pl.BlockSpec(block, lambda i: ((i + nb - 1) % nb, 0, 0))
    cur_spec = pl.BlockSpec(block, lambda i: (i, 0, 0))
    nxt_spec = pl.BlockSpec(block, lambda i: ((i + 1) % nb, 0, 0))

    coef_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_heat_kernel, bx=bx, nx=nx),
        grid=(nb,),
        in_specs=[prev_spec, cur_spec, nxt_spec, cur_spec, coef_spec],
        out_specs=cur_spec,
        out_shape=jax.ShapeDtypeStruct(T.shape, T.dtype),
        interpret=interpret,
        name="stencil3d_heat",
    )(T, T, T, Ci, coef)
