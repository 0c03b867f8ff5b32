"""Pallas TPU kernels for the fused solver hot path.

Same tiling scheme as ``kernels/stencil3d``: the local field is blocked
along the leading (x) dimension into ``(bx, ny, nz)`` VMEM tiles, and
the x-ghost rows come from mapping the SAME array through three
BlockSpecs at block indices ``i-1 / i / i+1``.  Two deliberate
differences from the historical heat kernel:

* **Wrap-mapped ghost blocks.** The neighbor indices are ``(i ± 1) mod
  nb``, not clamped to the edge.  A boundary block's ghost row is then
  the row the reference's ``jnp.roll`` wrap would read — NOT the
  block's own edge row — so the kernels compute exactly what the
  reference spellings compute on every row, including the ring rows the
  interior mask leaves untouched on interior ranks.  The clamped specs
  of the old heat kernel silently fed boundary blocks their own rows as
  ghosts; nothing here depends on a ghost value that differs from the
  reference's.
* **Fusion.** Each kernel performs the whole smoother update (7-point
  variable-coefficient stencil + diagonal scale + axpy) or the
  operator+residual in ONE pass over the tile, so each grid byte moves
  HBM->VMEM once per sweep — the paper's single-pass-per-byte
  discipline applied to the MG smoothers that dominate every V-cycle.

The arithmetic lives in pure per-block functions (``_jacobi_center``,
``_face_au``, ...) that mirror :mod:`.ref` op-for-op (division by
``h^2``, the ``u + omega * r / dia`` spelling, the MAC roll order).
Each is reachable two ways:

* through ``pl.pallas_call`` (compiled TPU kernel, or ``interpret=True``
  on any backend), and
* through :func:`blocked_ref` — an eager Python loop over the same
  blocks, feeding each one the exact ghost rows the wrap-mapped
  BlockSpecs map in.

Run outside ``jit``, every op in :func:`blocked_ref` executes as a
plain IEEE operation, as does the eager reference — which is what makes
the BITWISE pin in ``tests/test_kernel_solver3d.py`` well-defined.  The
compiled paths (jitted ref, interpret-mode ``pallas_call``) are allowed
to differ from it by compiler instruction selection (FMA contraction in
fused loop bodies), which on XLA CPU is worth at most an ulp or two —
the tests pin that envelope too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis import markers as _an

_IN3 = (slice(None), slice(1, -1), slice(1, -1))


def _specs(bx: int, ny: int, nz: int, nb: int):
    """(block, prev, cur, nxt) BlockSpecs with WRAP-mapped neighbors."""
    block = (bx, ny, nz)
    prev = pl.BlockSpec(block, lambda i: ((i + nb - 1) % nb, 0, 0))
    cur = pl.BlockSpec(block, lambda i: (i, 0, 0))
    nxt = pl.BlockSpec(block, lambda i: ((i + 1) % nb, 0, 0))
    return block, prev, cur, nxt


def _ext(prev, cur, nxt):
    """Extended tile (bx+2, ny, nz): one wrap-consistent ghost row per side."""
    return jnp.concatenate([prev[-1:, :, :], cur, nxt[:1, :, :]], axis=0)


def _xmask(i, bx: int, nx: int):
    """True on rows that are in the global x-interior of this block."""
    gx = i * bx + jax.lax.broadcasted_iota(jnp.int32, (bx, 1, 1), 0)
    return (gx >= 1) & (gx <= nx - 2)


# ---------------------------------------------------------------------------
# center: interior-slab flux-form stencil on the extended tile
# ---------------------------------------------------------------------------

def _center_au(ue, ce, h2):
    """``(u0, A u)`` on all ``bx`` rows x the (y, z) interior.

    Extended-tile transliteration of ``ref.poisson_stencil``: the x
    neighbors come from the ghost rows, y/z neighbors from the tile's
    own interior slabs; op order and the division by ``h^2`` match the
    reference exactly.
    """
    u0 = ue[1:-1, 1:-1, 1:-1]
    c0 = ce[1:-1, 1:-1, 1:-1]
    acc = jnp.zeros_like(u0)
    for d in range(3):
        if d == 0:
            up, um = ue[2:, 1:-1, 1:-1], ue[:-2, 1:-1, 1:-1]
            cp, cm = ce[2:, 1:-1, 1:-1], ce[:-2, 1:-1, 1:-1]
        elif d == 1:
            up, um = ue[1:-1, 2:, 1:-1], ue[1:-1, :-2, 1:-1]
            cp, cm = ce[1:-1, 2:, 1:-1], ce[1:-1, :-2, 1:-1]
        else:
            up, um = ue[1:-1, 1:-1, 2:], ue[1:-1, 1:-1, :-2]
            cp, cm = ce[1:-1, 1:-1, 2:], ce[1:-1, 1:-1, :-2]
        cf_p = 0.5 * (c0 + cp)
        cf_m = 0.5 * (c0 + cm)
        acc = acc + (cf_p * (up - u0) - cf_m * (u0 - um)) / h2[d]
    return u0, -acc


def _apply_center(i, ue, ce, *, bx, nx, h2):
    _, au = _center_au(ue, ce, h2)
    return jnp.where(_xmask(i, bx, nx), au, 0.0)


def _residual_center(i, ue, ce, f, *, bx, nx, h2):
    _, au = _center_au(ue, ce, h2)
    return jnp.where(_xmask(i, bx, nx), f[_IN3] - au, 0.0)


def _jacobi_center(i, ue, ce, f, dia, *, bx, nx, h2, omega):
    u0, au = _center_au(ue, ce, h2)
    r = f[_IN3] - au
    new = u0 + omega * r / dia[_IN3]
    return jnp.where(_xmask(i, bx, nx), new, u0)


def _cheb_center(i, ue, ce, f, dia, d, *, bx, nx, h2, a, b):
    u0, au = _center_au(ue, ce, h2)
    z = (f[_IN3] - au) / dia[_IN3]
    dn = z / b if a is None else a * d[_IN3] + b * z
    interior = _xmask(i, bx, nx)
    return jnp.where(interior, u0 + dn, u0), jnp.where(interior, dn, 0.0)


# The center functions above return the (y, z) interior of the block;
# the ring is the input block (u updates) or zero (A u, residual, d).
# In the kernels the two parts are two stores into the output ref: a
# value scatter (``.at[].set``) has no Mosaic lowering.

def _put(base, inner):
    """``base`` with its (y, z) interior replaced by ``inner`` (eager)."""
    return base.at[_IN3].set(inner.astype(base.dtype))


def _store(ref, base, inner):
    ref[...] = base
    ref[_IN3] = inner.astype(ref.dtype)


def _apply_center_kernel(pu, cu, nu, pc, cc, nc, out_ref, *, bx, nx, h2):
    cur = cu[...]
    _store(out_ref, jnp.zeros_like(cur), _apply_center(
        pl.program_id(0), _ext(pu, cur, nu), _ext(pc, cc[...], nc),
        bx=bx, nx=nx, h2=h2))


def _residual_center_kernel(pu, cu, nu, pc, cc, nc, f_ref, out_ref, *, bx,
                            nx, h2):
    cur = cu[...]
    _store(out_ref, jnp.zeros_like(cur), _residual_center(
        pl.program_id(0), _ext(pu, cur, nu), _ext(pc, cc[...], nc),
        f_ref[...], bx=bx, nx=nx, h2=h2))


def _jacobi_center_kernel(pu, cu, nu, pc, cc, nc, f_ref, dia_ref, out_ref,
                          *, bx, nx, h2, omega):
    cur = cu[...]
    _store(out_ref, cur, _jacobi_center(
        pl.program_id(0), _ext(pu, cur, nu), _ext(pc, cc[...], nc),
        f_ref[...], dia_ref[...], bx=bx, nx=nx, h2=h2, omega=omega))


def _cheb_center_kernel(pu, cu, nu, pc, cc, nc, f_ref, dia_ref, d_ref,
                        u_out, d_out, *, bx, nx, h2, a, b):
    cur = cu[...]
    u_in, d_in = _cheb_center(
        pl.program_id(0), _ext(pu, cur, nu), _ext(pc, cc[...], nc),
        f_ref[...], dia_ref[...], d_ref[...], bx=bx, nx=nx, h2=h2, a=a, b=b)
    _store(u_out, cur, u_in)
    _store(d_out, jnp.zeros_like(cur), d_in)


# ---------------------------------------------------------------------------
# face: MAC roll-form stencil on the extended tile
# ---------------------------------------------------------------------------

def _roll(a, d: int, s: int):
    """``mac.roll``: value at index ``i`` becomes ``a[i + s]``."""
    return jnp.roll(a, -s, axis=d)


def _edge_avg(e, d1: int, d2: int):
    a = e + _roll(e, d1, +1)
    return 0.25 * (a + _roll(a, d2, +1))


def _face_au(ue, ee, h2, sd: int):
    """``A u`` (``mac.stripped_component`` spelling) on the extended tile.

    y/z rolls wrap exactly like the reference's rolls on the full local
    array; x neighbors resolve through the ghost rows, so the center
    rows ``1..bx`` are valid — every composite term reads at most one
    row in each x direction (own-dim flux, edge-averaged coefficient,
    and the cross-dim flux differences all have x-depth <= 1).
    """
    acc = jnp.zeros_like(ue)
    for dd in range(3):
        if dd == sd:
            ep = _roll(ee, sd, +1)
            acc = acc + (ep * (_roll(ue, sd, +1) - ue)
                         - ee * (ue - _roll(ue, sd, -1))) / h2[sd]
        else:
            eedge = _edge_avg(ee, sd, dd)
            acc = acc + (eedge * (_roll(ue, dd, +1) - ue)
                         - _roll(eedge, dd, -1)
                         * (ue - _roll(ue, dd, -1))) / h2[dd]
    return -acc


def _apply_face(cur, ue, ee, *, sd, h2):
    au = _face_au(ue, ee, h2, sd)[1:-1]
    return au.astype(cur.dtype)


def _residual_face(cur, ue, ee, f, m, *, sd, h2):
    au = _face_au(ue, ee, h2, sd)[1:-1]
    return ((f - au) * m).astype(cur.dtype)


def _jacobi_face(cur, ue, ee, f, dia, m, *, sd, h2, omega):
    au = _face_au(ue, ee, h2, sd)[1:-1]
    r = (f - au) * m
    return (cur + omega * r / dia).astype(cur.dtype)


def _cheb_face(cur, ue, ee, f, dia, m, d, *, sd, h2, a, b):
    au = _face_au(ue, ee, h2, sd)[1:-1]
    z = ((f - au) * m) / dia
    dn = z / b if a is None else a * d + b * z
    return (cur + dn).astype(cur.dtype), dn.astype(cur.dtype)


def _apply_face_kernel(pu, cu, nu, pe, ce, ne, out_ref, *, sd, h2):
    cur = cu[...]
    out_ref[...] = _apply_face(cur, _ext(pu, cur, nu), _ext(pe, ce[...], ne),
                               sd=sd, h2=h2)


def _residual_face_kernel(pu, cu, nu, pe, ce, ne, f_ref, m_ref, out_ref,
                          *, sd, h2):
    cur = cu[...]
    out_ref[...] = _residual_face(
        cur, _ext(pu, cur, nu), _ext(pe, ce[...], ne), f_ref[...], m_ref[...],
        sd=sd, h2=h2)


def _jacobi_face_kernel(pu, cu, nu, pe, ce, ne, f_ref, dia_ref, m_ref,
                        out_ref, *, sd, h2, omega):
    cur = cu[...]
    out_ref[...] = _jacobi_face(
        cur, _ext(pu, cur, nu), _ext(pe, ce[...], ne), f_ref[...],
        dia_ref[...], m_ref[...], sd=sd, h2=h2, omega=omega)


def _cheb_face_kernel(pu, cu, nu, pe, ce, ne, f_ref, dia_ref, m_ref, d_ref,
                      u_out, d_out, *, sd, h2, a, b):
    cur = cu[...]
    u_new, d_new = _cheb_face(
        cur, _ext(pu, cur, nu), _ext(pe, ce[...], ne), f_ref[...],
        dia_ref[...], m_ref[...], d_ref[...], sd=sd, h2=h2, a=a, b=b)
    u_out[...] = u_new
    d_out[...] = d_new


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _check_block(nx: int, bx: int) -> int:
    if nx % bx != 0:
        raise ValueError(f"nx={nx} must be divisible by block bx={bx}")
    return nx // bx


def apply_pallas(u, c, *, h2, sd=None, bx: int, interpret: bool = False):
    """Fused ``A u`` (center: zero-ring interior stencil; face: raw)."""
    u = _an.consume(u, radius=1,
                    site="kernels.solver3d.kernel.apply_pallas")
    nx, ny, nz = u.shape
    nb = _check_block(nx, bx)
    block, prev, cur, nxt = _specs(bx, ny, nz, nb)
    if sd is None:
        kern = functools.partial(_apply_center_kernel, bx=bx, nx=nx, h2=h2)
    else:
        kern = functools.partial(_apply_face_kernel, sd=sd, h2=h2)
    return pl.pallas_call(
        kern, grid=(nb,),
        in_specs=[prev, cur, nxt, prev, cur, nxt],
        out_specs=cur,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret, name="solver3d_apply",
    )(u, u, u, c, c, c)


def residual_pallas(u, c, f, *, h2, sd=None, imask=None, bx: int,
                    interpret: bool = False):
    """Fused ``f - A u`` on the location's unknowns, zero elsewhere."""
    u = _an.consume(u, radius=1,
                    site="kernels.solver3d.kernel.residual_pallas")
    nx, ny, nz = u.shape
    nb = _check_block(nx, bx)
    block, prev, cur, nxt = _specs(bx, ny, nz, nb)
    if sd is None:
        kern = functools.partial(_residual_center_kernel, bx=bx, nx=nx, h2=h2)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur]
        args = (u, u, u, c, c, c, f)
    else:
        kern = functools.partial(_residual_face_kernel, sd=sd, h2=h2)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur, cur]
        args = (u, u, u, c, c, c, f, imask)
    return pl.pallas_call(
        kern, grid=(nb,), in_specs=in_specs, out_specs=cur,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret, name="solver3d_residual",
    )(*args)


def jacobi_pallas(u, c, f, dia, *, omega, h2, sd=None, imask=None, bx: int,
                  interpret: bool = False):
    """Fused damped-Jacobi sweep: stencil + residual + diag scale + axpy
    in one pass over each tile."""
    u = _an.consume(u, radius=1,
                    site="kernels.solver3d.kernel.jacobi_pallas")
    nx, ny, nz = u.shape
    nb = _check_block(nx, bx)
    block, prev, cur, nxt = _specs(bx, ny, nz, nb)
    if sd is None:
        kern = functools.partial(_jacobi_center_kernel, bx=bx, nx=nx, h2=h2,
                                 omega=omega)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur, cur]
        args = (u, u, u, c, c, c, f, dia)
    else:
        kern = functools.partial(_jacobi_face_kernel, sd=sd, h2=h2,
                                 omega=omega)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur, cur, cur]
        args = (u, u, u, c, c, c, f, dia, imask)
    return pl.pallas_call(
        kern, grid=(nb,), in_specs=in_specs, out_specs=cur,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret, name="solver3d_jacobi",
    )(*args)


def cheb_pallas(u, c, f, dia, d, *, a, b, h2, sd=None, imask=None, bx: int,
                interpret: bool = False):
    """Fused Chebyshev recurrence step -> ``(u, d)`` (see
    ``ref.cheb_sweep_ref`` for the ``a``/``b`` convention)."""
    u = _an.consume(u, radius=1,
                    site="kernels.solver3d.kernel.cheb_pallas")
    nx, ny, nz = u.shape
    nb = _check_block(nx, bx)
    block, prev, cur, nxt = _specs(bx, ny, nz, nb)
    if sd is None:
        kern = functools.partial(_cheb_center_kernel, bx=bx, nx=nx, h2=h2,
                                 a=a, b=b)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur, cur, cur]
        args = (u, u, u, c, c, c, f, dia, d)
    else:
        kern = functools.partial(_cheb_face_kernel, sd=sd, h2=h2, a=a, b=b)
        in_specs = [prev, cur, nxt, prev, cur, nxt, cur, cur, cur, cur]
        args = (u, u, u, c, c, c, f, dia, imask, d)
    out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype),
                 jax.ShapeDtypeStruct(u.shape, u.dtype)]
    return pl.pallas_call(
        kern, grid=(nb,), in_specs=in_specs, out_specs=[cur, cur],
        out_shape=out_shape, interpret=interpret, name="solver3d_cheb",
    )(*args)


# ---------------------------------------------------------------------------
# eager block harness (the bitwise oracle)
# ---------------------------------------------------------------------------

def blocked_ref(op: str, u, c, f=None, dia=None, d=None, *, h2, sd=None,
                imask=None, bx: int, omega=None, a=None, b=None):
    """Evaluate the EXACT kernel block arithmetic with a Python loop.

    Feeds each ``(bx, ny, nz)`` block the same wrap-mapped ghost rows
    the BlockSpecs map in, then runs the same pure per-block functions
    the pallas kernel bodies call.  Run OUTSIDE ``jit`` every op
    executes as a plain IEEE operation — bitwise-identical to the eager
    reference spellings in :mod:`.ref` — which is what the bitwise
    tests compare.  ``op`` is ``"apply" | "residual" | "jacobi" |
    "cheb"`` (cheb returns ``(u, d)``).
    """
    nx = u.shape[0]
    nb = _check_block(nx, bx)

    def blk(arr, j):
        return arr[j * bx:(j + 1) * bx]

    outs = []
    for i in range(nb):
        p, n = (i + nb - 1) % nb, (i + 1) % nb
        cur = blk(u, i)
        ue = _ext(blk(u, p), cur, blk(u, n))
        ce = _ext(blk(c, p), blk(c, i), blk(c, n))
        if sd is None:
            zero = jnp.zeros_like(cur)
            if op == "apply":
                outs.append(_put(zero, _apply_center(i, ue, ce, bx=bx, nx=nx,
                                                     h2=h2)))
            elif op == "residual":
                outs.append(_put(zero, _residual_center(
                    i, ue, ce, blk(f, i), bx=bx, nx=nx, h2=h2)))
            elif op == "jacobi":
                outs.append(_put(cur, _jacobi_center(
                    i, ue, ce, blk(f, i), blk(dia, i), bx=bx, nx=nx, h2=h2,
                    omega=omega)))
            elif op == "cheb":
                u_in, d_in = _cheb_center(i, ue, ce, blk(f, i), blk(dia, i),
                                          blk(d, i), bx=bx, nx=nx, h2=h2,
                                          a=a, b=b)
                outs.append((_put(cur, u_in), _put(zero, d_in)))
            else:
                raise ValueError(f"unknown op={op!r}")
        else:
            if op == "apply":
                outs.append(_apply_face(cur, ue, ce, sd=sd, h2=h2))
            elif op == "residual":
                outs.append(_residual_face(cur, ue, ce, blk(f, i),
                                           blk(imask, i), sd=sd, h2=h2))
            elif op == "jacobi":
                outs.append(_jacobi_face(cur, ue, ce, blk(f, i), blk(dia, i),
                                         blk(imask, i), sd=sd, h2=h2,
                                         omega=omega))
            elif op == "cheb":
                outs.append(_cheb_face(cur, ue, ce, blk(f, i), blk(dia, i),
                                       blk(imask, i), blk(d, i), sd=sd,
                                       h2=h2, a=a, b=b))
            else:
                raise ValueError(f"unknown op={op!r}")
    if op == "cheb":
        us, ds = zip(*outs)
        return (jnp.concatenate(us, axis=0), jnp.concatenate(ds, axis=0))
    return jnp.concatenate(outs, axis=0)
