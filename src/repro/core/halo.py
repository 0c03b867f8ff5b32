"""Halo updates — the paper's ``update_halo!`` as a pure JAX function.

Runs *inside* ``jax.shard_map`` (local view).  For each distributed grid
dimension, every rank sends its innermost non-halo slabs to its two
neighbors via ``jax.lax.ppermute`` (one ``collective-permute`` per
direction — the TPU ICI analogue of the paper's RDMA halo transfer).

Non-periodic physical boundaries keep their existing cell values (those
cells hold boundary conditions); ``ppermute`` delivers zeros to ranks with
no sender, which are masked out with a ``where`` on the rank coordinate.

Dimensions are updated sequentially so that corner/edge values propagate
across dimensions exactly as in ImplicitGlobalGrid.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.analysis import markers as _an
from repro.telemetry.counters import record_halo as _record_halo

from .locations import _STAGGER_DIM as _LOC_STAGGER_DIM
from .topology import CartesianTopology


def _slc(ndim: int, dim: int, start, stop) -> tuple:
    s = [slice(None)] * ndim
    s[dim] = slice(start, stop)
    return tuple(s)


def _update_one_dim(topo: CartesianTopology, A: jax.Array, gdim: int, adim: int, h: int):
    """Halo-update array axis ``adim`` which is grid dimension ``gdim``."""
    ax = topo.axes[gdim]
    n = A.shape[adim]
    nd = A.ndim
    if 2 * h >= n:
        raise ValueError(f"halo width {h} too large for local extent {n}")

    send_low = A[_slc(nd, adim, h, 2 * h)]          # my low inner -> left neighbor's high halo
    send_high = A[_slc(nd, adim, n - 2 * h, n - h)]  # my high inner -> right neighbor's low halo

    recv_high = jax.lax.ppermute(send_low, ax, topo.shift_perm(gdim, -1))
    recv_low = jax.lax.ppermute(send_high, ax, topo.shift_perm(gdim, +1))

    if not topo.periodic[gdim]:
        # Physical-boundary ranks keep their halo cells (they hold BCs).
        recv_low = jnp.where(topo.is_first(gdim), A[_slc(nd, adim, 0, h)], recv_low)
        recv_high = jnp.where(topo.is_last(gdim), A[_slc(nd, adim, n - h, n)], recv_high)

    A = jax.lax.dynamic_update_slice_in_dim(A, recv_low.astype(A.dtype), 0, axis=adim)
    A = jax.lax.dynamic_update_slice_in_dim(A, recv_high.astype(A.dtype), n - h, axis=adim)
    return A


# Staggering dim per field location — the canonical table lives in
# repro.core.locations (shared with the solvers and fields layers);
# bare arrays (location None) exchange like centers.
_STAGGER_DIM = {None: None, **_LOC_STAGGER_DIM}


def update_halo(
    topo: CartesianTopology,
    *arrays: jax.Array,
    width: int = 1,
    dims: Sequence[int] | None = None,
    locations: Sequence[str | None] | None = None,
):
    """Exchange halos of ``arrays`` (local view, inside shard_map).

    ``width`` is the halo width h (the paper's ``overlap = 2h``).  Returns
    updated arrays (single array if one was passed).  Grid dimensions are
    the trailing ``topo.ndims`` axes of each array.

    ``locations`` optionally gives each array's staggering location
    (``repro.fields`` convention: ``"center"``/``"xface"``/...).  Under
    shape-uniform staggering, face index ``i`` is aligned with center
    index ``i``, so the exchange mechanics are location-independent —
    including periodic wraparound, which is dead-plane-safe by
    construction: the send slabs ``[h, 2h)`` / ``[n-2h, n-h)`` never
    include the staggered dead plane (globally ``N-1``, always among the
    outermost ``h`` halo planes of the last blocks), and the periodic
    identification ``i == i +- (N - 2h)`` holds for faces exactly as for
    centers (faces and centers share the period).  The wraparound
    therefore fills the formerly dead plane with its live wrapped copy
    (global face ``N-1`` == face ``2h-1``), which is exactly what face
    stencils reading that halo plane need.
    """
    dims = tuple(dims) if dims is not None else tuple(range(topo.ndims))
    if locations is not None and len(locations) != len(arrays):
        raise ValueError(
            f"got {len(locations)} locations for {len(arrays)} arrays")
    for loc in locations or ():
        if loc not in _STAGGER_DIM:
            raise ValueError(f"unknown staggering location {loc!r}")
    out = []
    for A in arrays:
        off = A.ndim - topo.ndims
        if off < 0:
            raise ValueError(f"array rank {A.ndim} < topology rank {topo.ndims}")
        # Contract markers for the static analyzer: identity primitives
        # that bind only under an analysis trace (repro.analysis.markers)
        # — the production program never contains them.
        A = _an.exchange_in(A, width=width, site="core.halo.update_halo")
        exchanged = []
        for d in dims:
            if not topo.exchanges(d):
                continue  # nothing to exchange
            # Telemetry hook: a pure trace-time Python side effect (no-op
            # unless a counting collector is active) — the lowered program
            # is identical with or without it.
            _record_halo(A.shape, d + off, width,
                         jnp.dtype(A.dtype).itemsize)
            # Named scope: the exchange's ops carry "halo.update" in their
            # ``op_name`` metadata (trace-time only).
            with jax.named_scope("halo.update"):
                A = _update_one_dim(topo, A, d, d + off, width)
            exchanged.append(d)
        A = _an.exchange_out(A, width=width, site="core.halo.update_halo",
                             dims=exchanged)
        out.append(A)
    return out[0] if len(out) == 1 else tuple(out)
