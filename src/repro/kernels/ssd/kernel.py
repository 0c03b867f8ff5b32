"""Pallas TPU kernel for the intra-chunk part of the Mamba-2 SSD scan.

The SSD chunk decomposition splits the selective-SSM recurrence into
(a) an *intra-chunk* block that is pure matmul work — (L,N)x(N,L) scores,
a masked decay Hadamard, and (L,L)x(L,P) / (N,L)x(L,P) products — and
(b) a tiny *inter-chunk* state recurrence (nc steps over (N,P) states).

(a) is the compute hot spot and maps straight onto the MXU; this kernel
computes, per (batch*head, chunk) grid cell held in VMEM:

    Y_diag = ((C B^T) ∘ D) (dt ∘ X)        D = tril decay matrix
    S_c    = (dec_end ∘ dt ∘ B)^T X        chunk state contribution

(b) runs in jnp on the host graph (it is O(nc·N·P), bandwidth-trivial,
and sequential by nature).  The cumulative log-decays are precomputed in
fp32 outside and streamed in, keeping the kernel free of transcendentals
except the elementwise ``exp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, b_ref, c_ref, dt_ref, s_ref, yd_ref, st_ref):
    x = x_ref[0]      # (L, P)
    B = b_ref[0]      # (L, N)
    C = c_ref[0]      # (L, N)
    dt = dt_ref[0]    # (L, 1)
    s = s_ref[0]      # (L, 1) inclusive cumsum of log dA (fp32)

    L = x.shape[0]
    xf = x.astype(jnp.float32)
    scores = jax.lax.dot_general(
        C.astype(jnp.float32), B.astype(jnp.float32), (((1,), (1,)), ((), ()))
    )  # (L_t, L_j)
    decay = jnp.exp(s - s.T)  # s_t - s_j
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    )
    w = jnp.where(tri, scores * decay, 0.0) * dt.T  # (L_t, L_j) * dt_j
    y_diag = jax.lax.dot_general(w, xf, (((1,), (0,)), ((), ())))  # (L, P)
    yd_ref[0] = y_diag.astype(yd_ref.dtype)

    dec_end = jnp.exp(s[L - 1, 0] - s)  # (L, 1)
    bw = B.astype(jnp.float32) * (dec_end * dt)  # (L, N)
    state = jax.lax.dot_general(bw, xf, (((0,), (0,)), ((), ())))  # (N, P)
    st_ref[0] = state.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk_pallas(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False):
    """Intra-chunk SSD pieces.

    x: (Ba, T, H, P); dt: (Ba, T, H); A: (H,); B/C: (Ba, T, H, N) (per-head).
    Returns (y_diag: (Ba, T, H, P), states: (Ba, nc, H, N, P),
             s: (Ba, nc, L, H) fp32 cumulative log-decays).
    """
    Ba, T, H, P = x.shape
    N = B.shape[-1]
    if T % chunk:
        raise ValueError(f"T={T} % chunk={chunk} != 0")
    L = chunk
    nc = T // L

    logdA = (dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :])
    s = jnp.cumsum(logdA.reshape(Ba, nc, L, H), axis=2)  # (Ba, nc, L, H)

    # layout: (Ba*H*nc, L, ...) grid cells
    def to_cells(a, d):
        # (Ba, T, H, d) -> (Ba, nc, L, H, d) -> (Ba, H, nc, L, d) -> (BHN, L, d)
        return (
            a.reshape(Ba, nc, L, H, d).transpose(0, 3, 1, 2, 4).reshape(Ba * H * nc, L, d)
        )

    xc = to_cells(x, P)
    Bc = to_cells(B, N)
    Cc = to_cells(C, N)
    dtc = to_cells(dt[..., None], 1).astype(jnp.float32)
    sc = s.transpose(0, 3, 1, 2).reshape(Ba * H * nc, L)[..., None]

    spec = lambda d: pl.BlockSpec((1, L, d), lambda i: (i, 0, 0))
    y_diag, states = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(Ba * H * nc,),
        in_specs=[spec(P), spec(N), spec(N), spec(1), spec(1)],
        out_specs=[spec(P), pl.BlockSpec((1, N, P), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((Ba * H * nc, L, P), x.dtype),
            jax.ShapeDtypeStruct((Ba * H * nc, N, P), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_intra_chunk",
    )(xc, Bc, Cc, dtc, sc)

    y_diag = (
        y_diag.reshape(Ba, H, nc, L, P).transpose(0, 2, 3, 1, 4).reshape(Ba, T, H, P)
    )
    states = states.reshape(Ba, H, nc, N, P).transpose(0, 2, 1, 3, 4)
    return y_diag, states, s


def ssd_pallas(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False, h0=None):
    """Full SSD via the Pallas intra-chunk kernel + jnp inter-chunk scan.

    Same contract as ``ref.ssd_ref`` but with per-head B/C: (Ba, T, H, N)
    (the wrapper in ops.py broadcasts grouped B/C)."""
    Ba, T, H, P = x.shape
    N = B.shape[-1]
    y_diag, states, s = ssd_intra_chunk_pallas(
        x, dt, A, B, C, chunk=chunk, interpret=interpret
    )
    nc, L = s.shape[1], s.shape[2]
    dA_chunk = jnp.exp(s[:, :, -1, :])  # (Ba, nc, H)

    def step(h, inp):
        dAc, st = inp
        return h * dAc[..., None, None] + st, h

    if h0 is None:  # vma-correct zeros (see ref.py)
        h0 = jnp.broadcast_to((x[:, 0, :, 0] * 0)[..., None, None], (Ba, H, N, P))
    h = h0.astype(jnp.float32)
    h_fin, h_prevs = jax.lax.scan(
        step, h, (jnp.moveaxis(dA_chunk, 1, 0), jnp.moveaxis(states, 1, 0))
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (Ba, nc, H, N, P)
    Cc = C.reshape(Ba, nc, L, H, N)
    y_off = jnp.einsum("bclh,bclhn,bchnp->bclhp", jnp.exp(s), Cc, h_prevs)
    y = y_diag + y_off.reshape(Ba, T, H, P).astype(x.dtype)
    return y.astype(x.dtype), h_fin.astype(x.dtype)
