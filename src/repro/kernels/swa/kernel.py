"""Pallas TPU kernel: causal sliding-window flash attention (GQA).

TPU adaptation of FlashAttention restricted to a sliding window: the kv
grid axis enumerates only the blocks that can intersect the window of the
current q block, so FLOPs and HBM traffic scale with ``T * W`` instead of
``T * S`` — this is what makes gemma3-style local layers and 500k-token
sequence-parallel shards affordable.

Tiling: grid = (B*H, T/bq, ns) with ns = the static worst-case number of
kv blocks per q block.  q/k/v blocks live in VMEM; the MXU consumes
(bq, d) x (d, bk) matmuls; the running softmax (m, l, acc) persists in
VMEM scratch across the innermost (kv) grid axis, which TPU executes
sequentially per (head, q-block) — the standard flash accumulation.

The kv BlockSpec index is clamped into range; a step whose *intended*
block differs from the clamped one is fully masked in-kernel (this also
covers the ragged first/last blocks of the window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _first_kv_block(qlo_abs, bk, window):
    """First kv block intersecting the window of absolute q position ``qlo_abs``."""
    return jnp.maximum(0, qlo_abs - window + 1) // bk


def _swa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, window: int, ns: int, nkv_blocks: int, s_off: int, scale: float,
):
    iq = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    intended = _first_kv_block(iq * bq + s_off, bk, window) + s
    loaded = jnp.minimum(intended, nkv_blocks - 1)
    step_valid = intended == loaded

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]

    logits = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
    )  # (bq, bk)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + s_off
    kpos = intended * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos <= qpos) & (kpos > qpos - window) & step_valid
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    # fully-masked steps keep p == 0 (guard against exp(-inf - -inf) == 1)
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ()))
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(s == ns - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bq", "bk", "scale", "interpret")
)
def swa_pallas(
    q, k, v, *, window: int, bq: int = 128, bk: int = 128,
    scale: float | None = None, interpret: bool = False,
):
    """Causal sliding-window GQA flash attention.

    q: (B, H, T, D); k/v: (B, Hkv, S, D); queries are the last T of S.
    """
    B, H, T, D = q.shape
    _, Hkv, S, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    g = H // Hkv
    bq = min(bq, T)
    bk = min(bk, S)
    if T % bq or S % bk:
        raise ValueError(f"T={T} % bq={bq} or S={S} % bk={bk} != 0")
    scale = (D ** -0.5) if scale is None else scale
    w = min(window, S)
    nq, nkv = T // bq, S // bk
    # worst-case kv steps per q block: window span + q block span
    ns = min(nkv, (bq + w - 2) // bk + 2)
    s_off = S - T  # position offset of q within the kv sequence

    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * Hkv, S, D)
    vr = v.reshape(B * Hkv, S, D)

    def kv_index(b, iq, s):
        first = _first_kv_block(iq * bq + s_off, bk, w)
        blk = jnp.minimum(first + s, nkv - 1)
        return ((b // H) * Hkv + (b % H) // g, blk, 0)

    def q_index(b, iq, s):
        return (b, iq, 0)

    kernel = functools.partial(
        _swa_kernel, bq=bq, bk=bk, window=w, ns=ns, nkv_blocks=nkv,
        s_off=s_off, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, ns),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_index),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="swa_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(qr, kr, vr)
    return out.reshape(B, H, T, D)
