"""Ring attention + LSE-combined sharded decode attention.

Ring attention is the iterated generalization of the paper's halo update:
instead of one neighbor exchange, KV blocks rotate around the ring of
sequence shards via ``ppermute`` while each rank accumulates flash-style
partial softmax over the resident block — the communication of rotation
step i+1 overlaps the compute of step i (the ``@hide_communication``
principle, applied R-1 times).

Used for *full*-attention layers under sequence parallelism (gemma3's
global layers, jamba's attention layers at 500k tokens).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _partial_attn(q, k, v, mask, scale):
    """Flash-style partials. q: (B,Hkv,g,T,D); k/v: (B,Hkv,S,D); mask (T,S).

    Returns (acc, m, l): un-normalized weighted values, row max, row sum."""
    logits = jnp.einsum("bkgtd,bksd->bkgts", q * scale, k).astype(jnp.float32)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.where(mask[None, None, None], jnp.exp(logits - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(jnp.float32))
    return acc, m, l


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   scale: float | None = None):
    """Causal ring attention over sequence shards.

    q: (B, H, T_local, D); k/v: (B, Hkv, T_local, D), sequence-sharded over
    ``axis_name``.  Returns (B, H, T_local, D)."""
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    n = jax.lax.axis_size(axis_name)
    r = jax.lax.axis_index(axis_name)
    scale = (D ** -0.5) if scale is None else scale
    qg = q.reshape(B, Hkv, g, T, D)
    qpos = r * T + jnp.arange(T)

    rot = [(i, (i + 1) % n) for i in range(n)]  # kv moves to the next rank

    def body(i, carry):
        kb, vb, acc, m, l = carry
        src = (r - i) % n  # the rank whose kv block is resident at step i
        kvpos = src * T + jnp.arange(T)
        mask = (kvpos[None, :] <= qpos[:, None]) if causal else jnp.ones((T, T), bool)
        a, mb, lb = _partial_attn(qg, kb, vb, mask, scale)
        m_new = jnp.maximum(m, mb)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(mb - m_new)
        acc = acc * alpha + a * beta
        l = l * alpha + lb * beta
        # rotate kv for the next step (XLA overlaps this with the next matmul)
        kb = jax.lax.ppermute(kb, axis_name, rot)
        vb = jax.lax.ppermute(vb, axis_name, rot)
        return kb, vb, acc, m_new, l

    # mark the accumulators device-varying for shard_map's vma typing
    acc = jax.lax.pcast(jnp.zeros((B, Hkv, g, T, D), jnp.float32), (axis_name,), to="varying")
    m = jax.lax.pcast(jnp.full((B, Hkv, g, T, 1), -1e30, jnp.float32), (axis_name,), to="varying")
    l = jax.lax.pcast(jnp.zeros((B, Hkv, g, T, 1), jnp.float32), (axis_name,), to="varying")
    _, _, acc, m, l = jax.lax.fori_loop(0, n, body, (k, v, acc, m, l))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(B, H, T, D).astype(q.dtype)


def lse_combine_decode(q, k_shard, v_shard, kv_len_local, *, axis_name: str,
                       first_valid=None, scale: float | None = None):
    """Flash-decoding: one query token against a length-sharded KV cache.

    q: (B, H, D); k/v_shard: (B, S_local, Hkv, D); each rank computes a
    partial softmax over its shard, then partials combine with log-sum-exp
    weights via ``psum`` — O(H) bytes of communication instead of moving
    the cache.  ``first_valid``: per-rank index of the first valid cache
    slot (for masking unwritten tail slots), broadcastable to (B, S_local).
    """
    B, H, D = q.shape
    Hkv = k_shard.shape[2]
    g = H // Hkv
    scale = (D ** -0.5) if scale is None else scale
    qg = q.reshape(B, Hkv, g, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg * scale, k_shard).astype(jnp.float32)
    S = k_shard.shape[1]
    valid = jnp.arange(S)[None, :] < kv_len_local[:, None]  # (B, S_local)
    if first_valid is not None:
        valid = valid & (jnp.arange(S)[None, :] >= first_valid)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :], jnp.exp(logits - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, v_shard.astype(jnp.float32))
    # global combine
    m_g = jax.lax.pmax(m[..., 0], axis_name)[..., None]
    w = jnp.exp(m - m_g)
    acc = jax.lax.psum(acc * w[..., 0][..., None], axis_name)
    l_g = jax.lax.psum(l * w, axis_name)
    out = acc / jnp.where(l_g == 0.0, 1.0, l_g)
    return out.reshape(B, H, D).astype(q.dtype)
