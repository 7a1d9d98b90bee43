"""Reference oracle for the paged flash-decode kernel.

Gather-then-dense: assemble each slot's contiguous K/V view from the page
pool (trash-page rows explicitly zeroed — the same masking contract the
kernel's index map follows), then run the exact decode-plus-self-term math
of ``models.attention._decode_attn_plus_self``.  Kept standalone so the
kernels package never imports the models package.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

NEG_INF = -2.3819763e38


def gather_pages_ref(pool, pages):
    """pool: (P, KV, ps, D); pages: (B, n) int32 -> (B, KV, n*ps, D).
    Rows gathered from physical page 0 (the reserved trash page) are
    zeroed: its contents are scratch for free-slot writes and must never
    leak into a view."""
    g = pool[pages]                                  # (B, n, KV, ps, D)
    g = jnp.where((pages == 0)[:, :, None, None, None],
                  jnp.zeros((), pool.dtype), g)
    B, n, KV, ps, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, KV, n * ps, D)


def paged_decode_attention_ref(q, k_pool, v_pool, pages, kv_len, kt, vt, *,
                               window: int | None = None, scale=None):
    """q: (B, 1, H, D); pools: (P, KV, ps, D); pages: (B, n) int32;
    kv_len: scalar or (B,) OLD cache lengths; kt/vt: (B, KV, 1, D) the
    current token's K/V (merged as a self term); ``scale`` None is
    1 / sqrt(D).  Returns (B, 1, H, D)."""
    B, _, H, D = q.shape
    # a lane-dense pool pads D with zeros: read the head size back
    k_cache = gather_pages_ref(k_pool, pages)[..., :D]
    v_cache = gather_pages_ref(v_pool, pages)[..., :D]
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if k_cache.dtype != q.dtype:
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
        kt = kt.astype(q.dtype)
        vt = vt.astype(q.dtype)
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qf = (q.reshape(B, KV, G, D) * scale).astype(q.dtype)
    s_old = jnp.einsum("bkgd,bktd->bkgt", qf, k_cache,
                       preferred_element_type=jnp.float32)
    pos = jnp.arange(T)[None, :]
    kv_len = jnp.broadcast_to(jnp.reshape(jnp.asarray(kv_len), (-1,)), (B,))
    valid = pos < kv_len[:, None]
    if window is not None:
        valid = valid & (pos >= kv_len[:, None] + 1 - window)
    s_old = jnp.where(valid[:, None, None, :], s_old, NEG_INF)
    s_self = jnp.einsum("bkgd,bktd->bkgt", qf, kt,
                        preferred_element_type=jnp.float32)[..., 0]
    m_old = jnp.max(s_old, axis=-1)
    m = jnp.maximum(m_old, s_self)
    p_old = jnp.exp(s_old - m[..., None])
    p_self = jnp.exp(s_self - m)
    l = jnp.sum(p_old, axis=-1) + p_self
    out = jnp.einsum("bkgt,bktd->bkgd", p_old.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * vt[:, :, 0, :].astype(
        jnp.float32)[:, :, None, :]
    out = out / l[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)
