"""Attention: GQA + RoPE + causal/sliding/local-global masks.

Three execution paths, all numerically equivalent (tested against each
other and against the Pallas kernel oracle):

* ``dense``  — materialise scores; used for short sequences and as oracle.
* ``tiled``  — flash-style online-softmax over KV tiles (pure jnp, scan);
  the *lowering path* for long sequences so the compiled HLO never
  materialises an S×S tensor — this keeps the dry-run memory roofline
  honest on CPU, and is also what XLA:TPU receives when the Pallas kernel
  is disabled.
* ``pallas`` — the TPU kernel (``repro.kernels.flash_attention``), selected
  on TPU platforms or when forced; validated in interpret mode on CPU.

Decode (single new token vs. a long KV cache) is a separate einsum path:
it is memory-bound, and with the KV sequence axis sharded over the mesh the
softmax reductions lower to the all-reduce pattern of distributed
flash-decoding (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical

from .config import ModelConfig
from .layers import apply_norm, apply_rope, dense, init_dense, init_norm, rope_freqs

__all__ = ["init_attention", "attention", "decode_attention", "KVCache",
           "gather_pages"]

NEG_INF = -2.3819763e38  # large negative for bf16-safe masking


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    d, hd, H, KV = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "q": init_dense(ks[0], d, H * hd, cfg, bias=cfg.qkv_bias),
        "k": init_dense(ks[1], d, KV * hd, cfg, bias=cfg.qkv_bias),
        "v": init_dense(ks[2], d, KV * hd, cfg, bias=cfg.qkv_bias),
        "o": init_dense(ks[3], H * hd, d, cfg, scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, hd)
        p["k_norm"] = init_norm(cfg, hd)
    return p


# ---------------------------------------------------------------------------
# Mask helpers
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int | None, dtype):
    """(q, k) additive bias: 0 where attendable, NEG_INF elsewhere."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), jnp.bool_)
    rel = q_pos[:, None] - k_pos[None, :]
    if causal:
        ok = ok & (rel >= 0)
    if window is not None:
        ok = ok & (rel < window)
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


# ---------------------------------------------------------------------------
# Core attention paths (q: B,S,H,D  k/v: B,T,KV,D)
# ---------------------------------------------------------------------------


def _scale(scale, D):
    """The softmax scale: the configuration's ``attn_scale`` where it sets
    one, else 1 / sqrt(head_dim)."""
    return 1.0 / math.sqrt(D) if scale is None else scale


def _dense_attn(q, k, v, q_pos, k_pos, *, causal, window, scale=None):
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32) * _scale(scale, D)
    qg = qf.reshape(B, S, KV, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    scores = scores + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                                 dtype=scores.dtype)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def _tiled_attn(q, k, v, q_pos, k_pos, *, causal, window,
                q_tile: int = 1024, kv_tile: int = 1024, scale=None):
    """Flash-style: online softmax over KV tiles; python loop over q tiles
    (static triangular schedule — fully-masked tiles are never emitted into
    the HLO), ``lax.scan`` over kv tiles inside."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_tile = min(q_tile, S)
    kv_tile = min(kv_tile, T)
    # pad to tile multiples
    Sp, Tp = -(-S // q_tile) * q_tile, -(-T // kv_tile) * kv_tile
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, Sp - S), constant_values=-1)       # padded q: masked rows
    kpos = jnp.pad(k_pos, (0, Tp - T), constant_values=2**30)    # padded k: unattendable
    nq, nk = Sp // q_tile, Tp // kv_tile
    kp = kp.reshape(B, nk, kv_tile, KV, D)
    vp = vp.reshape(B, nk, kv_tile, KV, D)
    kpos_t = kpos.reshape(nk, kv_tile)
    scale = _scale(scale, D)

    outs = []
    for i in range(nq):
        qi = qp[:, i * q_tile:(i + 1) * q_tile].astype(jnp.float32) * scale
        qi = qi.reshape(B, q_tile, KV, G, D)
        qpos_i = qpos[i * q_tile:(i + 1) * q_tile]
        # causal: kv tiles strictly after this q tile can never be attended
        hi = nk if not causal else -(-((i + 1) * q_tile) // kv_tile)
        # sliding window: tiles entirely before the window start are masked
        lo = 0
        if window is not None and causal:
            lo = max(0, (i * q_tile - window - kv_tile + 1) // kv_tile)

        def step(carry, xs):
            m, l, acc = carry
            kj, vj, kpos_j = xs
            s = jnp.einsum("bqkgd,btkd->bkgqt", qi, kj.astype(jnp.float32))
            s = s + _mask_bias(qpos_i, kpos_j, causal=causal, window=window,
                               dtype=s.dtype)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_tile), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_tile), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_tile, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0),
            (kp[:, lo:hi].swapaxes(0, 1), vp[:, lo:hi].swapaxes(0, 1),
             kpos_t[lo:hi]))
        o = acc / jnp.maximum(l, 1e-37)[..., None]
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(B, q_tile, H, D))
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.astype(q.dtype)


def gather_pages(pool, page_table):
    """Assemble per-slot contiguous KV views from a shared page pool.

    pool: (P, KV, page_size, D) — one physical page pool for one layer — or
    (G, P, KV, page_size, D) group-stacked (ONE gather covers all scanned
    layers of a pattern: the gather runs outside the layer scan, not once
    per iteration); page_table: (B, n_pages) int32 — logical page i of slot
    b lives in physical page ``page_table[b, i]``.  Returns
    ([G,] B, KV, n_pages*page_size, D), the same layout dense caches use, so
    every attention path downstream is layout-agnostic.  The gather
    materialises the view (the TPU kernel route indexes pages inside the
    kernel instead); positions past a slot's ``kv_len`` may contain stale
    data from freed pages — they are masked to NEG_INF before the softmax
    exactly like the zero tail of a dense cache, so results are unaffected.

    Trash-page contract (shared with the paged_attention kernel's index
    map): table entry 0 is the engine's reserved trash page — free slots
    and the not-yet-written tail of a mid-prefill slot point there, and
    mid-prefill chunk writes land in it, so its CONTENTS are arbitrary
    concurrent garbage.  Rows gathered from page 0 are zeroed here rather
    than trusted to the kv_len mask alone: a mid-prefill slot's kv_len
    covers positions whose pages are still 0, and zeros reproduce the
    dense cache's zero tail bit-for-bit (exp(0-m) terms in the softmax
    denominator and 0·v in the numerator), where garbage would not.
    """
    if pool.ndim == 5:
        g = pool[:, page_table]                  # (G, B, n, KV, ps, D)
        g = jnp.where((page_table == 0)[None, :, :, None, None, None],
                      jnp.zeros((), g.dtype), g)
        G, B, n, KV, ps, D = g.shape
        out = g.transpose(0, 1, 3, 2, 4, 5).reshape(G, B, KV, n * ps, D)
        return logical(out, None, "slots", "kv_heads", None, None)
    g = pool[page_table]                         # (B, n, KV, ps, D)
    g = jnp.where((page_table == 0)[:, :, None, None, None],
                  jnp.zeros((), g.dtype), g)
    B, n, KV, ps, D = g.shape
    out = g.transpose(0, 2, 1, 3, 4).reshape(B, KV, n * ps, D)
    return logical(out, "slots", "kv_heads", None, None)


def _chunk_attn_with_cache(q, k_cache, v_cache, start, kt, vt, *,
                           window: int | None = None, scale=None):
    """Chunked-prefill attention: a prompt chunk at positions
    ``start .. start+C-1`` attends over the already-written cache entries
    (positions < start) plus itself (causal within the chunk) — the S>1
    generalisation of ``_decode_attn_plus_self``.  The chunk's own K/V enter
    through a separate score block so the cache write stays a pure delta.

    q: (B, C, H, D); k_cache/v_cache: (B, KV, T, D) views (dense buffers or
    gathered pages); kt/vt: (B, KV, C, D).  Scores are materialised
    (C × (T+C)) — chunks are short by construction, so this never
    approaches the S×S blow-up the tiled path exists to avoid.

    The FIRST chunk of every prompt has ``start == 0`` — nothing in the
    cache to read — so the whole C×T cache-score block is skipped behind a
    ``lax.cond``: measured on CPU it is the dominant cost of a chunk call
    (the view is worst-case wide), and most calls are first chunks (every
    short prompt is a single chunk).
    """
    B, C, H, D = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if k_cache.dtype != q.dtype:   # f8-stored caches: cast the layer slice
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
        kt = kt.astype(q.dtype)
        vt = vt.astype(q.dtype)
    qf = (q.reshape(B, C, KV, G, D) * _scale(scale, D)).astype(q.dtype)
    q_pos = start + jnp.arange(C)                                  # (C,)
    rel = q_pos[:, None] - q_pos[None, :]                          # (C, C)
    valid_self = rel >= 0
    if window is not None:
        valid_self = valid_self & (rel < window)

    def with_cache(_):
        t_pos = jnp.arange(T)
        s_old = jnp.einsum("bckgd,bktd->bkgct", qf, k_cache,
                           preferred_element_type=jnp.float32)
        valid_old = t_pos[None, :] < start                         # (1, T)
        if window is not None:
            valid_old = valid_old & (q_pos[:, None] - t_pos[None, :] < window)
        s_old = jnp.where(valid_old[None, None, None], s_old, NEG_INF)
        s_self = jnp.einsum("bckgd,bksd->bkgcs", qf, kt,
                            preferred_element_type=jnp.float32)
        s_self = jnp.where(valid_self[None, None, None], s_self, NEG_INF)
        s = jnp.concatenate([s_old, s_self], axis=-1)              # (.., T+C)
        w = jax.nn.softmax(s, axis=-1)
        w_old, w_self = w[..., :T], w[..., T:]
        out = jnp.einsum("bkgct,bktd->bckgd", w_old.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        out = out + jnp.einsum("bkgcs,bksd->bckgd", w_self.astype(vt.dtype),
                               vt, preferred_element_type=jnp.float32)
        return out.reshape(B, C, H, D).astype(q.dtype)

    def first_chunk(_):
        return _dense_attn(q, kt.swapaxes(1, 2), vt.swapaxes(1, 2),
                           q_pos, q_pos, causal=True, window=window,
                           scale=scale)

    return jax.lax.cond(jnp.asarray(start) > 0, with_cache, first_chunk,
                        None)


def decode_attention(q, k_cache, v_cache, kv_len, *, window: int | None = None,
                     scale=None):
    """One-token attention against a (possibly sequence-sharded) KV cache.

    q: (B, 1, H, D); caches: (B, KV, T, D) — heads-major layout so both dots
    contract the trailing dims without transpose copies; kv_len: scalar or
    (B,) — number of valid cache entries.  The dots consume the bf16 cache
    directly with fp32 accumulation (no materialised fp32 cast — §Perf).
    Softmax over the (sharded) T axis lowers to max/sum all-reduces:
    distributed flash-decoding.
    """
    B, _, H, D = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if k_cache.dtype != q.dtype:   # f8-stored caches: cast the layer slice
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    qf = (q.reshape(B, KV, G, D) * _scale(scale, D)).astype(q.dtype)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(T)[None, :]
    valid = pos < jnp.reshape(jnp.asarray(kv_len), (-1, 1))
    if window is not None:
        valid = valid & (pos >= jnp.reshape(jnp.asarray(kv_len), (-1, 1)) - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", w.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def _decode_attn_plus_self(q, k_cache, v_cache, kv_len_old, kt, vt, *,
                           window: int | None = None, scale=None):
    """Decode attention over the *old* cache entries plus the just-computed
    token's own K/V (kt/vt, (B,KV,1,D)) — so the cache write can happen
    outside, as a pure delta.  Numerically identical to writing first and
    attending over kv_len_old+1 entries."""
    B, _, H, D = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if k_cache.dtype != q.dtype:   # f8-stored caches: cast the layer slice
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
        kt = kt.astype(q.dtype)
        vt = vt.astype(q.dtype)
    qf = (q.reshape(B, KV, G, D) * _scale(scale, D)).astype(q.dtype)
    s_old = jnp.einsum("bkgd,bktd->bkgt", qf, k_cache,
                       preferred_element_type=jnp.float32)
    pos = jnp.arange(T)[None, :]
    kv_len_new = jnp.reshape(kv_len_old, (-1, 1)) + 1
    valid = pos < jnp.reshape(kv_len_old, (-1, 1))
    if window is not None:
        valid = valid & (pos >= kv_len_new - window)
    s_old = jnp.where(valid[:, None, None, :], s_old, NEG_INF)
    s_self = jnp.einsum("bkgd,bktd->bkgt", qf, kt,
                        preferred_element_type=jnp.float32)[..., 0]  # (B,KV,G)
    # log-sum-exp merge of the self term — no concat along the (sharded) T
    # axis, so everything stays shard-local except the max/sum reductions
    m_old = jnp.max(s_old, axis=-1)
    m = jnp.maximum(m_old, s_self)
    p_old = jnp.exp(s_old - m[..., None])
    p_self = jnp.exp(s_self - m)                                   # (B,KV,G)
    l = jnp.sum(p_old, axis=-1) + p_self
    out = jnp.einsum("bkgt,bktd->bkgd", p_old.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * vt[:, :, 0, :].astype(
        jnp.float32)[:, :, None, :]
    out = out / l[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full layer
# ---------------------------------------------------------------------------


class KVCache:
    """Pytree-friendly KV cache for one attention layer.

    Layout (B, KV, T, D): heads-major so decode dots contract trailing dims
    (no transpose copies of multi-GiB caches — §Perf)."""

    @staticmethod
    def init(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
        hd, KV = cfg.hd, cfg.n_kv_heads
        return {
            "k": jnp.zeros((batch, KV, max_len, hd), dtype),
            "v": jnp.zeros((batch, KV, max_len, hd), dtype),
        }


def attention(cfg: ModelConfig, p: dict, x, *, positions, kv_x=None,
              kv_positions=None, causal: bool = True,
              window: int | None = None, cache: dict | None = None,
              cache_len=None, impl: str = "auto",
              rope: bool | None = None, paged_impl: str = "ref",
              chunk_continue: bool = False) -> tuple[jax.Array, dict | None]:
    """Full attention layer: qkv proj -> rope -> core -> out proj.

    ``cache``/``cache_len``: decode mode — x is (B, 1, d); K/V for the new
    token are written at ``cache_len`` and attention runs against the cache.
    ``kv_x``: cross-attention (whisper decoder) — keys/values from encoder.
    ``chunk_continue``: S > 1 with a *live* cache — chunked prefill: the
    chunk attends over prior cache entries (< ``cache_len``) plus itself.
    Paged caches reach this layer in one of two forms: on the reference
    path the serving engine gathers per-slot views (``gather_pages``) into
    the dense (B, KV, T, D) layout before the block runs, so reads here are
    layout-agnostic; on the kernel path (``paged_impl`` in
    kernel/interpret, S == 1) the cache instead carries the raw pools of
    every layer plus the page table and this layer's index
    (``k_pool``/``v_pool``/``pages``/``layer``) and the paged flash-decode
    kernel copies this layer's pages itself — no gather, no slice.
    Writes stay deltas either way.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rope = cfg.use_rope if rope is None else rope
    scale = cfg.attn_scale

    q = dense(p["q"], x, cd).reshape(B, S, H, hd)
    reuse_cached_kv = cache is not None and kv_x is not None
    if reuse_cached_kv:
        k = v = None  # cross-attention decode: encoder K/V already cached
    else:
        src = x if kv_x is None else kv_x
        k = dense(p["k"], src, cd).reshape(B, src.shape[1], KV, hd)
        v = dense(p["v"], src, cd).reshape(B, src.shape[1], KV, hd)

    if cfg.qk_norm:
        q = apply_norm(cfg, p["q_norm"], q)
        if k is not None:
            k = apply_norm(cfg, p["k_norm"], k)

    kv_pos = positions if kv_positions is None else kv_positions
    if rope:
        sin_q, cos_q = rope_freqs(cfg, positions, hd)
        q = apply_rope(q, sin_q, cos_q)
        if kv_x is None:
            sin_k, cos_k = rope_freqs(cfg, kv_pos, hd)
            k = apply_rope(k, sin_k, cos_k)

    new_cache = None
    if cache is not None:
        if "k" in cache and cache["k"].shape[-1] != hd:
            # views of a lane-dense page pool carry D padded with zeros
            cache = {kk: cache[kk][..., :hd] for kk in ("k", "v")}
        if kv_x is None:
            # DELTA cache contract (§Perf iter 4 — best measured variant):
            # return only this step's K/V; the caller writes them into the
            # cache buffer.  The written value is independent of the cache
            # read.  (Write-then-read through the stacked carry was tried
            # and REFUTED: +113% memory term — see EXPERIMENTS.md §Perf.)
            k_store = cache.get("k", cache.get("k_pool"))
            kt = k.swapaxes(1, 2).astype(k_store.dtype)      # (B,KV,S,D)
            vt = v.swapaxes(1, 2).astype(k_store.dtype)
            # delta marked by key STRUCTURE (k_delta/v_delta) so it survives
            # being scanned out as ys (a bool leaf would get stacked)
            new_cache = {"k_delta": kt, "v_delta": vt}
            if S == 1 and "k_pool" in cache:
                # paged kernel path: attend straight against the page pool
                # through the table — DESIGN.md §15
                from repro.kernels.paged_attention import ops as pa_ops
                out = pa_ops.paged_decode_attention(
                    q, cache["k_pool"], cache["v_pool"], cache["pages"],
                    jnp.asarray(cache_len), kt, vt, layer=cache["layer"],
                    window=window, impl=paged_impl, scale=scale)
            elif S == 1:
                out = _decode_attn_plus_self(
                    q, cache["k"], cache["v"], jnp.asarray(cache_len),
                    kt, vt, window=window, scale=scale)
            elif chunk_continue:
                out = _chunk_attn_with_cache(
                    q, cache["k"], cache["v"], jnp.asarray(cache_len), kt, vt,
                    window=window, scale=scale)
            else:
                # batched prefill: attend over the freshly computed local
                # K/V (the cache holds exactly these entries when starting
                # from empty) — no cache read at all
                q_pos = jnp.asarray(cache_len) + jnp.arange(S)
                k_pos = jnp.asarray(cache_len) + jnp.arange(S)
                if S * S <= 4096 * 4096 // 4:
                    out = _dense_attn(q, k, v, q_pos, k_pos,
                                      causal=True, window=window, scale=scale)
                else:
                    out = _tiled_attn(q, k, v, q_pos, k_pos,
                                      causal=True, window=window, scale=scale)
        else:
            out = decode_attention(q, cache["k"], cache["v"], cache_len,
                                   window=None, scale=scale)
            new_cache = cache
    else:
        q_pos = positions[0] if positions.ndim > 1 else positions
        k_pos = kv_pos[0] if kv_pos.ndim > 1 else kv_pos
        if impl == "pallas":
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                         scale=scale)
        elif S * k.shape[1] <= 4096 * 4096 // 4 or impl == "dense":
            out = _dense_attn(q, k, v, q_pos, k_pos, causal=causal,
                              window=window, scale=scale)
        else:
            out = _tiled_attn(q, k, v, q_pos, k_pos, causal=causal,
                              window=window, scale=scale)

    out = dense(p["o"], out.reshape(B, S, H * hd), cd)
    return out, new_cache
