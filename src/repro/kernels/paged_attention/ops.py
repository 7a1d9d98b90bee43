"""Public wrapper: paged flash-decode attention over a shared page pool.

``paged_decode_attention`` is the drop-in replacement for the decode-path
``gather_pages`` + ``_decode_attn_plus_self`` pair: same inputs the serving
engine already holds (pool, page table, per-slot lengths, the current
token's K/V delta), same (B, 1, H, D) output, no materialised per-slot
view.  The pool may be one layer's or the stacked pools of every layer
with a layer index: the kernel reads that layer's pages in place, so a
layer scan hands it the whole stack and no layer's pool is copied out.
The kernel walks each slot's live pages only, one slot per grid step,
copying ``pages_per_block`` pages of both kv heads at a time
(``kernel.py``); the block size follows from the shapes
(:func:`_pages_per_block`), not from a tune cache.  The kernel returns
unnormalised (acc, m, l); the current token's self term is LSE-merged here
so the delta-cache write contract of ``models.attention`` is untouched.

Impl resolution differs from :func:`runtime.resolve_impl` in two ways:
``auto`` off-TPU resolves to ``ref`` (the gather oracle), not interpret —
decode runs every step of every serve trace, and the Pallas interpreter is
orders of magnitude too slow to be a serving default.  Tests opt into
``interpret`` explicitly so CPU CI still exercises the kernel body.  And
the kernel reads the pool in the query's dtype, so a pool stored in another
dtype (f8 KV) resolves ``auto`` to ``ref``, and an explicit ``kernel`` or
``interpret`` request for it raises instead of quietly running the ref.
The same holds for a step compiled over a multi-device mesh, which the
compiler cannot partition the kernel into.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ..runtime import IMPLS, on_tpu
from .kernel import paged_attention_kernel
from .ref import paged_decode_attention_ref

_SUBLANE = 8        # grouped-q axis padded to the f32 sublane tile
# keys per copy-and-compute block: on a v5e at 180 slots of ~1k tokens, 512
# ran 11% faster than 256 and 14% faster than 128
_BLOCK_KEYS = 512


def resolve_paged_impl(impl: str, kv_dtype=None, q_dtype=None, *,
                       partitioned: bool = False) -> str:
    """The impl a decode step runs for ``impl`` on this backend, given the
    pool's and the query's dtypes (unknown dtypes are taken to match).

    ``partitioned``: the step is compiled over a mesh of more than one
    device.  The compiler cannot partition a Mosaic kernel (it asks for a
    ``shard_map``, which this path does not have yet), so ``auto`` resolves
    to ``ref`` there and an explicit ``kernel`` raises."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; pick from {IMPLS}")
    same_dtype = (kv_dtype is None or q_dtype is None
                  or jnp.dtype(kv_dtype) == jnp.dtype(q_dtype))
    if impl == "auto":
        return ("kernel" if on_tpu() and same_dtype and not partitioned
                else "ref")
    if impl != "ref" and not same_dtype:
        raise ValueError(
            f"paged attention impl {impl!r} reads the KV pool in the query "
            f"dtype {jnp.dtype(q_dtype).name}, but the pool is "
            f"{jnp.dtype(kv_dtype).name}; use 'ref' or 'auto'")
    if impl == "kernel" and partitioned:
        raise ValueError("the paged attention kernel cannot run on a mesh of "
                         "more than one device (the compiler does not "
                         "partition Mosaic kernels); use 'ref' or 'auto'")
    return impl


def _pages_per_block(page_size: int, max_pages: int) -> int:
    """Pages per kernel block: enough for ``_BLOCK_KEYS`` keys, capped at
    the table's width (a slot never has more live pages than that)."""
    return max(1, min(_BLOCK_KEYS // page_size, max_pages))


def paged_decode_attention(q, k_pool, v_pool, pages, kv_len, kt, vt, *,
                           layer=0, window: int | None = None,
                           impl: str = "auto",
                           pages_per_block: int | None = None, scale=None):
    """One-token attention straight against a paged KV pool.

    q: (B, 1, H, D); k/v_pool: (P, KV, page_size, D') one layer's shared
    pools, or (n, P, KV, page_size, D') n layers' read at ``layer`` (an int
    or a traced int32 scalar; D' >= D: a lane-dense pool pads D with
    zeros); pages:
    (B, n_pages) int32 per-slot page table (physical page 0 = trash, read
    as zeros); kv_len: scalar or (B,) OLD cache lengths; kt/vt:
    (B, KV, 1, D) current-token K/V.  Returns (B, 1, H, D), numerically
    matching ``_decode_attn_plus_self`` over the gathered view.

    ``pages_per_block`` pins the kernel's block (tests walk several sizes
    over the same slots); unset, :func:`_pages_per_block` derives it.
    ``scale``: the softmax scale the query is multiplied by (None:
    1 / sqrt(D)).
    """
    if k_pool.ndim == 4:            # one layer's pools: a stack of one
        k_pool, v_pool = k_pool[None], v_pool[None]
    impl = resolve_paged_impl(impl, k_pool.dtype, q.dtype)
    if impl == "ref":
        return paged_decode_attention_ref(q, k_pool[layer], v_pool[layer],
                                          pages, kv_len, kt, vt,
                                          window=window, scale=scale)

    B, _, H, D = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    if pages_per_block is None:
        pages_per_block = _pages_per_block(k_pool.shape[3], pages.shape[1])

    kv_len = jnp.broadcast_to(jnp.reshape(jnp.asarray(kv_len), (-1,)),
                              (B,)).astype(jnp.int32)
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qf = (q.reshape(B, KV, G, D) * scale).astype(q.dtype)
    g_pad = -(-G // _SUBLANE) * _SUBLANE
    # a lane-dense pool stores D padded with zeros to its last axis: the
    # query's zero lanes meet them, and the output is sliced back
    d_pool = k_pool.shape[-1]
    qp = jnp.pad(qf, ((0, 0), (0, 0), (0, g_pad - G), (0, d_pool - D)))
    acc, m, l = paged_attention_kernel(
        qp, k_pool, v_pool, pages.astype(jnp.int32), kv_len,
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        pages_per_block=pages_per_block, window=window,
        interpret=(impl == "interpret"))
    acc, m, l = acc[:, :, :G, :D], m[:, :, :G, 0], l[:, :, :G, 0]

    # LSE merge of the current token's self term (delta-cache contract:
    # kt/vt are not yet in the pool) — mirrors _decode_attn_plus_self
    s_self = jnp.einsum("bkgd,bktd->bkgt", qf, kt.astype(q.dtype),
                        preferred_element_type=jnp.float32)[..., 0]
    m_tot = jnp.maximum(m, s_self)
    alpha = jnp.exp(m - m_tot)
    beta = jnp.exp(s_self - m_tot)
    l_tot = alpha * l + beta
    out = alpha[..., None] * acc + beta[..., None] * vt[:, :, 0, :].astype(
        jnp.float32)[:, :, None, :]
    out = out / l_tot[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)
