"""Paged flash-decode Pallas TPU kernel.

vLLM-style paged attention (DESIGN.md §15): one query token per slot
attends over that slot's KV pages *in place* in the shared page pool.  The
per-slot page table, per-slot cache lengths and the layer index ride in as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``, the table
first); the pools of every layer, stacked ``(n_layers, P, KV, page_size, D)``,
stay in HBM (``memory_space=pl.ANY``) and the kernel copies the pages of
its layer itself, so neither a dense per-slot gather nor a copy of one
layer's pool is ever materialised.

Grid: one step per slot.  Inside it a loop runs over blocks of
``pages_per_block`` pages, from the slot's first live page (0, or the page
holding the sliding window's start) to its last (``ceil(kv_len /
page_size)``): the work follows each slot's live pages, not the table's
width, and a slot with ``kv_len == 0`` costs one empty step and no copy.
Each page of a layer's ``(P, KV, page_size, D)`` pool holds both kv heads
contiguously, so one DMA per page brings both heads of K (and one of V)
into a VMEM block buffer ``(KV, pages_per_block * page_size, D)``.  The
buffers are doubled: the next block, of this slot or of the next slot with
live pages, is started before the current one is computed.  Each block is
one f32 online-softmax update per kv head: q ``(g_pad, D)`` against the
block's keys.  GQA is native to the layout: q arrives grouped as
``(B, KV, g_pad, D)``.

Masking contract (shared with ``models.attention.gather_pages``): physical
page 0 is the reserved trash page — decode writes of free/mid-prefill slots
land there, so its contents are arbitrary.  It is never copied: its rows in
the block buffer are copied from a zero page instead (ZEROS, not NEG_INF),
so positions inside ``kv_len`` still contribute exp(0 - m) to the
denominator, exactly like the zero-filled rows the gather path produces.  Positions at or past
``kv_len`` (and outside the sliding window) are masked to NEG_INF; the
buffers are zeroed once per call, so what such rows hold is always finite.

The kernel emits the *unnormalised* accumulator plus the running (m, l)
statistics; the ops wrapper LSE-merges the current token's own K/V (the
delta-cache self term) outside, mirroring ``_decode_attn_plus_self`` so the
cache write stays a pure delta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
LANE = 128   # the (m, l) outputs broadcast over a full lane dim


def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  acc_ref, m_ref, l_ref, k_buf, v_buf, zero_page, sem,
                  state, *,
                  window: int | None, page_size: int, ppb: int):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_pages = tbl_ref.shape[1]
    KV, g_pad, D = q_ref.shape[1:]
    ps = page_size
    layer = layer_ref[0]

    def live_pages(slot):
        """[first, last) logical pages of ``slot`` holding a valid key."""
        kv_len = len_ref[slot]
        last = jnp.minimum((kv_len + ps - 1) // ps, n_pages)
        if window is None:
            return jnp.int32(0), last
        first = jnp.maximum(kv_len + 1 - window, 0) // ps
        return jnp.minimum(first, last), last

    def n_blocks(first, last):
        return (last - first + ppb - 1) // ppb

    def block_pages(first, last, block):
        return jnp.minimum(ppb, last - first - block * ppb)

    def start_block(slot, block, buf):
        """Copy one block's live pages into buffer ``buf``: both kv heads
        of a page in one DMA per pool; a trash page's rows are copied
        from the zero page instead."""
        first, last = live_pages(slot)
        base = first + block * ppb

        def page(j, _):
            pid = tbl_ref[slot, base + j]
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for pool, vmem in ((k_hbm, k_buf), (v_hbm, v_buf)):
                @pl.when(pid != 0)
                def _copy():
                    pltpu.make_async_copy(pool.at[layer, pid],
                                          vmem.at[buf, :, rows],
                                          sem.at[buf]).start()

                @pl.when(pid == 0)
                def _trash():
                    pltpu.make_async_copy(zero_page, vmem.at[buf, :, rows],
                                          sem.at[buf]).start()

        jax.lax.fori_loop(0, block_pages(first, last, block), page, None)

    def wait_block(block, buf):
        """Wait for the copies of this slot's block in ``buf``: every copy
        moves one page, so one page-sized wait each."""
        def page(j, _):
            for vmem in (k_buf, v_buf):
                pltpu.make_async_copy(zero_page, vmem.at[buf, :, pl.ds(0, ps)],
                                      sem.at[buf]).wait()

        jax.lax.fori_loop(0, block_pages(first, last, block), page, None)

    # state[0]: buffer the next block lands in; state[1]: 1 when this
    # slot's first block was already started by an earlier slot
    @pl.when(b == 0)
    def _init():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        zero_page[...] = jnp.zeros_like(zero_page)
        state[0] = 0
        state[1] = 0

    kv_len = len_ref[b]
    first, last = live_pages(b)
    nb = n_blocks(first, last)

    @pl.when(jnp.logical_and(nb > 0, state[1] == 0))
    def _first_block():
        start_block(b, 0, state[0])

    def empty(s):
        f, l = live_pages(jnp.minimum(s, n_slots - 1))
        return jnp.logical_and(s < n_slots, n_blocks(f, l) == 0)

    # the next slot with live pages: its first block follows this slot's
    # last (an empty slot looks for none)
    next_slot = jax.lax.while_loop(empty, lambda s: s + 1,
                                   jnp.where(nb > 0, b + 1, n_slots))

    @pl.when(nb > 0)
    def _chain():
        state[1] = (next_slot < n_slots).astype(jnp.int32)

    def body(i, carry):
        cur = state[0]
        nxt = 1 - cur
        last_block = i + 1 == nb
        slot = jnp.where(last_block, next_slot, b)

        @pl.when(slot < n_slots)
        def _next_block():
            start_block(slot, jnp.where(last_block, 0, i + 1), nxt)

        wait_block(i, cur)
        state[0] = nxt

        pos = (first + i * ppb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (g_pad, ppb * ps), 1)
        ok = pos < kv_len
        if window is not None:
            ok = jnp.logical_and(ok, pos >= kv_len + 1 - window)
        out = []
        for h in range(KV):
            m_prev, l_prev, acc_prev = carry[h]
            k = k_buf[cur, h].astype(jnp.float32)            # (T, D)
            v = v_buf[cur, h].astype(jnp.float32)
            s = jax.lax.dot_general(qs[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(ok, s, NEG_INF)                    # (g_pad, T)
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)
            corr = jnp.exp(m_prev - m)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            out.append((m, l_prev * corr + jnp.sum(p, axis=1, keepdims=True),
                        acc_prev * corr + pv))
        return tuple(out)

    qs = [q_ref[0, h].astype(jnp.float32) for h in range(KV)]  # (g_pad, D)
    init = ((jnp.full((g_pad, 1), NEG_INF, jnp.float32),
             jnp.zeros((g_pad, 1), jnp.float32),
             jnp.zeros((g_pad, D), jnp.float32)),) * KV
    for h, (m, l, acc) in enumerate(jax.lax.fori_loop(0, nb, body, init)):
        acc_ref[0, h] = acc
        m_ref[0, h] = jnp.broadcast_to(m, (g_pad, LANE))
        l_ref[0, h] = jnp.broadcast_to(l, (g_pad, LANE))


def paged_attention_kernel(q, k_pool, v_pool, pages, kv_len, layer, *,
                           pages_per_block: int,
                           window: int | None = None,
                           interpret: bool = False):
    """q: (B, KV, g_pad, D) pre-scaled grouped queries; k/v_pool:
    (n, P, KV, page_size, D) shared pools of n layers; pages: (B, n_pages)
    int32 page table; kv_len: (B,) int32 valid lengths (OLD lengths — the
    current token's self term is merged outside); layer: (1,) int32, the
    layer whose pages are read; ``pages_per_block``: pages per
    copy-and-compute block.

    Returns ``(acc, m, l)``: unnormalised f32 accumulator
    (B, KV, g_pad, D) and running max / denominator broadcast over a LANE
    axis, (B, KV, g_pad, LANE).
    """
    B, KV, g_pad, D = q.shape
    ps = k_pool.shape[3]
    ppb = pages_per_block

    kernel = functools.partial(_paged_kernel, window=window, page_size=ps,
                               ppb=ppb)
    per_slot = lambda b, tbl, lens, layer: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, g_pad, D), per_slot),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, g_pad, D), per_slot),
            pl.BlockSpec((1, KV, g_pad, LANE), per_slot),
            pl.BlockSpec((1, KV, g_pad, LANE), per_slot),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, KV, ppb * ps, D), k_pool.dtype),   # K blocks
            pltpu.VMEM((2, KV, ppb * ps, D), v_pool.dtype),   # V blocks
            pltpu.VMEM((KV, ps, D), k_pool.dtype),            # trash reads
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, g_pad, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, g_pad, LANE), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, g_pad, LANE), jnp.float32),
        ],
        # slots share the block buffers and the copies in flight, so the
        # grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pages, kv_len, layer, q, k_pool, v_pool)
