"""Flash attention Pallas TPU kernel.

TPU adaptation of the (GPU) FlashAttention blocking (DESIGN.md §2): instead
of warp-level softmax reductions, tiles are sized for VMEM and the MXU —
(q_block × head_dim) and (kv_block × head_dim) operands with head_dim and
block sizes multiples of 128 where possible.  The kv axis is the innermost
*sequential* grid dimension; running max / denominator / accumulator live in
VMEM scratch across kv steps (the standard TPU flash schedule).

Supports GQA (q heads grouped over kv heads), causal masking and sliding
windows.  Grid: (batch, q_heads, q_blocks, kv_blocks).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int | None,
                 q_block: int, kv_block: int, kv_steps: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)

    # skip blocks that are fully masked (causal: kv entirely after q;
    # window: kv entirely before the window)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, ki * kv_block <= qi * q_block + q_block - 1)
    if window is not None:
        run = jnp.logical_and(
            run, (ki + 1) * kv_block - 1 >= qi * q_block - window + 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (qb, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (kb, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ok = k_pos < seq_len
        if causal:
            ok = jnp.logical_and(ok, q_pos >= k_pos)
        if window is not None:
            ok = jnp.logical_and(ok, q_pos - k_pos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                                   # (qb, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                   # (kb, d)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(ki == kv_steps - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           q_block: int = 512, kv_block: int = 512,
                           interpret: bool = False, scale=None):
    """q: (B, H, S, D); k/v: (B, KV, T, D).  Returns (B, H, S, D);
    ``scale`` None is 1 / sqrt(D)."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    assert S % q_block == 0 and T % kv_block == 0, (S, q_block, T, kv_block)
    nq, nk = S // q_block, T // kv_block
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, kv_steps=nk, seq_len=T)

    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),   # running max
            pltpu.VMEM((q_block, 1), jnp.float32),   # running denominator
            pltpu.VMEM((q_block, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
