"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch·chunk, head), the dual quadratic form of the SSD
algorithm — the compute hot-spot of Mamba-2:

    cum   = cumsum(a)        (outside the kernel)      (Q,)
    L     = tril(exp(cum_t - cum_s))                   (Q, Q)
    M     = (C Bᵀ) ⊙ L                                 (Q, Q)
    y     = M (dt ⊙ x)                                 (Q, P)
    S_out = (B ⊙ dt ⊙ exp(cum_end - cum))ᵀ x           (N, P)

The inter-chunk state recurrence is a cheap sequential scan handled in jnp
by the caller (``repro.models.ssm.ssd_chunked``).  TPU adaptation: the
(Q, Q) decay/score matrix lives entirely in VMEM (Q = chunk ≤ 256 ⇒ 256 KB
fp32), and both heavy contractions are MXU matmuls.

Grid: (batch·chunks, heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the operands are float32: at default precision the MXU rounds them to
# bfloat16, which put the kernel ~5e-3 off its float32 oracle on the chip
_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, s_ref):
    # x: (1,1,Q,P) dt/cum: (1,1,Q,1) b/c: (1,Q,N)
    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    cum = cum_ref[0, 0]                          # (Q, 1)  cumsum(a), fp32
    B = b_ref[0].astype(jnp.float32)             # (Q, N)
    C = c_ref[0].astype(jnp.float32)             # (Q, N)
    Q = x.shape[0]

    # cum arrives precomputed: Pallas TPU has no cumsum lowering
    seg = cum - cum.reshape(1, Q)                # (Q, Q)  cum_t - cum_s
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)   # (Q, Q)
    M = CB * L
    y = jax.lax.dot_general(M, x * dt, (((1,), (0,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)    # (Q, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    decay_end = jnp.exp(cum[-1:, :] - cum)       # (Q, 1)
    Bw = B * (decay_end * dt)                    # (Q, N)
    S = jax.lax.dot_general(Bw, x, (((0,), (0,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)    # (N, P)
    s_ref[0, 0] = S.astype(s_ref.dtype)


def ssd_intra_chunk_kernel(xh, dt, a, Bm, Cm, *, interpret: bool = False):
    """xh: (BC, H, Q, P); dt/a: (BC, H, Q, 1); Bm/Cm: (BC, Q, N).

    Returns (y_intra (BC,H,Q,P), S_chunk (BC,H,N,P))."""
    BC, H, Q, P = xh.shape
    cum = jnp.cumsum(a.astype(jnp.float32), axis=2)
    N = Bm.shape[-1]
    grid = (BC, H)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BC, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BC, H, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dt, cum, Bm, Cm)
