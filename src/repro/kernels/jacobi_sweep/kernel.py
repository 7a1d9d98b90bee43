"""Blocked Jacobi sweep Pallas TPU kernel — the paper's §4 hot loop.

One Jacobi iteration  x' = (b - (A - D) x) / diag(A)  as a blocked
matrix-vector product: grid (row_blocks, col_blocks), col axis innermost
sequential with a VMEM row accumulator; on the last col step the diagonal
correction, right-hand side and division are fused in.

TPU adaptation of the paper's OpenMP-parallel sweep: the (rb × cb) A tile
is the MXU operand; the accumulator never leaves VMEM (the paper's
"sequences of instructions" = row blocks here).

Two variants:

* :func:`jacobi_sweep_kernel` — the plain sweep, x' only.
* :func:`jacobi_sweep_residual_kernel` — **fused-residual** sweep.  On the
  last col step the accumulator holds ``A·x`` for the row block, so the
  residual of the *incoming* iterate, ``r = b - A·x``, is already in VMEM:
  the kernel emits both ``x' = x + r / d`` and the per-row squares ``r²``
  in the same pass.  The caller reduces them to ``‖b - A·x‖²`` outside the
  kernel (an (rb, 1) output block tiles like x'; a per-row-block (1, 1)
  partial breaks the chip's (8, 128) block tiling).  A convergence loop
  built on this needs exactly **one** A-matvec per iteration (the residual
  it tests is lagged by one iteration — standard for fused
  Jacobi/Richardson loops), halving the memory traffic of the
  sweep+residual pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# full float32 products: at default precision the chip's matrix unit rounds
# f32 operands to bfloat16, and the solver then stalls near a residual of
# 2^-9 relative instead of converging like its float32 reference
_F32 = jax.lax.Precision.HIGHEST


def _jacobi_kernel(a_ref, x_ref, b_ref, diag_ref, xr_ref, o_ref, acc, *,
                   n_col_blocks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[...].astype(jnp.float32)            # (rb, cb)
    x = x_ref[...].astype(jnp.float32)            # (cb, 1)
    acc[...] += jax.lax.dot_general(a, x, (((1,), (0,)), ((), ())),
                                    precision=_F32,
                                    preferred_element_type=jnp.float32)

    @pl.when(ci == n_col_blocks - 1)
    def _emit():
        b = b_ref[...].astype(jnp.float32)        # (rb, 1)
        d = diag_ref[...].astype(jnp.float32)     # (rb, 1)
        xr = xr_ref[...].astype(jnp.float32)      # (rb, 1)
        # acc holds (A x) including the diagonal term; remove it.
        o_ref[...] = ((b - acc[...] + d * xr) / d).astype(o_ref.dtype)


def jacobi_sweep_kernel(A, x, b, diag, *, row_block: int = 256,
                        col_block: int = 256, interpret: bool = False):
    """A: (N, N); x, b, diag: (N,).  Returns x' (N,)."""
    N = A.shape[0]
    rb, cb = min(row_block, N), min(col_block, N)
    assert N % rb == 0 and N % cb == 0, (N, rb, cb)
    x2 = x.reshape(N, 1)
    out = pl.pallas_call(
        functools.partial(_jacobi_kernel, n_col_blocks=N // cb),
        grid=(N // rb, N // cb),
        in_specs=[
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
            pl.BlockSpec((cb, 1), lambda r, c: (c, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        ],
        out_specs=pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), x.dtype),
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32)],
        interpret=interpret,
    )(A, x2, b.reshape(N, 1), diag.reshape(N, 1), x2)
    return out[:, 0]


def _jacobi_fused_kernel(a_ref, x_ref, b_ref, diag_ref, xr_ref, o_ref, p_ref,
                         acc, *, n_col_blocks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[...].astype(jnp.float32)            # (rb, cb)
    x = x_ref[...].astype(jnp.float32)            # (cb, 1)
    acc[...] += jax.lax.dot_general(a, x, (((1,), (0,)), ((), ())),
                                    precision=_F32,
                                    preferred_element_type=jnp.float32)

    @pl.when(ci == n_col_blocks - 1)
    def _emit():
        b = b_ref[...].astype(jnp.float32)        # (rb, 1)
        d = diag_ref[...].astype(jnp.float32)     # (rb, 1)
        xr = xr_ref[...].astype(jnp.float32)      # (rb, 1)
        r = b - acc[...]                          # residual rows of incoming x
        o_ref[...] = (xr + r / d).astype(o_ref.dtype)
        p_ref[...] = r * r


def jacobi_sweep_residual_kernel(A, x, b, diag, *, row_block: int = 256,
                                 col_block: int = 256,
                                 interpret: bool = False):
    """Fused sweep: returns ``(x', partials)`` in one A-pass.

    ``partials`` has shape (N, 1) fp32, one ``r²`` per row;
    ``partials.sum()`` is ``‖b - A·x‖²`` — the squared residual of the
    *input* iterate.
    """
    N = A.shape[0]
    rb, cb = min(row_block, N), min(col_block, N)
    assert N % rb == 0 and N % cb == 0, (N, rb, cb)
    x2 = x.reshape(N, 1)
    out, partials = pl.pallas_call(
        functools.partial(_jacobi_fused_kernel, n_col_blocks=N // cb),
        grid=(N // rb, N // cb),
        in_specs=[
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
            pl.BlockSpec((cb, 1), lambda r, c: (c, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), x.dtype),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32)],
        interpret=interpret,
    )(A, x2, b.reshape(N, 1), diag.reshape(N, 1), x2)
    return out[:, 0], partials
