"""The Jacobi system of arXiv:1807.00394 §4 and its plain reference.

:func:`make_system` is the benchmark's copy of the definition of the
repository's ``make_system`` (a dense, diagonally dominant float32 system
``A`` with unit-scale off-diagonal noise ``N(0, 1)/n``, diagonal 3, and
``b = A @ x_true``), drawn on the device from ``--seed`` in one jitted
call instead of on the host.

:func:`solve` is the plain Jacobi iteration ``x <- x + (b - A x) / diag(A)``
in numpy, in float32.  :func:`control_solve` is the control, on the
device where the program runs: the same iteration with ``A`` stored in
bfloat16, the step that would tempt a change to halve the bytes a sweep
streams (``x`` and the products stay in float32, so ``A``'s rounding is
the only change).
"""
from __future__ import annotations

import numpy as np


def seed_key(jax, seed: int):
    """A PRNG key for any non-negative whole seed, also past 2**32."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_system(jax, n: int, seed: int, device=None):
    """``(A, b)`` on ``device``, float32."""
    import jax.numpy as jnp

    def build(key):
        ka, kx = jax.random.split(key)
        A = jax.random.normal(ka, (n, n), jnp.float32) / n
        A = A.at[jnp.arange(n), jnp.arange(n)].set(3.0)
        x_true = jax.random.normal(kx, (n,), jnp.float32)
        b = jnp.dot(A, x_true, precision=jax.lax.Precision.HIGHEST)
        return A, b

    key = seed_key(jax, seed)
    if device is not None:
        key = jax.device_put(key, device)
    A, b = jax.jit(build)(key)
    return A, b


def solve(A: np.ndarray, b: np.ndarray, iters: int) -> np.ndarray:
    """``iters`` Jacobi iterations from ``x = 0``, float32.  Stops early
    only at an exact fixed point, after which every further iterate is the
    same."""
    A = np.asarray(A, np.float32)
    b = np.asarray(b, np.float32)
    d = np.diag(A).copy()
    x = np.zeros_like(b)
    for _ in range(iters):
        x_new = x + (b - A @ x) / d
        if np.array_equal(x_new, x):
            break
        x = x_new
    return x


def control_solve(jax, A, b, iters: int):
    """The control on ``A``'s device: ``iters`` Jacobi iterations from
    ``x = 0`` with ``A`` stored in bfloat16 (``x``, the diagonal and the
    products in float32).  ``A`` is rounded by a call of its own, so the
    loop's compiler cannot fold the rounding away as excess precision."""
    import jax.numpy as jnp

    @jax.jit
    def control(A16, d, b):
        def body(_, x):
            ax = jnp.dot(A16.astype(jnp.float32), x,
                         precision=jax.lax.Precision.HIGHEST)
            return x + (b - ax) / d
        return jax.lax.fori_loop(0, iters, body, jnp.zeros_like(b))

    A16 = jax.jit(lambda A: A.astype(jnp.bfloat16))(A)
    return control(A16, jnp.diag(A), b)


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))
