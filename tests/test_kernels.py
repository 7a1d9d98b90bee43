"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle
across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.jacobi_sweep.ops import jacobi_sweep, jacobi_sweep_residual
from repro.kernels.jacobi_sweep.ref import (jacobi_sweep_ref,
                                            jacobi_sweep_residual_ref)
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.ssd_scan.ops import ssd_intra_chunk

KEYS = jax.random.split(jax.random.PRNGKey(0), 8)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, S, H, KV, D, causal, window, qb, kb
    (1, 128, 4, 4, 64, True, None, 64, 64),
    (2, 256, 4, 2, 64, True, None, 128, 128),
    (1, 512, 8, 2, 32, True, 64, 128, 64),
    (2, 256, 8, 8, 32, False, None, 256, 64),
    (1, 384, 4, 1, 64, True, 100, 128, 128),
    (1, 256, 2, 2, 128, True, None, 64, 256),
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    B, S, H, KV, D, causal, window, qb, kb = case
    q = jax.random.normal(KEYS[0], (B, S, H, D), dtype)
    k = jax.random.normal(KEYS[1], (B, S, KV, D), dtype)
    v = jax.random.normal(KEYS[2], (B, S, KV, D), dtype)
    ref = flash_attention(q, k, v, causal=causal, window=window, impl="ref")
    ker = flash_attention(q, k, v, causal=causal, window=window,
                          impl="interpret", q_block=qb, kv_block=kb)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_fully_masked_rows_are_finite():
    """Sliding window + causal can fully mask early rows of a late q block —
    output must stay finite (0/denominator guard)."""
    B, S, H, D = 1, 256, 2, 32
    q = jax.random.normal(KEYS[3], (B, S, H, D))
    k = jax.random.normal(KEYS[4], (B, S, H, D))
    v = jax.random.normal(KEYS[5], (B, S, H, D))
    out = flash_attention(q, k, v, causal=True, window=1, impl="interpret",
                          q_block=64, kv_block=64)
    assert np.isfinite(np.asarray(out, np.float32)).all()


# ---------------------------------------------------------------------------
# SSD intra-chunk
# ---------------------------------------------------------------------------

SSD_CASES = [
    (2, 2, 64, 32, 16),
    (4, 1, 128, 64, 32),
    (1, 8, 32, 16, 64),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_matches_oracle(case, dtype):
    BC, H, Q, P, N = case
    xh = jax.random.normal(KEYS[0], (BC, H, Q, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(KEYS[1], (BC, H, Q, 1))).astype(dtype)
    a = (-jnp.exp(jax.random.normal(KEYS[2], (BC, H, Q, 1)) * 0.3)
         * dt.astype(jnp.float32)).astype(dtype)
    Bm = jax.random.normal(KEYS[3], (BC, Q, N), dtype)
    Cm = jax.random.normal(KEYS[4], (BC, Q, N), dtype)
    y_r, s_r = ssd_intra_chunk(xh, dt, a, Bm, Cm, impl="ref")
    y_k, s_k = ssd_intra_chunk(xh, dt, a, Bm, Cm, impl="interpret")
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               atol=tol, rtol=tol)


def test_ssd_chunked_model_path_kernel_parity():
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 96, 4, 16, 8     # S not a chunk multiple: pad path
    xh = jax.random.normal(KEYS[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(KEYS[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(KEYS[2], (H,)) * 0.2)
    Bm = jax.random.normal(KEYS[3], (B, S, N))
    Cm = jax.random.normal(KEYS[4], (B, S, N))
    y1 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=32, impl="jnp")
    y2 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=32, impl="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-5, rtol=1e-5)


def test_ssd_sequential_oracle():
    """Chunked SSD == naive per-step recurrence."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 1, 40, 2, 8, 4
    xh = jax.random.normal(KEYS[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(KEYS[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(KEYS[2], (H,)) * 0.2)
    Bm = jax.random.normal(KEYS[3], (B, S, N))
    Cm = jax.random.normal(KEYS[4], (B, S, N))
    y = ssd_chunked(xh, dt, A, Bm, Cm, chunk=8)
    # naive recurrence
    state = np.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        dec = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])   # (B,H)
        upd = np.einsum("bh,bn,bhp->bhpn", np.asarray(dt[:, t]),
                        np.asarray(Bm[:, t]), np.asarray(xh[:, t]))
        state = state * dec[:, :, None, None] + upd
        ys.append(np.einsum("bn,bhpn->bhp", np.asarray(Cm[:, t]), state))
    y_naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), y_naive, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(64, 128), (256, 512), (8, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = jax.random.normal(KEYS[0], shape, dtype)
    g = (jax.random.normal(KEYS[1], (shape[-1],)) + 1.0).astype(jnp.float32)
    r = rmsnorm(x, g, impl="ref")
    k = rmsnorm(x, g, impl="interpret")
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(k, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# jacobi sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,rb,cb", [(256, 128, 128), (512, 256, 128),
                                     (512, 128, 512)])
def test_jacobi_sweep_matches_oracle(n, rb, cb):
    A = jax.random.normal(KEYS[0], (n, n)) / n + jnp.eye(n) * 3.0
    x = jax.random.normal(KEYS[1], (n,))
    b = jax.random.normal(KEYS[2], (n,))
    d = jnp.diag(A)
    r = jacobi_sweep(A, x, b, d, impl="ref")
    k = jacobi_sweep(A, x, b, d, impl="interpret", row_block=rb, col_block=cb)
    np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                               atol=1e-5, rtol=1e-5)


def _jacobi_system(n, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(ks[0], (n, n)) / n + jnp.eye(n) * 3.0
    x = jax.random.normal(ks[1], (n,)).astype(dtype)
    b = jax.random.normal(ks[2], (n,))
    return A, x, b, jnp.diag(A)


@pytest.mark.parametrize("n,rb,cb", [(256, 128, 128), (512, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_fused_residual_matches_unfused(n, rb, cb, dtype):
    """Fused kernel: x' identical to the unfused sweep, and the emitted
    residual equals ‖b - A·x‖ of the incoming iterate."""
    A, x, b, d = _jacobi_system(n, dtype)
    x2, res = jacobi_sweep_residual(A, x, b, d, impl="interpret",
                                    row_block=rb, col_block=cb)
    ref = jacobi_sweep_ref(A, x, b, d)
    res_true = float(jnp.linalg.norm(b - A @ x.astype(jnp.float32)))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(x2, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(float(res), res_true, rtol=max(tol, 1e-5))
    assert x2.dtype == x.dtype


@pytest.mark.parametrize("n", [200, 300, 333])
def test_jacobi_fused_residual_padding(n):
    """Non-divisible N: the wrapper zero-pads up to the block lcm; padded
    lanes must contribute exactly zero to x' and the residual."""
    A, x, b, d = _jacobi_system(n)
    x2, res = jacobi_sweep_residual(A, x, b, d, impl="interpret",
                                    row_block=128, col_block=128)
    x2r, rsqr = jacobi_sweep_residual_ref(A, x, b, d)
    assert x2.shape == (n,)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x2r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(res), float(jnp.sqrt(rsqr)), rtol=1e-5)
    # plain sweep wrapper pads too
    k = jacobi_sweep(A, x, b, d, impl="interpret", row_block=128,
                     col_block=128)
    np.testing.assert_allclose(np.asarray(k),
                               np.asarray(jacobi_sweep_ref(A, x, b, d)),
                               atol=1e-5, rtol=1e-5)


def test_jacobi_fused_iteration_halves_flops():
    """The paper's hot loop: one fused iteration (sweep + residual) must
    cost ~half the FLOPs of the unfused sweep-then-residual pair — i.e.
    exactly one A-matvec instead of two."""
    A, x, b, d = _jacobi_system(256)

    def unfused_iter(A, x, b, d):
        x2 = jacobi_sweep_ref(A, x, b, d)
        return x2, jnp.linalg.norm(b - A @ x2.astype(jnp.float32))

    def fused_iter(A, x, b, d):
        x2, rsq = jacobi_sweep_residual_ref(A, x, b, d)
        return x2, jnp.sqrt(rsq)

    cu = jax.jit(unfused_iter).lower(A, x, b, d).compile()
    cf = jax.jit(fused_iter).lower(A, x, b, d).compile()
    flops_unfused = dict(cu.cost_analysis() or {}).get("flops", 0.0)
    flops_fused = dict(cf.cost_analysis() or {}).get("flops", 0.0)
    if not flops_unfused:
        pytest.skip("cost_analysis reports no flops on this backend")
    assert flops_fused < 0.6 * flops_unfused, (flops_fused, flops_unfused)


def test_jacobi_fused_loop_matches_unfused_loop():
    """A fixed-iteration fused loop (lagged residual) produces the same
    iterates as the classic two-matvec loop."""
    n, iters = 128, 50
    A, x0, b, d = _jacobi_system(n)
    x_f = x0
    for _ in range(iters):
        x_f, _ = jacobi_sweep_residual(A, x_f, b, d, impl="ref")
    x_u = x0
    for _ in range(iters):
        x_u = jacobi_sweep_ref(A, x_u, b, d)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_u),
                               atol=1e-6, rtol=1e-6)


def test_jacobi_iteration_converges():
    """500 sweeps of a diagonally-dominant system reach the solution —
    the paper's §4 experiment in miniature."""
    n = 128
    A = np.asarray(jax.random.normal(KEYS[0], (n, n))) / n
    np.fill_diagonal(A, 4.0)
    x_true = np.asarray(jax.random.normal(KEYS[1], (n,)))
    b = A @ x_true
    A, b = jnp.asarray(A), jnp.asarray(b)
    d = jnp.diag(A)
    x = jnp.zeros((n,))
    for _ in range(500):
        x = jacobi_sweep(A, x, b, d, impl="ref")
    np.testing.assert_allclose(np.asarray(x), x_true, atol=1e-5)


# ---------------------------------------------------------------------------
# paged flash-decode attention
# ---------------------------------------------------------------------------

def _paged_case(key, B, H, KV, D, page_size, n_slot_pages, kv_lens, *,
                share=False, trash_tail=0):
    """Build a pool + page table exercising the serve layouts: ragged
    lengths, trailing trash-page entries, optionally slots SHARING physical
    pages (the prefix-cache / COW refcount>1 read case — DESIGN.md §11) and
    mid-prefill slots whose last ``trash_tail`` in-range logical pages still
    point at trash page 0 (a tuple instead names the in-range logical pages
    that do).  Page 0 is filled with NaN: the masking contract says its
    contents must never reach an output."""
    ks = jax.random.split(key, 5)
    n_pool = 1 + B * n_slot_pages
    k_pool = jax.random.normal(ks[0], (n_pool, KV, page_size, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_pool, KV, page_size, D), jnp.float32)
    k_pool = k_pool.at[0].set(jnp.nan)
    v_pool = v_pool.at[0].set(jnp.nan)
    table = np.zeros((B, n_slot_pages), np.int32)
    nxt = 1
    for b, L in enumerate(kv_lens):
        need = -(-max(int(L), 1) // page_size)
        for i in range(need):
            if share and b > 0 and i == 0:
                table[b, i] = table[0, 0]      # shared prefix page
            else:
                table[b, i] = nxt
                nxt += 1
        trash = (range(max(need - trash_tail, 0), need)
                 if isinstance(trash_tail, int) else trash_tail)
        for i in trash:
            table[b, i] = 0                    # mid-prefill: unwritten page
    q = jax.random.normal(ks[2], (B, 1, H, D), jnp.float32)
    kt = jax.random.normal(ks[3], (B, KV, 1, D), jnp.float32)
    vt = jax.random.normal(ks[4], (B, KV, 1, D), jnp.float32)
    return q, k_pool, v_pool, jnp.asarray(table), \
        jnp.asarray(kv_lens, jnp.int32), kt, vt


PA_CASES = [
    # B, H, KV, D, page_size, n_slot_pages, kv_lens, window, share, trash
    # [, (layers, layer, pool head size): the pools stacked over layers]
    (3, 4, 2, 64, 8, 4, (5, 17, 0), None, False, 0),
    (2, 8, 8, 64, 16, 3, (31, 16), None, False, 0),
    (4, 4, 4, 32, 8, 6, (40, 23, 8, 1), 11, False, 0),
    (3, 2, 2, 64, 16, 4, (33, 33, 50), None, True, 0),   # shared/COW pages
    (2, 4, 2, 32, 8, 5, (37, 21), None, False, 1),       # mid-prefill trash
    (2, 4, 1, 64, 16, 2, (9, 25), 7, True, 0),
    # the kernel's page blocks (run at 1 and 4 pages per block):
    (2, 4, 2, 32, 8, 11, (83, 45), None, False, 0),      # ragged last block
    (3, 4, 2, 32, 8, 8, (32, 64, 0), None, False, 0),    # on block bounds
    (2, 4, 2, 32, 8, 6, (48, 41), None, False, 0),       # every table page
    (3, 4, 2, 32, 8, 4, (0, 0, 0), None, False, 0),      # no live slot
    (2, 4, 2, 32, 8, 6, (44, 36), None, False, (1, 2)),  # trash mid-block
    # every layer's pools stacked, read at one layer in place:
    (2, 4, 2, 128, 16, 4, (37, 50), None, False, 0, (3, 0, 128)),
    (2, 4, 2, 128, 16, 4, (37, 50), None, False, 0, (3, 2, 128)),
    (2, 8, 2, 64, 16, 4, (20, 45), None, False, 0, (3, 0, 128)),  # lane-dense
    (2, 8, 2, 64, 16, 4, (20, 45), None, False, 0, (3, 2, 128)),
    (3, 4, 2, 32, 8, 6, (40, 23, 8), 11, False, 0, (3, 0, 32)),
    (3, 4, 2, 32, 8, 6, (40, 23, 8), 11, False, 0, (3, 2, 32)),
    (2, 4, 2, 32, 8, 4, (5, 30), None, False, (0,), (3, 0, 32)),  # all trash
    (2, 4, 2, 32, 8, 4, (5, 30), None, False, (0,), (3, 2, 32)),
]


def _stacked(key, pool, layers, layer, width):
    """``layers`` pools of head size ``width`` stacked, ``pool`` padded
    with zeros at ``layer`` and the others random, NaN on the trash
    page."""
    stack = jax.random.normal(key, (layers,) + pool.shape[:-1] + (width,),
                              pool.dtype).at[:, 0].set(jnp.nan)
    pad = jnp.pad(pool, [(0, 0)] * (pool.ndim - 1)
                  + [(0, width - pool.shape[-1])])
    return stack.at[layer].set(pad)


@pytest.mark.parametrize("case", PA_CASES)
@pytest.mark.parametrize("pages_per_block", [1, 4])
def test_paged_attention_kernel_matches_ref(case, pages_per_block):
    """The kernel against the gather oracle; on stacked pools, read at
    one layer, also bit-equal to the kernel on that layer's slice."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    B, H, KV, D, ps, n, lens, window, share, trash, *stack = case
    q, kp, vp, tbl, kv_len, kt, vt = _paged_case(
        KEYS[3], B, H, KV, D, ps, n, lens, share=share, trash_tail=trash)
    ref = paged_decode_attention(q, kp, vp, tbl, kv_len, kt, vt,
                                 window=window, impl="ref")
    layer = 0
    if stack:
        (layers, layer, width), = stack
        kp, vp = (_stacked(k, pool, layers, layer, width) for k, pool
                  in zip(jax.random.split(KEYS[7]), (kp, vp)))
    out = paged_decode_attention(q, kp, vp, tbl, kv_len, kt, vt,
                                 layer=layer, window=window,
                                 impl="interpret",
                                 pages_per_block=pages_per_block)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    if stack:
        one = paged_decode_attention(q, kp[layer], vp[layer], tbl, kv_len,
                                     kt, vt, window=window, impl="interpret",
                                     pages_per_block=pages_per_block)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(one))


@pytest.mark.parametrize("case", PA_CASES[:4])
def test_paged_attention_matches_models_gather_oracle(case):
    """Kernel vs the MODELS-level path it replaces: gather_pages (dense
    view materialisation, trash rows zeroed) + _decode_attn_plus_self.
    This pins the cross-layer contract, not just the in-package ref."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.models.attention import _decode_attn_plus_self, gather_pages
    B, H, KV, D, ps, n, lens, window, share, trash = case
    q, kp, vp, tbl, kv_len, kt, vt = _paged_case(
        KEYS[4], B, H, KV, D, ps, n, lens, share=share, trash_tail=trash)
    kc = gather_pages(kp, tbl)
    vc = gather_pages(vp, tbl)
    want = _decode_attn_plus_self(q, kc, vc, kv_len, kt, vt, window=window)
    got = paged_decode_attention(q, kp, vp, tbl, kv_len, kt, vt,
                                 window=window, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_paged_attention_all_trash_slot_is_finite():
    """A free slot (kv_len 0, whole table row on the NaN-poisoned trash
    page) must produce the degenerate self-only answer, not NaN."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    q, kp, vp, tbl, kv_len, kt, vt = _paged_case(
        KEYS[5], 2, 4, 2, 32, 8, 3, (0, 0))
    for impl in ("ref", "interpret"):
        out = np.asarray(paged_decode_attention(q, kp, vp, tbl, kv_len,
                                                kt, vt, impl=impl))
        assert np.isfinite(out).all()
        # kv_len 0 -> softmax collapses onto the self term: out == vt
        want = np.repeat(np.asarray(vt)[:, :, 0, :], 4 // 2, axis=1)
        np.testing.assert_allclose(out[:, 0], want, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("impl,kv_dtype,partitioned,want", [
    ("auto", jnp.float32, False, "ref"),           # CPU backend: gather
    ("auto", jnp.float8_e4m3fn, False, "ref"),
    ("interpret", jnp.float32, False, "interpret"),
    ("interpret", jnp.float8_e4m3fn, False, ValueError),
    ("kernel", jnp.float8_e4m3fn, False, ValueError),
    ("auto", jnp.float32, True, "ref"),
    ("kernel", jnp.float32, True, ValueError),
    ("ref", jnp.float8_e4m3fn, True, "ref"),
])
def test_paged_impl_resolution_never_falls_back_silently(
        impl, kv_dtype, partitioned, want):
    """An explicit kernel request raises where the kernel cannot run (a pool
    in another dtype than the query, a multi-device mesh); ``auto`` picks
    the gather path there instead."""
    from repro.kernels.paged_attention.ops import resolve_paged_impl
    args = (impl, kv_dtype, jnp.float32)
    if want is ValueError:
        with pytest.raises(ValueError):
            resolve_paged_impl(*args, partitioned=partitioned)
    else:
        assert resolve_paged_impl(*args, partitioned=partitioned) == want


def test_paged_attention_f8_pool_rejects_explicit_kernel():
    from repro.kernels.paged_attention.ops import paged_decode_attention
    q, kp, vp, tbl, kv_len, kt, vt = _paged_case(
        KEYS[6], 2, 4, 2, 32, 8, 3, (5, 9))
    kp8, vp8 = (kp.at[0].set(0).astype(jnp.float8_e4m3fn),
                vp.at[0].set(0).astype(jnp.float8_e4m3fn))
    out = paged_decode_attention(q, kp8, vp8, tbl, kv_len, kt, vt,
                                 impl="auto")
    assert np.isfinite(np.asarray(out)).all()
    with pytest.raises(ValueError, match="float8"):
        paged_decode_attention(q, kp8, vp8, tbl, kv_len, kt, vt,
                               impl="interpret")
