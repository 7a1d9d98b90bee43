"""Compile the main path for a described, unattached TPU v5e chip.

Interpret mode runs a Pallas kernel's logic on the CPU but not the chip's
compiler, which refuses blocks that break the (8, 128) tiling, primitives
it cannot lower, and programs that do not fit.  These tests hand the TPU
compiler the kernels and serving steps at the widths the system runs them
(qwen2-1.5b, mamba2-370m, the paper's largest Jacobi system) as shapes
only: nothing runs and nothing is timed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.jacobi_sweep.ops import jacobi_sweep_residual
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.ssd_scan.ops import ssd_intra_chunk
from repro.models.transformer import (chunk_prefill_step, decode_step,
                                      init_paged_cache, init_params)

BATCH, PAGE, MAX_LEN = 8, 16, 280          # chip_smoke.py's serving shapes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without a chip: keep the cache off here
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _qwen2_state(one_chip):
    cfg = get_config("qwen2-1.5b")
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    n_pages = -(-MAX_LEN // PAGE)
    cache = jax.eval_shape(functools.partial(
        init_paged_cache, cfg, BATCH, num_pages=1 + BATCH * n_pages,
        page_size=PAGE))
    return cfg, _on(one_chip, params), _on(one_chip, cache), n_pages


def test_paged_attention_kernel_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-1.5b")
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_pages = -(-MAX_LEN // PAGE)
    bf16 = jnp.bfloat16
    args = (_spec(one_chip, (BATCH, 1, H, D), bf16),
            _spec(one_chip, (1 + BATCH * n_pages, KV, PAGE, D), bf16),
            _spec(one_chip, (1 + BATCH * n_pages, KV, PAGE, D), bf16),
            _spec(one_chip, (BATCH, n_pages), jnp.int32),
            _spec(one_chip, (BATCH,), jnp.int32),
            _spec(one_chip, (BATCH, KV, 1, D), bf16),
            _spec(one_chip, (BATCH, KV, 1, D), bf16))
    fn = functools.partial(paged_decode_attention, impl="kernel")
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_kernel_takes_the_page_table_first(one_chip):
    """At the batch cell's shapes (180 slots, 320 table pages, an
    18,500-page pool) the kernel's first operand is the page table: the
    benchmark's roofline reader finds the kernel by that operand."""
    cfg = get_config("qwen2-1.5b")
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    slots, max_pages, pool = 180, 320, 18_500
    bf16 = jnp.bfloat16
    args = (_spec(one_chip, (slots, 1, H, D), bf16),
            _spec(one_chip, (pool, KV, PAGE, D), bf16),
            _spec(one_chip, (pool, KV, PAGE, D), bf16),
            _spec(one_chip, (slots, max_pages), jnp.int32),
            _spec(one_chip, (slots,), jnp.int32),
            _spec(one_chip, (slots, KV, 1, D), bf16),
            _spec(one_chip, (slots, KV, 1, D), bf16))
    fn = functools.partial(paged_decode_attention, impl="kernel")
    compiled = jax.jit(fn).lower(*args).compile()
    # the profiler names an op by its text with the operands' shapes
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert f"custom-call(s32[{slots},{max_pages}]" in calls[0]


def test_jacobi_sweep_residual_compiles_at_paper_size(one_chip):
    n = 7209                       # the paper's largest Fig. 3 system
    f32 = jnp.float32
    fn = functools.partial(jacobi_sweep_residual, impl="kernel",
                           row_block=256, col_block=256)
    compiled = jax.jit(fn).lower(_spec(one_chip, (n, n), f32),
                                 *[_spec(one_chip, (n,), f32)] * 3).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_intra_chunk_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-370m")
    H, P, N, Q = (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_chunk)
    BC = 4096 // Q                 # one 4k-token sequence in chunks
    f32 = jnp.float32
    fn = functools.partial(ssd_intra_chunk, impl="kernel")
    compiled = jax.jit(fn).lower(
        _spec(one_chip, (BC, H, Q, P), f32),
        _spec(one_chip, (BC, H, Q, 1), f32),
        _spec(one_chip, (BC, H, Q, 1), f32),
        _spec(one_chip, (BC, Q, N), f32),
        _spec(one_chip, (BC, Q, N), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_paged_decode_step_compiles_with_kernel(one_chip):
    cfg, params, cache, n_pages = _qwen2_state(one_chip)
    step = functools.partial(decode_step, cfg, page_size=PAGE,
                             paged_impl="kernel")
    compiled = jax.jit(step).lower(
        params, cache, _spec(one_chip, (BATCH, 1), jnp.int32),
        pages=_spec(one_chip, (BATCH, n_pages), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_chunk_prefill_step_compiles(one_chip):
    cfg, params, cache, n_pages = _qwen2_state(one_chip)
    step = functools.partial(chunk_prefill_step, cfg, page_size=PAGE)
    scalar = _spec(one_chip, (), jnp.int32)
    compiled = jax.jit(step).lower(
        params, cache, _spec(one_chip, (1, 64), jnp.int32), slot=scalar,
        start=scalar, valid_len=scalar,
        pages_row=_spec(one_chip, (1, n_pages), jnp.int32)).compile()
    logits = compiled.out_info[0]
    assert logits.shape == (1, 1, cfg.padded_vocab)
