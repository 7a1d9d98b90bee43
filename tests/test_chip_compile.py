"""Compile the main path for a described, unattached TPU v5e chip.

Interpret mode runs a Pallas kernel's logic on the CPU but not the chip's
compiler, which refuses blocks that break the (8, 128) tiling, primitives
it cannot lower, and programs that do not fit.  These tests hand the TPU
compiler the kernels and serving steps at the widths the system runs them
(qwen2-1.5b, mamba2-370m, granite-4.0-h-micro, the paper's largest Jacobi
system) as shapes only: nothing runs and nothing is timed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import dataclasses
import functools
import re

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

BATCH, PAGE, MAX_LEN = 8, 16, 280          # a small paged serving shape


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


def _hlo_text(compiled) -> str:
    """The compiled module's text as the profiler names its ops: with the
    operands' shapes."""
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(opts)


def test_ssd_intra_chunk_compiles_at_granite_widths(one_chip):
    """One 512-token prefill chunk of granite-4.0-h-micro (H 64, P 64,
    N 128, Q 256).  The benchmark's SSD roofline reader finds the kernel
    by its five operands in this order and these shapes."""
    cfg = get_config("granite-4.0-h-micro")
    H, P, N, Q = (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_chunk)
    assert (H, P, N, Q) == (64, 64, 128, 256)
    BC = 512 // Q
    f32 = jnp.float32
    fn = functools.partial(ssd_intra_chunk, impl="kernel")
    compiled = jax.jit(fn).lower(
        _spec(one_chip, (BC, H, Q, P), f32),
        _spec(one_chip, (BC, H, Q, 1), f32),
        _spec(one_chip, (BC, H, Q, 1), f32),
        _spec(one_chip, (BC, Q, N), f32),
        _spec(one_chip, (BC, Q, N), f32)).compile()
    calls = [ln for ln in _hlo_text(compiled).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    shapes = [f"f32[{BC},{H},{Q},{P}]", f"f32[{BC},{H},{Q},1]",
              f"f32[{BC},{H},{Q},1]", f"f32[{BC},{Q},{N}]",
              f"f32[{BC},{Q},{N}]"]
    args = calls[0].split("custom-call(", 1)[1].split("custom_call_target")[0]
    assert re.findall(r"f32\[[\d,]*\]", args) == shapes


def test_paged_attention_kernel_compiles_at_granite_widths(one_chip):
    """Head size 64: the pool is lane-dense (padded to 128), as the
    engine allocates it for the kernel; an unpadded pool's page copies
    are refused."""
    cfg = get_config("granite-4.0-h-micro")
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    slots, max_pages, pool = 32, 320, 10_241
    bf16 = jnp.bfloat16
    args = (_spec(one_chip, (slots, 1, H, D), bf16),
            _spec(one_chip, (pool, KV, PAGE, 128), bf16),
            _spec(one_chip, (pool, KV, PAGE, 128), bf16),
            _spec(one_chip, (slots, max_pages), jnp.int32),
            _spec(one_chip, (slots,), jnp.int32),
            _spec(one_chip, (slots, KV, 1, D), bf16),
            _spec(one_chip, (slots, KV, 1, D), bf16))
    fn = functools.partial(paged_decode_attention, impl="kernel",
                           scale=cfg.attn_scale)
    compiled = jax.jit(fn).lower(*args).compile()
    assert f"custom-call(s32[{slots},{max_pages}]" in _hlo_text(compiled)


def test_granite_paged_decode_step_compiles_with_kernel(one_chip):
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"),
                              param_dtype="bfloat16")
    params = _on(one_chip, jax.eval_shape(functools.partial(init_params, cfg),
                                          jax.random.PRNGKey(0)))
    n_pages = -(-MAX_LEN // PAGE)
    cache = _on(one_chip, jax.eval_shape(functools.partial(
        init_paged_cache, cfg, BATCH, num_pages=1 + BATCH * n_pages,
        page_size=PAGE, lane_dense=True)))
    step = functools.partial(decode_step, cfg, page_size=PAGE,
                             paged_impl="kernel")
    compiled = jax.jit(step).lower(
        params, cache, _spec(one_chip, (BATCH, 1), jnp.int32),
        pages=_spec(one_chip, (BATCH, n_pages), jnp.int32),
        update_mask=_spec(one_chip, (BATCH,), jnp.bool_)).compile()
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


def _scheduled_ops(text: str):
    """(name, result shape, opcode, first operand) of every instruction in
    the module's scheduled computations (the entry, loop bodies and
    conditions), leaving out those inside fusions.  Tuple-shaped
    instructions (loops, kernel calls) are left out too."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    ops, comp = [], None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            comp = head.group(1)
            continue
        if comp in fused:
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\((.*)", ln)
        if m:
            first = re.search(r"%([\w.\-]+)", m.group(4))
            ops.append((m.group(1), m.group(2), m.group(3),
                        first.group(1) if first else None, m.group(4)))
    return ops


@pytest.mark.parametrize("arch,slots,pool_pages", [
    ("qwen2-1.5b", 180, 18_500),
    ("granite-4.0-h-micro", 32, 10_241),
])
def test_paged_decode_step_keeps_the_pool_in_place(one_chip, arch, slots,
                                                   pool_pages):
    """The decode step at the serving cells' shapes, with the cache donated
    as ``PagedEngine`` donates it: no op copies the stacked KV pools or a
    layer's slice of them (neither the layer scan nor a change of layout
    around the token write); each pool's one op is the write into it, in
    its own layout, on the donated buffer.  The kernel appears once, the
    page table its first operand.  The pools are the cells' own: one of a
    few MiB the compiler may hold whole in the chip's VMEM, and copy there."""
    cfg = dataclasses.replace(get_config(arch), param_dtype="bfloat16")
    max_pages = -(-5120 // PAGE)              # the cells' max_len
    lane_dense = cfg.hd % 128 != 0
    params = _on(one_chip, jax.eval_shape(functools.partial(init_params, cfg),
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(functools.partial(
        init_paged_cache, cfg, slots, num_pages=pool_pages, page_size=PAGE,
        lane_dense=lane_dense)))
    step = functools.partial(decode_step, cfg, page_size=PAGE,
                             paged_impl="kernel")
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, _spec(one_chip, (slots, 1), jnp.int32),
        pages=_spec(one_chip, (slots, max_pages), jnp.int32),
        update_mask=_spec(one_chip, (slots,), jnp.bool_)).compile()
    text = _hlo_text(compiled)

    pool = next(bc["self"]["k"].shape for bc in cache["groups"]
                if "self" in bc)
    dims = lambda shape: "bf16[" + ",".join(map(str, shape)) + "]"
    shapes = {dims(pool), dims(pool[1:])}
    ops = _scheduled_ops(text)
    of_pool = [op for op in ops if op[1].split("{")[0] in shapes
               and op[2] not in ("parameter", "get-tuple-element", "bitcast")]
    params_in = {name: (shape, int(re.match(r"(\d+)", rest).group(1)))
                 for name, shape, opcode, _, rest in ops
                 if opcode == "parameter" and shape.split("{")[0] in shapes}
    aliased = {int(n) for n in re.findall(
        r"\{[\d,]*\}: \((\d+), \{\}", text.split("\n", 1)[0])}
    writes = [op for op in of_pool
              if op[3] in params_in and op[1] == params_in[op[3]][0]]
    assert [op[:3] for op in of_pool if op not in writes] == []
    assert sorted(op[3] for op in writes) == sorted(params_in)
    assert all(n in aliased for _, n in params_in.values())
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and f"custom-call(s32[{slots},{max_pages}]" in ln]
    assert len(calls) == 1
