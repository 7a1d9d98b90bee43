"""granite-4.0-h-micro: the program against the plain float32 reference
(``bench/reference/granite.py``) at the SMOKE preset's sizes, on seeded
weights, compared by logits.

Tolerances.  Both sides compute in float32 at the highest matmul
precision, so they differ only by the order of operations: the program's
chunked SSD and fused projections against the reference's per-token
recurrence, ~4e-8 of logits whose largest are ~0.012 (the small tied
embedding and the ÷8 logit scaling).  ``ATOL`` (2e-5) leaves room for the
longer paths (chunked prefill, decode through the cache) and still sits
far below what the same program computed in bfloat16 misses by (1.2e-3)
and what a dropped scalar moves (every case of
``test_each_scalar_moves_the_logits`` more than 1e-3).  The engine tests
compare the engine with itself on the same arithmetic (reused slot
against a fresh one, an interleaved prefill against one back to back):
1e-6."""
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models.transformer import (chunk_prefill_step, decode_step,
                                      forward, init_paged_cache, init_params,
                                      layer_plan)
from repro.serve import PageAllocator, PagedEngine, chunk_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.reference import granite as ref  # noqa: E402

ATOL = 2e-5
SEED = 2147483901          # above 2**31: the reference folds in its high bits
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def smoke_conf() -> dict:
    """The benchmark's configuration at the SMOKE preset's sizes."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf = json.load(f)
    conf.update({"num_hidden_layers": 20, "hidden_size": 64,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "shared_intermediate_size": 128, "intermediate_size": 128,
                 "vocab_size": 512, "max_position_embeddings": 256,
                 "layer_types": PERIOD * 2, "mamba_n_heads": 8,
                 "mamba_d_head": 16, "mamba_d_state": 16,
                 "mamba_chunk_size": 16, "attention_multiplier": 1 / 16,
                 "program_size": "smoke"})
    return conf


def _f32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


@pytest.fixture(scope="module")
def granite():
    conf = smoke_conf()
    cfg = _f32(get_smoke_config("granite-4.0-h-micro"))
    params = ref.program_params(jax, conf, SEED,
                                padded_vocab=cfg.padded_vocab,
                                dtype="float32")
    return conf, cfg, params


def _tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _reference(conf, toks):
    return ref.forward_logits(jax, conf, SEED, [toks],
                              [np.arange(len(toks))],
                              served_dtype="float32")[0]


def _forward(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        lg, _ = forward(cfg, params, tokens=jnp.asarray(toks)[None])
    return np.asarray(lg[0, :, :cfg.vocab_size], np.float32)


def test_plan_and_sizes():
    cfg = get_config("granite-4.0-h-micro")
    plan = layer_plan(cfg)
    assert plan.pattern == ("ssm",) * 5 + ("dense",) + ("ssm",) * 4
    assert (plan.n_groups, plan.tail) == (4, ())
    smoke = get_smoke_config("granite-4.0-h-micro")
    params = init_params(smoke, jax.random.PRNGKey(0))
    assert smoke.n_params() == sum(x.size for x in jax.tree.leaves(params))


def test_reference_lays_weights_out_as_the_program(granite):
    conf, cfg, params = granite
    ref.check_config(cfg, conf)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), want))


def test_forward_matches_reference(granite):
    conf, cfg, params = granite
    toks = _tokens(100)
    want = _reference(conf, toks)
    np.testing.assert_allclose(_forward(cfg, params, toks), want,
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize("field,value", [
    ("embed_multiplier", 1.0), ("attn_scale", 1.0), ("attn_scale", None),
    ("residual_multiplier", 1.0), ("logits_divisor", 1.0)])
def test_each_scalar_moves_the_logits(granite, field, value):
    """A program that dropped one of Granite's scalars (or computed the
    softmax scale as 1/sqrt(head_dim), ``None``) would fail the
    comparison above by far more than its tolerance."""
    conf, cfg, params = granite
    toks = _tokens(100)
    want = _reference(conf, toks)
    got = _forward(dataclasses.replace(cfg, **{field: value}), params, toks)
    assert np.abs(got - want).max() > 50 * ATOL


def _engine(cfg, params, batch=2):
    return PagedEngine(cfg, params, batch=batch, max_len=96, page_size=16,
                       prefill_chunk=16)


def test_paged_prefill_then_decode_matches_reference(granite):
    """Chunked prefill over three chunks (NoPE attention through the page
    pool, the SSD scan carried across chunks), then decode through the
    cache, against the reference's full forward at every position."""
    conf, cfg, params = granite
    toks = _tokens(48, seed=1)
    n_prompt = 40
    want = _reference(conf, toks)
    eng = _engine(cfg, params)
    alloc = PageAllocator(eng.num_pages)
    pages = alloc.alloc(eng.pages_needed(n_prompt, 8))
    got = [np.asarray(eng.insert(1, toks[:n_prompt], page_ids=pages,
                                 max_new=8))[0, -1]]
    for t in range(n_prompt, len(toks) - 1):
        step = np.array([[0], [toks[t]]], np.int32)
        got.append(np.asarray(eng.decode(jnp.asarray(step)))[1, -1])
    got = np.stack(got)[:, :cfg.vocab_size]
    np.testing.assert_allclose(got, want[n_prompt - 1:len(toks) - 1],
                               atol=ATOL, rtol=0)


def test_reused_slot_starts_from_zero_state(granite):
    conf, cfg, params = granite
    a, b = _tokens(20, seed=2), _tokens(9, seed=3)

    fresh = _engine(cfg, params, batch=1)
    alloc = PageAllocator(fresh.num_pages)
    want = np.asarray(fresh.insert(
        0, b, page_ids=alloc.alloc(fresh.pages_needed(len(b), 4)),
        max_new=4))
    assert fresh.ssm_state_resets == 0

    used = _engine(cfg, params, batch=1)
    alloc = PageAllocator(used.num_pages)
    pages = alloc.alloc(used.pages_needed(len(a), 6))
    lg = used.insert(0, a, page_ids=pages, max_new=6)
    for _ in range(3):
        tok = np.asarray(jnp.argmax(lg[:, -1, :], -1), np.int32)[:, None]
        lg = used.decode(jnp.asarray(tok))
    alloc.free(pages)
    used.free_slot(0)
    assert used.ssm_state_resets == 0
    got = np.asarray(used.insert(
        0, b, page_ids=alloc.alloc(used.pages_needed(len(b), 4)),
        max_new=4))
    assert used.ssm_state_resets == 1
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(got[0, -1, :cfg.vocab_size],
                               _reference(conf, b)[-1], atol=ATOL, rtol=0)


def test_decode_leaves_a_mid_prefill_slot_alone(granite):
    conf, cfg, params = granite
    short, long = _tokens(6, seed=4), _tokens(40, seed=5)

    def setup():
        eng = _engine(cfg, params)
        alloc = PageAllocator(eng.num_pages)
        lg = eng.insert(0, short, page_ids=alloc.alloc(
            eng.pages_needed(len(short), 8)), max_new=8)
        tok = np.array([[int(jnp.argmax(lg[0, -1]))], [0]], np.int32)
        return eng, alloc.alloc(eng.pages_needed(len(long), 4)), tok

    eng, pages, _ = setup()
    want = np.asarray(eng.insert(1, long, page_ids=pages, max_new=4))

    eng, pages, tok = setup()
    for start, blen, vlen in chunk_plan(len(long), eng.chunk_len,
                                        eng.chunk_buckets):
        ck = np.zeros((1, blen), np.int32)
        ck[0, :vlen] = long[start:start + vlen]
        got = eng.prefill_chunk(1, ck, pages, start, vlen)
        eng.decode(jnp.asarray(tok), live_mask=np.array([True, False]))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[0, -1, :cfg.vocab_size],
                               _reference(conf, long)[-1], atol=ATOL, rtol=0)


def test_lane_dense_pool_decode_matches(granite):
    """The chip's pool layout (head size 16 padded to 128 lanes) through
    the paged kernel, in interpret mode, against the unpadded pool
    through the gather path."""
    _, cfg, params = granite
    B, PS, NP = 2, 16, 3
    pages = jnp.array([[1, 2, 0], [3, 4, 5]], jnp.int32)
    rng = np.random.default_rng(6)
    out = {}
    for dense in (False, True):
        cache = init_paged_cache(cfg, B, num_pages=1 + B * NP, page_size=PS,
                                 lane_dense=dense)
        cache["len"] = jnp.array([20, 37], jnp.int32)
        fill = lambda x: jnp.asarray(
            rng.normal(size=x.shape[:-1] + (cfg.hd,)).astype(np.float32))
        rng = np.random.default_rng(6)
        for bc in cache["groups"]:
            if "self" in bc:
                for kk in ("k", "v"):
                    pool = bc["self"][kk]
                    bc["self"][kk] = pool.at[..., :cfg.hd].set(fill(pool))
        step = functools.partial(decode_step, cfg, page_size=PS,
                                 paged_impl="interpret" if dense else "ref")
        lg, new = step(params, cache, jnp.array([[3], [7]], jnp.int32),
                       pages=pages)
        pools = [bc["self"]["k"] for bc in new["groups"] if "self" in bc]
        out[dense] = (np.asarray(lg), np.asarray(pools[0][..., :cfg.hd]))
        if dense:
            assert pools[0].shape[-1] == 128
            assert not np.asarray(pools[0][..., cfg.hd:]).any()
    np.testing.assert_allclose(out[True][0], out[False][0], atol=1e-5,
                               rtol=1e-5)
    # the written K of later layers passes through the earlier attention
    np.testing.assert_allclose(out[True][1], out[False][1], atol=1e-5,
                               rtol=1e-5)


def test_ssm_state_bytes_counts_the_cache_tree():
    cfg = get_config("granite-4.0-h-micro")
    eng = PagedEngine(cfg, None, batch=32, max_len=5120, page_size=16,
                      num_pages=10_241, prefill_chunk=512)
    # 36 Mamba-2 layers: the (64, 64, 128) float32 SSD state and the
    # 3-row conv tail of x, B and C (4096 + 2 x 128 channels)
    assert eng.ssm_state_bytes == 36 * (64 * 64 * 128 + 3 * 4352) * 4
    qwen = PagedEngine(get_config("qwen2-1.5b"), None, batch=2, max_len=64,
                       page_size=16, prefill_chunk=16)
    assert qwen.ssm_state_bytes == 0


# ---------------------------------------------------------------------------
# qwen2-1.5b's programs are as they were: no multiply by 1, no new scale op
# ---------------------------------------------------------------------------


def _count(jaxpr, prim: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == prim
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += _count(sub.jaxpr, prim)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += _count(sub, prim)
    return n


# ``mul`` equations, nested jaxprs included, before granite's scalars and
# attention scale were threaded through the model
QWEN2_MULS = {"decode_ref": 20, "decode_kernel": 33, "chunk": 20}


@pytest.mark.parametrize("program", sorted(QWEN2_MULS))
def test_qwen2_programs_keep_their_multiplies(program):
    cfg = get_smoke_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, PS, NP = 4, 16, 4
    cache = init_paged_cache(cfg, B, num_pages=1 + B * NP, page_size=PS)
    if program == "chunk":
        def chunk(p, c, t, s, st, v, pr):
            return chunk_prefill_step(cfg, p, c, t, slot=s, start=st,
                                      valid_len=v, pages_row=pr,
                                      page_size=PS)
        jaxpr = jax.make_jaxpr(chunk)(
            params, cache, jnp.zeros((1, 32), jnp.int32), 0, 0, 32,
            jnp.zeros((1, NP), jnp.int32))
    else:
        impl = program.split("_")[1]
        jaxpr = jax.make_jaxpr(functools.partial(
            decode_step, cfg, page_size=PS, paged_impl=impl))(
            params, cache, jnp.zeros((B, 1), jnp.int32),
            pages=jnp.zeros((B, NP), jnp.int32),
            update_mask=jnp.ones((B,), bool))
    assert _count(jaxpr.jaxpr, "mul") == QWEN2_MULS[program]
