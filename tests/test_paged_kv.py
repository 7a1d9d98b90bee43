"""Paged KV cache + chunked prefill (DESIGN.md §9): allocator invariants,
paged-vs-dense decode parity, chunked-vs-full prefill parity, surviving-slot
isolation, admission by free pages, bounded compiles, batched placement."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.transformer import init_params
from repro.serve import (Engine, HyParRequestTracker, PagedEngine,
                         PageAllocator, ServeScheduler, chunk_buckets_for,
                         chunk_plan)


def _fp32(cfg):
    return dataclasses.replace(cfg, compute_dtype="float32")


@pytest.fixture(scope="module")
def qwen():
    cfg = _fp32(get_smoke_config("qwen2-1.5b"))
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size - 1, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# Allocator + chunk planning units (no jax)
# ---------------------------------------------------------------------------


def test_page_allocator_exhaustion_free_and_no_aliasing():
    a = PageAllocator(8)                    # page 0 reserved -> 7 usable
    assert a.n_free == 7
    p1 = a.alloc(3)
    p2 = a.alloc(4)
    assert a.alloc(1) is None               # exhausted -> admission refusal
    # no aliasing: every outstanding page is unique and never the trash page
    assert len(set(p1) | set(p2)) == 7
    assert 0 not in p1 + p2
    a.free(p1)
    assert a.n_free == 3
    with pytest.raises(ValueError):         # double free refused
        a.free(p1)
    p3 = a.alloc(3)
    assert set(p3) == set(p1)               # recycled, still unique
    a.free(p2 + p3)
    assert a.n_free == 7 and a.n_outstanding == 0


def test_chunk_plan_is_page_aligned():
    buckets = chunk_buckets_for(64, 16)
    assert buckets == (16, 32, 64)
    assert chunk_plan(70, 64, buckets) == [(0, 64, 64), (64, 16, 6)]
    assert chunk_plan(64, 64, buckets) == [(0, 64, 64)]
    assert chunk_plan(5, 64, buckets) == [(0, 16, 5)]
    for true_len in (1, 17, 64, 65, 130):
        plan = chunk_plan(true_len, 64, buckets)
        assert all(start % 16 == 0 and blen % 16 == 0
                   for start, blen, _ in plan)
        assert sum(v for _, _, v in plan) == true_len
    with pytest.raises(ValueError):
        chunk_plan(0, 64, buckets)


# ---------------------------------------------------------------------------
# Parity: paged + chunked vs dense, end to end
# ---------------------------------------------------------------------------


# tier-1 archs: qwen2 (dense attention / paged KV pool) and mamba2 (SSM
# state continuation across chunks; no attention pool at all)
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-370m"])
def test_paged_scheduler_matches_dense(arch):
    """The same mixed-length request set through a dense engine and a paged
    engine (multi-chunk prefills included) must produce identical tokens for
    every request."""
    cfg = _fp32(get_smoke_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    # 40 > prefill_chunk => multi-chunk; 5/12 => single bucket chunks
    prompts = [_prompt(rng, cfg, n) for n in (5, 40, 12, 23)]

    def run(engine):
        sched = ServeScheduler(engine, buckets=(8, 16, 32, 64))
        rids = [sched.submit(p, max_new=6) for p in prompts]
        assert all(r is not None for r in rids)
        results = {r.rid: r.tokens for r in sched.run()}
        return [results[r] for r in rids]

    dense = run(Engine(cfg, params, batch=2, max_len=64))
    paged = run(PagedEngine(cfg, params, batch=2, max_len=64, page_size=8,
                            prefill_chunk=16))
    assert dense == paged


def test_chunked_prefill_logits_match_full_prefill(qwen):
    """First-token logits of a 3-chunk paged insert vs a one-shot dense
    prefill of the same prompt."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    prompt = _prompt(rng, cfg, 40)
    ref = Engine(cfg, params, batch=1, max_len=64)
    want = np.asarray(ref.prefill(jnp.asarray(prompt[None])))

    pe = PagedEngine(cfg, params, batch=2, max_len=64, page_size=8,
                     prefill_chunk=16)
    alloc = PageAllocator(pe.num_pages)
    pages = alloc.alloc(pe.pages_needed(len(prompt), 4))
    got = np.asarray(pe.insert(0, prompt, page_ids=pages, max_new=4))
    assert pe.trace_count("chunk_prefill") >= 2      # actually chunked
    np.testing.assert_allclose(got[0], want[0], atol=1e-4, rtol=1e-4)


def test_paged_insert_preserves_surviving_slots(qwen):
    """Mid-decode insert into a freed slot: the surviving slots' tokens are
    bit-identical to an uninterrupted run — chunk writes land only in the
    inserting slot's own pages (PR-3 parity guarantee under paging)."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    B, steps = 3, 8
    prompts = [_prompt(rng, cfg, 8) for _ in range(B)]
    newcomer = _prompt(rng, cfg, 21)                 # multi-chunk insert

    def run(insert_at):
        eng = PagedEngine(cfg, params, batch=B, max_len=64, page_size=8,
                          prefill_chunk=16)
        alloc = PageAllocator(eng.num_pages)
        slot_pages = []
        toks = np.zeros(B, np.int32)
        for b, p in enumerate(prompts):
            pages = alloc.alloc(eng.pages_needed(len(p), steps + 1))
            slot_pages.append(pages)
            lg = eng.insert(b, p, page_ids=pages, max_new=steps + 1)
            toks[b] = int(jnp.argmax(lg[0, -1]))
        outs = [toks.copy()]
        for i in range(steps):
            if insert_at is not None and i == insert_at:
                alloc.free(slot_pages[1])
                eng.free_slot(1)
                pages = alloc.alloc(eng.pages_needed(len(newcomer), steps))
                lg = eng.insert(1, newcomer, page_ids=pages, max_new=steps)
                toks = toks.copy()
                toks[1] = int(jnp.argmax(lg[0, -1]))
            lg = eng.decode(jnp.asarray(toks)[:, None])
            toks = np.asarray(jnp.argmax(lg[:, -1, :], -1), np.int32)
            outs.append(toks)
        return np.stack(outs, axis=1)

    base = run(None)
    mixed = run(3)
    assert np.array_equal(base[0], mixed[0])
    assert np.array_equal(base[2], mixed[2])
    assert not np.array_equal(base[1], mixed[1])


@pytest.mark.parametrize("arch", ["mamba2-370m", "qwen2-1.5b"])
def test_chunked_prefill_immune_to_interleaved_decode(arch):
    """Decode steps of the live batch between the chunks of a mid-prefill
    slot must not perturb that slot's state: attention K/V is parked on the
    trash page, and the live-mask freezes the dense per-slot SSM buffers.
    Logits-level check — token equality alone missed this (tiny smoke
    logit perturbations rarely flip the argmax)."""
    cfg = _fp32(get_smoke_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(10)
    short = _prompt(rng, cfg, 6)
    long = _prompt(rng, cfg, 40)                    # 3 chunks at 16

    def setup():
        eng = PagedEngine(cfg, params, batch=2, max_len=64, page_size=8,
                          prefill_chunk=16)
        alloc = PageAllocator(eng.num_pages)
        pg0 = alloc.alloc(eng.pages_needed(len(short), 8))
        lg = eng.insert(0, short, page_ids=pg0, max_new=8)
        tok = np.array([[int(jnp.argmax(lg[0, -1]))], [0]], np.int32)
        pages = alloc.alloc(eng.pages_needed(len(long), 4))
        return eng, pages, tok

    # reference: chunks back-to-back, no decode in between
    eng, pages, _ = setup()
    want = np.asarray(eng.insert(1, long, page_ids=pages, max_new=4))

    # interleaved: one live-batch decode step between each chunk, the
    # mid-prefill slot masked out exactly as ServeScheduler does
    eng, pages, tok = setup()
    got = None
    for start, blen, vlen in chunk_plan(len(long), eng.chunk_len,
                                        eng.chunk_buckets):
        ck = np.zeros((1, blen), np.int32)
        ck[0, :vlen] = long[start:start + vlen]
        got = eng.prefill_chunk(1, ck, pages, start, vlen)
        eng.decode(jnp.asarray(tok), live_mask=np.array([True, False]))
    eng.commit_slot(1, pages)
    np.testing.assert_allclose(np.asarray(got)[0], want[0],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch", ["mamba2-370m", "qwen2-1.5b"])
def test_paged_slot_reuse_resets_state(arch):
    """A request inserted into a freed slot must see none of the previous
    occupant's state: attention skips the cache read on the first chunk,
    and the SSM path resets the slot's conv tail + SSD state to the
    fresh-prefill zeros (there is no splice step to replace them)."""
    cfg = _fp32(get_smoke_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    a, b = _prompt(rng, cfg, 20), _prompt(rng, cfg, 9)

    def insert_b(eng, alloc):
        pages = alloc.alloc(eng.pages_needed(len(b), 4))
        return np.asarray(eng.insert(0, b, page_ids=pages, max_new=4))

    fresh = PagedEngine(cfg, params, batch=1, max_len=48, page_size=8,
                        prefill_chunk=16)
    alloc = PageAllocator(fresh.num_pages)
    want = insert_b(fresh, alloc)

    used = PagedEngine(cfg, params, batch=1, max_len=48, page_size=8,
                       prefill_chunk=16)
    alloc = PageAllocator(used.num_pages)
    pages = alloc.alloc(used.pages_needed(len(a), 6))
    lg = used.insert(0, a, page_ids=pages, max_new=6)
    tok = np.array([[int(jnp.argmax(lg[0, -1]))]], np.int32)
    for _ in range(3):
        lg = used.decode(jnp.asarray(tok))
        tok = np.asarray(jnp.argmax(lg[:, -1, :], -1), np.int32)[:, None]
    alloc.free(pages)
    used.free_slot(0)
    got = insert_b(used, alloc)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)


def test_dense_insert_masks_ssm_padding():
    """Regression for the dense insert path: a bucketed (right-padded)
    prompt into an SSM engine must produce the same tokens as the unpadded
    prompt — pad tokens must not decay into the state or the conv tail."""
    cfg = _fp32(get_smoke_config("mamba2-370m"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompt = _prompt(rng, cfg, 5)

    eng = Engine(cfg, params, batch=2, max_len=32)
    eng.prefill(jnp.asarray(np.stack([_prompt(rng, cfg, 8)] * 2)))
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = prompt
    lg = eng.insert(0, jnp.asarray(padded), true_len=5)

    ref = Engine(cfg, params, batch=1, max_len=32)
    want = ref.prefill(jnp.asarray(prompt[None]))
    np.testing.assert_allclose(np.asarray(lg)[0], np.asarray(want)[0],
                               atol=1e-4, rtol=1e-4)


def test_gather_pages_masks_trash_page_garbage(qwen):
    """Regression for the trash-page contract (DESIGN.md §15): table entry
    0 is the reserved trash page — free slots, the unwritten tail of every
    slot's table row, and mid-prefill chunk writes all point there, so its
    contents are arbitrary.  ``gather_pages`` must ZERO rows gathered from
    page 0 rather than trust the kv_len mask alone: mask-by-addition turns
    NaN/Inf garbage into NaN scores that survive the softmax even at
    masked positions.  Logits-level, bit-exact — the clean and the
    NaN-poisoned pool must decode identically."""
    cfg, params = qwen
    rng = np.random.default_rng(11)
    prompt = _prompt(rng, cfg, 6)

    def run(poison):
        eng = PagedEngine(cfg, params, batch=2, max_len=64, page_size=8,
                          prefill_chunk=16)
        alloc = PageAllocator(eng.num_pages)
        pages = alloc.alloc(eng.pages_needed(len(prompt), 4))
        lg = eng.insert(0, prompt, page_ids=pages, max_new=4)
        if poison:
            for part in ("groups", "tail"):
                for bc in eng.cache[part]:
                    if isinstance(bc, dict) and "self" in bc:
                        for key in ("k", "v"):
                            pool = bc["self"][key]
                            idx = ((slice(None), 0) if pool.ndim == 5
                                   else (0,))
                            bc["self"][key] = pool.at[idx].set(jnp.nan)
        tok = np.array([[int(jnp.argmax(lg[0, -1]))], [0]], np.int32)
        out = []
        for _ in range(3):
            lg = eng.decode(jnp.asarray(tok),
                            live_mask=np.array([True, False]))
            out.append(np.asarray(lg[0]))
            tok = np.array([[int(jnp.argmax(lg[0, -1]))], [0]], np.int32)
        return np.stack(out)

    clean, poisoned = run(False), run(True)
    assert np.isfinite(clean).all()
    assert np.array_equal(clean, poisoned), \
        "trash-page garbage leaked into decode logits"


# ---------------------------------------------------------------------------
# Scheduler: admission by pages, bounded compiles, batched placement
# ---------------------------------------------------------------------------


def test_page_exhaustion_defers_admission_until_retire(qwen):
    """A pool too small for two concurrent requests serialises them instead
    of shedding: the second request waits for the first retirement's pages."""
    cfg, params = qwen
    rng = np.random.default_rng(5)
    # each request needs ceil((16+4)/8) = 3 pages; pool has 4 usable
    eng = PagedEngine(cfg, params, batch=2, max_len=32, page_size=8,
                      prefill_chunk=16, num_pages=5)
    sched = ServeScheduler(eng, buckets=(16,))
    rids = [sched.submit(_prompt(rng, cfg, 10), max_new=4) for _ in range(3)]
    assert all(r is not None for r in rids)          # all admitted (queued)
    results = sched.run()
    assert sorted(r.rid for r in results) == sorted(rids)
    assert all(r.n_generated == 4 for r in results)
    assert sched.queue.n_rejected == 0
    # every page came back
    assert sched.allocator.n_outstanding == 0
    # with 3 pages/request and 4 free, the batch=2 engine never ran both
    # slots at once: concurrency was page-bound, not slot-bound
    assert sched.occupancy <= 0.75


def test_paged_never_fits_is_shed(qwen):
    cfg, params = qwen
    rng = np.random.default_rng(6)
    eng = PagedEngine(cfg, params, batch=2, max_len=32, page_size=8,
                      prefill_chunk=16)
    sched = ServeScheduler(eng)
    assert sched.submit(_prompt(rng, cfg, 30), max_new=8) is None  # > max_len
    assert sched.queue.n_rejected == 1


def test_paged_compile_counts_bounded(qwen):
    """N mixed-length requests compile one chunk-prefill program per chunk
    bucket and ONE decode program — compiles are workload-independent."""
    cfg, params = qwen
    rng = np.random.default_rng(7)
    eng = PagedEngine(cfg, params, batch=2, max_len=64, page_size=8,
                      prefill_chunk=16)                # buckets (8, 16)
    sched = ServeScheduler(eng)
    for n in (5, 12, 7, 20, 3, 40, 9, 14):
        assert sched.submit(_prompt(rng, cfg, n), max_new=4) is not None
    results = sched.run()
    assert len(results) == 8
    assert eng.trace_count("chunk_prefill") == len(eng.chunk_buckets) == 2
    assert eng.trace_count("decode") == 1


def test_admission_wave_issues_single_plan_segment_call(qwen):
    """Batched HyPar placement: one fill wave of N requests = ONE
    plan_segment call (PR 3 issued one per request — the ~25% serve
    overhead the ROADMAP flagged)."""
    from repro.core.scheduler import MasterScheduler
    cfg, params = qwen
    rng = np.random.default_rng(8)
    tracker = HyParRequestTracker(4, strategy="greedy")
    calls = []
    orig = tracker.master.plan_segment

    def counting(jobs, store, **kw):
        calls.append(len(jobs))
        return orig(jobs, store, **kw)

    tracker.master.plan_segment = counting
    eng = Engine(cfg, params, batch=4, max_len=32)
    sched = ServeScheduler(eng, buckets=(8,), tracker=tracker)
    rids = [sched.submit(_prompt(rng, cfg, 6), max_new=3) for _ in range(4)]
    results = sched.run()
    assert sorted(r.rid for r in results) == sorted(rids)
    assert calls[0] == 4                     # the whole wave in one call
    assert len(calls) == 1
    # and the graph/store were cleaned up per-request as before
    assert tracker.graph.n_jobs() == 0


def test_paged_hypar_tracker_matches_direct(qwen):
    """Placement through the job machinery must not change paged results."""
    cfg, params = qwen
    rng = np.random.default_rng(9)
    prompts = [_prompt(rng, cfg, n) for n in (6, 20, 7, 5)]

    def run(tracker):
        eng = PagedEngine(cfg, params, batch=2, max_len=48, page_size=8,
                          prefill_chunk=16)
        sched = ServeScheduler(eng, tracker=tracker)
        rids = [sched.submit(p, max_new=4) for p in prompts]
        return rids, {r.rid: r.tokens for r in sched.run()}

    _, direct = run(None)
    _, hypar = run(HyParRequestTracker(2, strategy="cost",
                                       flops_per_token=1e6))
    assert direct == hypar


def test_paged_engine_reports_resolved_attention_impl(qwen):
    """The engine resolves its decode attention once, from the backend and
    the pool dtype, and an explicit kernel request it cannot honour raises
    at construction instead of serving on the gather path."""
    cfg, params = qwen
    eng = PagedEngine(cfg, params, batch=2, max_len=32, page_size=8,
                      prefill_chunk=16)
    assert eng.attn_impl == "ref"               # CPU backend: gather
    eng = PagedEngine(cfg, params, batch=2, max_len=32, page_size=8,
                      prefill_chunk=16, attn_impl="interpret")
    assert eng.attn_impl == "interpret"
    f8 = dataclasses.replace(cfg, kv_cache_dtype="float8_e4m3fn")
    with pytest.raises(ValueError, match="float8"):
        PagedEngine(f8, params, batch=2, max_len=32, page_size=8,
                    prefill_chunk=16, attn_impl="interpret")


# ---------------------------------------------------------------------------
# The decode write by whole pages (the paged-kernel path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("seed,width", [(0, 8), (1, 16)])
def test_page_write_matches_row_write(stacked, seed, width):
    """``_write_kv_pages`` leaves every page but the trash page bit-equal
    to the row scatter ``_write_kv_paged``: live slots writing at the first
    and the last offset of a page, free slots writing to the trash page,
    and a slot that shares another's pages, its partial page copied first
    (``copy_page``) — the owner's rows stay as they were.  ``width`` 16
    pads a head size of 8 (a lane-dense pool)."""
    from repro.models.transformer import _write_kv_pages, _write_kv_paged
    rng = np.random.default_rng(seed)
    G, P, KV, ps, D, n = 3, 16, 2, 4, 8, 4
    ids = rng.permutation(np.arange(1, P))
    own0, own1, copy = ids[:3], ids[3:6], ids[6]
    table = np.zeros((5, n), np.int32)
    table[0, :3] = own0                 # writes own0[2] at offset 0
    table[1, :3] = own1                 # writes own1[2] at offset ps - 1
    table[4, :2] = own0[0], copy        # shares own0[:2]; own0[1] copied
    pos = np.array([2 * ps, 3 * ps - 1, 5, 2, ps + 2], np.int32)
    lead = (G,) if stacked else ()
    pool = jnp.asarray(rng.normal(size=lead + (P, KV, ps, width)),
                       jnp.bfloat16)
    pool = pool.at[..., copy, :, :, :].set(pool[..., own0[1], :, :, :])
    delta = jnp.asarray(rng.normal(size=lead + (5, KV, 1, D)), jnp.float32)
    args = (pool, delta, jnp.asarray(pos), jnp.asarray(table), ps)
    got = np.asarray(_write_kv_pages(*args, stacked=stacked), np.float32)
    want = np.asarray(_write_kv_paged(*args, stacked=stacked), np.float32)
    np.testing.assert_array_equal(got[..., 1:, :, :, :],
                                  want[..., 1:, :, :, :])
    before = np.asarray(pool, np.float32)
    for b, pid, off in ((0, own0[2], 0), (1, own1[2], ps - 1), (4, copy, 2)):
        row = np.zeros(lead + (KV, width), np.float32)
        row[..., :D] = np.asarray(delta[..., b, :, 0, :].astype(pool.dtype),
                                  np.float32)
        np.testing.assert_array_equal(got[..., pid, :, off, :], row)
    untouched = [p for p in range(1, P) if p not in (own0[2], own1[2], copy)]
    np.testing.assert_array_equal(got[..., untouched, :, :, :],
                                  before[..., untouched, :, :, :])


def test_kernel_decode_matches_gather_decode_tokens(qwen):
    """A ``PagedEngine`` decoding on the paged kernel (interpret mode, the
    pools read in place and written by whole pages) gives the gather
    path's tokens, a free slot writing to the trash page throughout."""
    cfg, params = qwen
    rng = np.random.default_rng(5)
    prompts = [_prompt(rng, cfg, 13), _prompt(rng, cfg, 8)]
    steps = 5

    def run(impl):
        eng = PagedEngine(cfg, params, batch=3, max_len=32, page_size=8,
                          prefill_chunk=16, attn_impl=impl)
        alloc = PageAllocator(eng.num_pages)
        toks = np.zeros(3, np.int32)
        for b, p in enumerate(prompts):
            pages = alloc.alloc(eng.pages_needed(len(p), steps + 1))
            lg = eng.insert(b, p, page_ids=pages, max_new=steps + 1)
            toks[b] = int(jnp.argmax(lg[0, -1]))
        outs, logits = [toks.copy()], []
        for _ in range(steps):
            lg = eng.decode(jnp.asarray(toks)[:, None])
            logits.append(np.asarray(lg[:2, -1]))
            toks = np.asarray(jnp.argmax(lg[:, -1, :], -1), np.int32)
            outs.append(toks)
        return np.stack(outs, axis=1)[:2], np.stack(logits)

    (ref_toks, ref_lg), (ker_toks, ker_lg) = run("ref"), run("interpret")
    np.testing.assert_array_equal(ker_toks, ref_toks)
    np.testing.assert_allclose(ker_lg, ref_lg, atol=1e-4, rtol=1e-4)
