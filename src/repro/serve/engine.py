"""Batched serving engine: prefill + decode with slot-based continuous
batching.

The decode fleet is the HyPar picture one level up (DESIGN.md §4, §8): each
*slot* is a job whose KV cache is retained device-local (``no_send_back``);
a finished request frees its slot and a waiting request is prefilled into
it (``insert``), without disturbing the other slots — dynamic job creation
at serving time.  The request-level scheduler that drives this lives in
``repro.serve.scheduler``.

Compilation contract: the engine owns exactly three jitted programs —
batched prefill, single-step decode, and the slot splice — each compiled
once per input-shape signature and reused for every request.  Slot
insertion reuses the *same* prefill program at the ``(1, S)`` signature, so
N inserts of same-length (bucketed) prompts cost one compilation total.
``trace_count(name)`` exposes the per-program trace counters the
compile-counter test asserts on.

Per-slot positions: after the first prefill the cache ``len`` is a ``(B,)``
vector, one length per slot, so a short prompt inserted into a batch that
has already decoded far ahead attends, RoPEs, and writes its KV at *its
own* position rather than the global cache length.  The vector form is
kept even while all slots are uniform — deliberately: interrupted and
uninterrupted batches then run the SAME compiled decode program, which is
what makes surviving slots bit-identical under continuous batching.  The
cost is one vmapped KV-write slice per slot instead of one batched slice;
raw ``decode_step`` users (training, parity tests) keep the scalar path.

Sharding comes from the ambient ``use_rules`` context: the KV cache batch
axis maps to ("pod","data"), the KV sequence axis to "model"
(flash-decoding with all-reduce softmax merges; long_500k shards sequence
over every axis).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.paged_attention import resolve_paged_impl
from repro.models.config import ModelConfig
from repro.parallel.sharding import ShardingRules, use_rules
from repro.models.transformer import (chunk_prefill_step, decode_step,
                                      init_cache, init_paged_cache,
                                      layer_plan)
from repro.models.layers import apply_norm
from repro.models.transformer import _run_stack  # encoder reuse

__all__ = ["Engine", "PagedEngine", "SamplingParams", "count_generated",
           "chunk_plan", "chunk_buckets_for"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0       # 0 => greedy
    top_k: int = 0                 # 0 => no top-k filter
    stop_token: int = -1           # -1 => never stop early


def count_generated(out: np.ndarray, stop_token: int) -> int:
    """Real generated tokens in a ``generate`` result: stop-token padding
    rows emitted after a sequence terminated do not count (the first stop
    token itself does — the model produced it)."""
    out = np.asarray(out)
    if stop_token < 0:
        return int(out.size)
    total = 0
    for row in out:
        hits = np.flatnonzero(row == stop_token)
        total += int(hits[0]) + 1 if hits.size else row.size
    return total


class Engine:
    """Owns jitted prefill/decode/splice programs for one model + max_len."""

    def __init__(self, cfg: ModelConfig, params, *, batch: int, max_len: int,
                 donate_cache: bool = True):
        self.cfg, self.params = cfg, params
        self.batch, self.max_len = batch, max_len
        self._trace_counts: collections.Counter = collections.Counter()

        def _prefill(params, cache, tokens, embeds, enc_embeds, last_idx):
            self._trace_counts["prefill"] += 1
            enc_out = None
            if cfg.family == "encdec":
                plan = layer_plan(cfg)
                e = enc_embeds.astype(jnp.dtype(cfg.compute_dtype))
                e = e + params["enc_pos"][: e.shape[1]].astype(e.dtype)[None]
                e, _ = _run_stack(cfg, plan.enc_pattern,
                                  tuple(params["enc_groups"]), (), (), None,
                                  e, jnp.arange(e.shape[1]))
                enc_out = apply_norm(cfg, params["enc_norm_f"], e)
            logits, cache = decode_step(cfg, params, cache, tokens,
                                        enc_out=enc_out, embeds=embeds,
                                        valid_len=last_idx + 1)
            # logits at the *true* last prompt token (bucketed prompts are
            # right-padded; the pad tail must not pick the sampled logits —
            # and for SSM layers the pad must not decay into the state)
            last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
            B = (tokens if tokens is not None else embeds).shape[0]
            cache = {**cache, "len": jnp.broadcast_to(cache["len"], (B,))}
            return last, cache, enc_out

        def _decode(params, cache, tokens, enc_out):
            self._trace_counts["decode"] += 1
            return decode_step(cfg, params, cache, tokens, enc_out=enc_out)

        def _splice(cache, mini_cache, enc_out, mini_enc, slot, true_len):
            self._trace_counts["splice"] += 1
            new_groups = [
                jax.tree.map(lambda f, o: _splice_batch(f, o, slot), gf, go)
                for gf, go in zip(cache["groups"], mini_cache["groups"])]
            new_tail = [
                jax.tree.map(lambda f, o: _splice_batch(f, o, slot), tf, to)
                for tf, to in zip(cache["tail"], mini_cache["tail"])]
            lens = jnp.broadcast_to(jnp.asarray(cache["len"]), (batch,))
            lens = lens.at[slot].set(true_len)
            new_enc = enc_out
            if enc_out is not None:
                new_enc = jax.lax.dynamic_update_slice_in_dim(
                    enc_out, mini_enc.astype(enc_out.dtype), slot, axis=0)
            return ({"groups": new_groups, "tail": new_tail, "len": lens},
                    new_enc)

        donate = (1,) if donate_cache else ()
        self._prefill = jax.jit(_prefill, donate_argnums=donate)
        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._splice = jax.jit(_splice,
                               donate_argnums=(0,) if donate_cache else ())
        enc_len = 1 if cfg.family == "encdec" else 0
        self._fresh_b1 = jax.jit(
            functools.partial(init_cache, cfg, 1, max_len, enc_len=enc_len))
        self._enc_out = None
        self.cache = None

    def trace_count(self, name: str) -> int:
        """How many times program ``name`` (prefill|decode|splice) has been
        traced (= compiled signatures) so far."""
        return self._trace_counts[name]

    def probe_device(self) -> bool:
        """Serve-layer health probe (DESIGN.md §14): one tiny jitted op must
        execute on the device and transfer back.  Returns False instead of
        raising so group failover can keep a group quarantined and retry —
        a probe is exactly the place failure is expected."""
        try:
            x = jnp.ones((2,), jnp.int32)
            return int(jax.block_until_ready(jnp.sum(x))) == 2
        except Exception:
            return False

    # -- lifecycle -------------------------------------------------------------
    def fresh_cache(self):
        enc_len = 0
        if self.cfg.family == "encdec":
            enc_len = 1  # cross K/V recomputed from enc_out, no cache needed
        return init_cache(self.cfg, self.batch, self.max_len, enc_len=enc_len)

    def ensure_batch(self, *, enc_len: int | None = None) -> None:
        """Initialise an empty live batch (all slots free, zero lengths) so
        insert-driven serving can start without a full-batch prefill.  For
        encdec models ``enc_len`` sizes the encoder-output buffer the per-slot
        inserts splice into."""
        if self.cache is None:
            cache = self.fresh_cache()
            cache["len"] = jnp.zeros((self.batch,), jnp.int32)
            self.cache = cache
        if self.cfg.family == "encdec" and self._enc_out is None:
            if enc_len is None:
                raise ValueError("encdec ensure_batch() needs enc_len to size "
                                 "the encoder-output buffer")
            self._enc_out = jnp.zeros(
                (self.batch, enc_len, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype))

    def prefill(self, tokens=None, *, embeds=None, enc_embeds=None):
        """tokens: (batch, S). Returns last-position logits (batch, 1, V)."""
        self.cache = self.fresh_cache()
        S = (tokens if tokens is not None else embeds).shape[1]
        logits, self.cache, self._enc_out = self._prefill(
            self.params, self.cache, tokens, embeds, enc_embeds, S - 1)
        return logits

    def decode(self, tokens):
        """tokens: (batch, 1) — one step for every slot."""
        logits, self.cache = self._decode(self.params, self.cache, tokens,
                                          self._enc_out)
        return logits

    # -- sampling ----------------------------------------------------------------
    @staticmethod
    @functools.partial(jax.jit, static_argnames=("sp",))
    def _sample(logits, key, sp: SamplingParams):
        lg = logits[:, -1, :].astype(jnp.float32)
        if sp.top_k:
            thresh = jax.lax.top_k(lg, sp.top_k)[0][:, -1:]
            lg = jnp.where(lg < thresh, -jnp.inf, lg)
        if sp.temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / sp.temperature).astype(jnp.int32)

    def generate(self, tokens, *, max_new: int, sp: SamplingParams = SamplingParams(),
                 key=None, enc_embeds=None) -> np.ndarray:
        """Greedy/temperature generation for a full batch.  Returns
        (batch, max_new) generated ids (stop_token-padded after stop)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        logits = self.prefill(tokens, enc_embeds=enc_embeds)
        out = []
        done = np.zeros((tokens.shape[0],), bool)
        cur = None
        for i in range(max_new):
            key, sub = jax.random.split(key)
            cur = self._sample(logits, sub, sp)
            ids = np.asarray(cur)
            if sp.stop_token >= 0:
                ids = np.where(done, sp.stop_token, ids)
                done |= ids == sp.stop_token
            out.append(ids)
            if done.all():
                out.extend([np.full_like(ids, sp.stop_token)] *
                           (max_new - len(out)))
                break
            logits = self.decode(jnp.asarray(ids)[:, None])
        return np.stack(out, axis=1)

    # -- continuous batching -----------------------------------------------------
    def insert(self, slot: int, tokens_1xS, *, true_len: int | None = None,
               enc_embeds=None):
        """Prefill a single request into slot ``slot`` without disturbing the
        other slots (slot-local cache splice).

        ``tokens_1xS`` may be right-padded to a bucket length; ``true_len``
        is the unpadded prompt length (defaults to the full width).  The
        slot's cache length is set to ``true_len`` so subsequent decode
        steps position, mask, and write at the request's own offset.

        Returns the logits of the true last prompt token, (1, 1, V), so the
        caller can sample the request's first generated token immediately
        (time-to-first-token is the prefill, not the next batch step).
        """
        if self.cache is None:
            raise RuntimeError("insert() needs a live batch; call prefill() "
                               "first")
        S = tokens_1xS.shape[1]
        true_len = S if true_len is None else int(true_len)
        if not 0 < true_len <= S:
            raise ValueError(f"true_len {true_len} outside (0, {S}]")
        if not 0 <= slot < self.batch:
            raise ValueError(f"slot {slot} outside [0, {self.batch})")
        if self.cfg.family == "encdec":
            if enc_embeds is None:
                raise ValueError(
                    "inserting into an encdec engine requires enc_embeds — "
                    "the slot's encoder output must be spliced alongside its "
                    "KV cache")
            if self._enc_out is None:
                raise RuntimeError("encdec insert() needs a live batch with "
                                   "encoder output; call prefill() first")
            if enc_embeds.shape[1] != self._enc_out.shape[1]:
                raise ValueError(
                    f"enc_embeds length {enc_embeds.shape[1]} != batch "
                    f"encoder length {self._enc_out.shape[1]}")
        logits, mini_cache, mini_enc = self._prefill(
            self.params, self._fresh_b1(), tokens_1xS, None, enc_embeds,
            true_len - 1)
        self.cache, self._enc_out = self._splice(
            self.cache, mini_cache, self._enc_out, mini_enc, slot, true_len)
        return logits


# ---------------------------------------------------------------------------
# Paged KV cache + chunked prefill (DESIGN.md §9)
# ---------------------------------------------------------------------------


def chunk_buckets_for(prefill_chunk: int, page_size: int) -> tuple[int, ...]:
    """Length buckets for the FINAL (partial) chunk of a prompt: power-of-two
    multiples of the page size up to the full chunk length.  One jitted
    chunk-prefill program compiles per bucket, so the compile count is
    ``len(buckets)`` regardless of how many prompts are served."""
    buckets = {prefill_chunk}
    b = page_size
    while b < prefill_chunk:
        buckets.add(b)
        b *= 2
    return tuple(sorted(buckets))


def chunk_plan(true_len: int, prefill_chunk: int,
               buckets: Sequence[int], *,
               start: int = 0) -> list[tuple[int, int, int]]:
    """Split a prompt into page-aligned chunks: full ``prefill_chunk``-sized
    chunks, then the remainder padded up to the smallest fitting bucket.
    Returns ``[(start, bucket_len, valid_in_chunk), ...]``.

    ``start`` (a chunk-aligned offset, prefix-cache hits) begins the plan at
    a later position; because full chunks are laid at multiples of
    ``prefill_chunk``, the result is exactly the suffix of the ``start=0``
    plan — the bit-exactness contract prefix sharing relies on (the final
    chunk, whose logits seed the first sampled token, is identical to the
    one a full prefill would run)."""
    if true_len <= 0:
        raise ValueError(f"true_len {true_len} must be positive")
    if not 0 <= start < true_len:
        raise ValueError(f"start {start} outside [0, {true_len})")
    if start % prefill_chunk:
        raise ValueError(f"start {start} must be chunk-aligned "
                         f"(prefill_chunk {prefill_chunk}) so the plan is a "
                         f"suffix of the full-prefill plan")
    plan = []
    while true_len - start > prefill_chunk:
        plan.append((start, prefill_chunk, prefill_chunk))
        start += prefill_chunk
    rem = true_len - start
    fitting = [b for b in buckets if b >= rem]
    if not fitting:
        raise ValueError(f"no chunk bucket fits remainder {rem} "
                         f"(buckets {tuple(buckets)})")
    plan.append((start, min(fitting), rem))
    return plan


class PagedEngine:
    """Serving engine over a paged KV cache with chunked prefill.

    Attention layers share one page pool ``(num_pages, KV, page_size, D)``
    per k/v (group-stacked like dense caches); a slot's cache is whatever
    pages the scheduler's allocator assigned it, recorded in a *host-side*
    page table ``(batch, max_pages_per_slot)`` that is passed into every
    jitted program.  Page 0 is reserved as the trash page: free (and
    mid-prefill) slots' table rows point at it, so the always-full-batch
    decode program can write their dead K/V somewhere harmless without
    masking — live slots never alias it (allocator hands out pages ≥ 1).

    Prefill is chunked: ``prefill_chunk``-sized page-aligned chunks run
    through ONE jitted chunk program per chunk-length bucket
    (``trace_count("chunk_prefill")`` = #buckets used), each attending over
    the slot's previously-written pages plus itself, so the scheduler can
    interleave live-batch decode steps between the chunks of a long prompt
    instead of stalling on it.  During a multi-chunk prefill the slot's
    LIVE table row stays on the trash page (interleaved decodes of the
    still-empty slot must not touch the real pages); the chunk program gets
    the real page row as an argument, and ``commit_slot`` installs it once
    the last chunk has run.  Surviving slots stay bit-identical under all
    of this: chunk writes land only in the inserting slot's own pages, and
    every other slot's gathered view depends only on its own table row.
    """

    def __init__(self, cfg: ModelConfig, params, *, batch: int, max_len: int,
                 page_size: int = 16, num_pages: int | None = None,
                 prefill_chunk: int = 64, donate_cache: bool = True,
                 mesh=None, attn_impl: str = "auto"):
        if cfg.family == "encdec":
            raise NotImplementedError("paged serving for encdec models "
                                      "(cross-attention buffers)")
        if prefill_chunk % page_size:
            raise ValueError(f"prefill_chunk {prefill_chunk} must be a "
                             f"multiple of page_size {page_size}")
        self.cfg, self.params = cfg, params
        self.batch, self.max_len = batch, max_len
        self.page_size = page_size
        # paged flash-decode attention impl (DESIGN.md §15), resolved once
        # here so a run can report what it ran: "auto" becomes the Pallas
        # kernel on one TPU device (when the pool is stored in the compute
        # dtype) and the gather_pages oracle elsewhere; "interpret" forces
        # the kernel body through the Pallas interpreter (tests), "ref" pins
        # the gather
        self.attn_impl = resolve_paged_impl(
            attn_impl, cfg.kv_cache_dtype or cfg.compute_dtype,
            cfg.compute_dtype, partitioned=mesh is not None and mesh.size > 1)
        self.chunk_len = prefill_chunk
        self.max_pages = -(-max_len // page_size)       # per-slot table width
        # default pool: the dense engine's footprint (batch × max_len) plus
        # the trash page — callers shrink it to oversubscribe, or keep the
        # bytes and raise ``batch`` instead (more slots, same memory)
        self.num_pages = (1 + batch * self.max_pages if num_pages is None
                          else num_pages)
        self.chunk_buckets = chunk_buckets_for(prefill_chunk, page_size)
        self._trace_counts: collections.Counter = collections.Counter()
        # host-side page table; all-zero rows = trash page (slot empty)
        self.page_table = np.zeros((batch, self.max_pages), np.int32)
        # prefix caching shares pages between slots, which only the paged
        # attention pools support: SSM layers keep per-SLOT dense state that
        # a chunk prefill rebuilds position by position — there is no page
        # of it to hand a second request
        plan = layer_plan(cfg)
        self._has_ssm = "ssm" in plan.pattern + plan.tail
        self.supports_prefix_cache = not self._has_ssm
        # chunks that started a reused slot's recurrent state from zero
        # (``chunk_prefill_step`` zeroes it at a prompt's first chunk)
        self.ssm_state_resets = 0
        self._slot_used = np.zeros((batch,), bool)
        # multi-device serving (DESIGN.md §13): with a mesh, the page pools
        # shard kv_heads->model (TP) and pages/slots->data (DP groups); the
        # three jitted programs trace inside a use_rules context so the
        # model code's logical() annotations become real constraints.  A
        # mesh of total size 1 resolves every rule to replication — the
        # single-device code path, bit for bit.
        self.mesh = mesh
        self._rules = None
        if mesh is not None:
            from .mesh import serve_rules
            self._rules = ShardingRules(mesh=mesh, rules=serve_rules())
            # weights are replicated over the mesh once, here: left on the
            # device they were made on, every jitted call would re-send them
            # to the other devices
            self.params = jax.device_put(params, NamedSharding(mesh, P()))

        def _decode(params, cache, tokens, page_table, update_mask):
            self._trace_counts["decode"] += 1
            return decode_step(cfg, params, cache, tokens, pages=page_table,
                               page_size=page_size, update_mask=update_mask,
                               paged_impl=self.attn_impl)

        def _chunk(params, cache, tokens, pages_row, slot, start, valid_len):
            self._trace_counts["chunk_prefill"] += 1
            return chunk_prefill_step(cfg, params, cache, tokens, slot=slot,
                                      start=start, valid_len=valid_len,
                                      pages_row=pages_row,
                                      page_size=page_size)

        def _copy(cache, src, dst):
            self._trace_counts["copy_page"] += 1

            def cp_block(bc, axis):
                # attention pool blocks only — SSM blocks hold per-slot
                # state, no pages to copy (cf. _page_view_block)
                if not (isinstance(bc, dict) and "self" in bc):
                    return bc

                def cp(pool):
                    blk = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=axis)
                    return jax.lax.dynamic_update_slice_in_dim(pool, blk, dst,
                                                               axis=axis)

                return {**bc,
                        "self": {k: cp(v) for k, v in bc["self"].items()}}

            return {"groups": [cp_block(bc, 1) for bc in cache["groups"]],
                    "tail": [cp_block(bc, 0) for bc in cache["tail"]],
                    "len": cache["len"]}

        donate = (1,) if donate_cache else ()
        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._chunk = jax.jit(_chunk, donate_argnums=donate)
        self._copy = jax.jit(_copy, donate_argnums=(0,) if donate_cache
                             else ())
        # device copy of the page table, refreshed only when a slot commits
        # or frees — decode steps between table changes reuse it instead of
        # paying a host->device transfer per step
        self._pt_device = None
        self.cache = None

    def trace_count(self, name: str) -> int:
        """Trace (= compiled-signature) count of program ``name``
        (chunk_prefill|decode)."""
        return self._trace_counts[name]

    def probe_device(self) -> bool:
        """Serve-layer health probe (DESIGN.md §14): one tiny op must run
        on the mesh (or the default device) and transfer back.  Returns
        False instead of raising — group failover keeps the group
        quarantined and retries on the next probe interval."""
        try:
            with self._rules_ctx():
                x = jnp.ones((2,), jnp.int32)
                return int(jax.block_until_ready(jnp.sum(x))) == 2
        except Exception:
            return False

    def _rules_ctx(self):
        """Ambient sharding rules for tracing the jitted programs — a
        nullcontext without a mesh, so the single-device path is untouched."""
        if self._rules is None:
            return contextlib.nullcontext()
        return use_rules(self.mesh, self._rules.rules)

    # -- lifecycle -------------------------------------------------------------
    def ensure_batch(self, *, enc_len: int | None = None) -> None:
        """Initialise an empty live batch (all slots free, zero lengths,
        every table row on the trash page)."""
        if self.cache is None:
            cache = init_paged_cache(self.cfg, self.batch,
                                     num_pages=self.num_pages,
                                     page_size=self.page_size,
                                     lane_dense=self.attn_impl == "kernel")
            if self._rules is not None:
                from .mesh import shard_paged_cache
                cache = shard_paged_cache(cache, self._rules)
            self.cache = cache

    @property
    def ssm_state_bytes(self) -> int:
        """Bytes of recurrent state one slot holds: the SSM layers' SSD
        state and conv tail, from the cache tree's leaves (0 where the
        model has no SSM layer).  Read off the shapes; nothing is
        allocated."""
        cache = self.cache if self.cache is not None else jax.eval_shape(
            functools.partial(init_paged_cache, self.cfg, self.batch,
                              num_pages=self.num_pages,
                              page_size=self.page_size))
        total = sum(
            leaf.size * leaf.dtype.itemsize
            for part in ("groups", "tail") for bc in cache[part]
            if isinstance(bc, dict) and "state" in bc
            for leaf in jax.tree.leaves(bc))
        return total // self.batch

    def per_device_pool_bytes(self) -> int:
        """Max attention-pool bytes resident on any one device (equals the
        total pool bytes on a single device; a TP=2 mesh halves it when
        kv_heads divides)."""
        self.ensure_batch()
        from .mesh import per_device_pool_bytes
        return per_device_pool_bytes(self.cache)

    def total_pool_bytes(self) -> int:
        """Attention page-pool bytes across the whole mesh (0 for pure-SSM
        models — their dense per-slot state is not paged)."""
        self.ensure_batch()
        total = 0
        for part in ("groups", "tail"):
            for bc in self.cache[part]:
                if isinstance(bc, dict) and "self" in bc:
                    total += sum(int(a.nbytes) for a in bc["self"].values())
        return total

    def pages_needed(self, true_len: int, max_new: int) -> int:
        """Pages a request needs to hold ``true_len`` prompt tokens plus
        ``max_new`` generated ones: the padded prefill span or the prompt +
        generation budget, whichever reaches further.

        Two reservation disciplines build on this (``ServeScheduler``):

        * ``reserve="lifetime"`` calls it with the full generation budget at
          admission — an admitted request can never hit pool exhaustion, at
          the cost of reserving pages that sit empty until decode reaches
          them;
        * ``reserve="demand"`` calls it with ``max_new=1`` (the prompt span
          plus room for the first decode write) and appends further decode
          pages lazily via :meth:`append_page`, preempting on exhaustion.
        """
        plan = chunk_plan(true_len, self.chunk_len, self.chunk_buckets)
        span = max(plan[-1][0] + plan[-1][1], true_len + max_new)
        return -(-span // self.page_size)

    # -- chunked prefill -------------------------------------------------------
    def prefill_chunk(self, slot: int, tokens_1xC, page_ids, start: int,
                      valid_in_chunk: int):
        """Run one chunk through the slot's pages (``page_ids``: the slot's
        full allocation, host list).  Returns the logits at the chunk's true
        last token — only the final chunk's are meaningful."""
        self.ensure_batch()
        ids = self._check_page_row(slot, page_ids)
        if start == 0 and self._has_ssm:
            self.ssm_state_resets += int(self._slot_used[slot])
            self._slot_used[slot] = True
        row = np.zeros((1, self.max_pages), np.int32)
        row[0, :len(ids)] = ids
        with self._rules_ctx():
            logits, self.cache = self._chunk(self.params, self.cache,
                                             tokens_1xC, row, slot, start,
                                             valid_in_chunk)
        return logits

    def _check_page_row(self, slot: int, page_ids) -> list[int]:
        """Fail fast on a bad table row: the trash page (id 0) mid-row would
        silently truncate the nonzero-prefix page count ``append_page``
        depends on (a later append would overwrite a live mapping), an
        out-of-range id would index the pool out of bounds on device, and an
        over-long row would overflow the per-slot table width."""
        ids = [int(p) for p in page_ids]
        if len(ids) > self.max_pages:
            raise ValueError(f"slot {slot}: {len(ids)} pages exceed the "
                             f"per-slot table width {self.max_pages}")
        bad = [p for p in ids if not 0 < p < self.num_pages]
        if bad:
            raise ValueError(f"slot {slot}: page id(s) {bad} outside "
                             f"(0, {self.num_pages}) — 0 is the reserved "
                             f"trash page")
        return ids

    def commit_slot(self, slot: int, page_ids) -> None:
        """Install the slot's pages into the live table — decode reads (and
        writes) go through them from the next step on."""
        ids = self._check_page_row(slot, page_ids)
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(ids)] = ids
        self.page_table[slot] = row
        self._pt_device = None

    def append_page(self, slot: int, page_id: int) -> None:
        """Reserve-on-demand decode growth: append one page to a COMMITTED
        slot's live table row, just before the decode write that crosses
        into it.  The row's current page count is its nonzero prefix —
        ``commit_slot`` writes a prefix and appends only ever extend it, and
        the allocator never hands out the trash page (id 0)."""
        if page_id <= 0:
            raise ValueError(f"page {page_id} is reserved (trash page) or "
                             f"invalid — appends take allocator pages >= 1")
        if page_id >= self.num_pages:
            raise ValueError(f"page {page_id} outside the pool "
                             f"(num_pages {self.num_pages}) — a foreign id "
                             f"would index the device pool out of bounds")
        n = int(np.count_nonzero(self.page_table[slot]))
        if n == 0:
            raise ValueError(f"slot {slot} is not committed (row on the "
                             f"trash page); append_page only grows live "
                             f"slots")
        if n >= self.max_pages:
            raise ValueError(f"slot {slot} table is full "
                             f"({self.max_pages} pages)")
        self.page_table[slot, n] = page_id
        self._pt_device = None

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write primitive: duplicate page ``src``'s K/V block into
        ``dst`` across every attention pool (one jitted program, traced
        once).  The caller (scheduler) then remaps the writing slot's table
        row from the shared original to the private copy."""
        for name, p in (("src", src), ("dst", dst)):
            if not 0 < p < self.num_pages:
                raise ValueError(f"copy_page {name} {p} outside "
                                 f"(0, {self.num_pages})")
        if src == dst:
            raise ValueError(f"copy_page onto itself (page {src})")
        self.ensure_batch()
        with self._rules_ctx():
            self.cache = self._copy(self.cache, np.int32(src), np.int32(dst))

    def remap_slot_page(self, slot: int, idx: int, page_id: int) -> None:
        """Replace ONE live table-row entry (COW remap: shared original ->
        private copy).  Only committed rows can be remapped — a mid-prefill
        slot's live row is parked on the trash page, and its real row is
        (re)installed wholesale by ``commit_slot``."""
        if not 0 < page_id < self.num_pages:
            raise ValueError(f"page {page_id} outside (0, {self.num_pages})")
        if not 0 <= idx < self.max_pages:
            raise ValueError(f"row index {idx} outside [0, {self.max_pages})")
        if self.page_table[slot, idx] == 0:
            raise ValueError(f"slot {slot} row index {idx} is not live "
                             f"(trash page) — remap only swaps existing "
                             f"mappings")
        self.page_table[slot, idx] = page_id
        self._pt_device = None

    def free_slot(self, slot: int) -> None:
        """Retire (or preempt) the slot: its table row points back at the
        trash page, so interleaved decode writes of the parked slot land
        somewhere harmless.  The pages themselves go back to the
        scheduler's allocator — preempt-safe because reads of every other
        slot depend only on that slot's own table row."""
        self.page_table[slot] = 0
        self._pt_device = None

    def insert(self, slot: int, tokens, *, true_len: int | None = None,
               page_ids=None, max_new: int = 0):
        """Convenience one-call insert: run every chunk back-to-back (no
        decode interleaving — the scheduler drives chunks itself for that)
        and commit the pages.  ``tokens``: (S,) or (1, S) prompt."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        true_len = len(toks) if true_len is None else int(true_len)
        if page_ids is None:
            raise ValueError("insert() needs the slot's allocated page_ids")
        need = self.pages_needed(true_len, max_new)
        if len(page_ids) < need:
            raise ValueError(f"slot {slot} got {len(page_ids)} pages, needs "
                             f"{need}")
        logits = None
        for start, blen, vlen in chunk_plan(true_len, self.chunk_len,
                                            self.chunk_buckets):
            ck = np.zeros((1, blen), np.int32)
            ck[0, :vlen] = toks[start:start + vlen]
            logits = self.prefill_chunk(slot, jnp.asarray(ck), page_ids,
                                        start, vlen)
        self.commit_slot(slot, page_ids)
        return logits

    # -- decode ----------------------------------------------------------------
    def decode(self, tokens, live_mask=None):
        """tokens: (batch, 1) — one step for every slot, page-table reads
        and writes.  ``live_mask`` (batch,) bool: slots whose per-slot SSM
        state may advance — mid-prefill slots must be masked out, or the
        interleaved decode would corrupt the state their next chunk
        continues from (their attention K/V needs no mask: the live page
        table parks them on the trash page).  Defaults to all-live."""
        self.ensure_batch()
        if self._pt_device is None:
            if self._rules is not None:
                spec = self._rules.spec_for(["slots", None],
                                            self.page_table.shape)
                self._pt_device = jax.device_put(
                    self.page_table, NamedSharding(self.mesh, spec))
            else:
                self._pt_device = jnp.asarray(self.page_table)
        if live_mask is None:
            live_mask = np.ones((self.batch,), bool)
        with self._rules_ctx():
            logits, self.cache = self._decode(self.params, self.cache, tokens,
                                              self._pt_device,
                                              np.asarray(live_mask, bool))
        return logits

    _sample = Engine._sample


def _splice_batch(full, one, slot):
    """Insert ``one`` (batch=1 leaf) into ``full`` at batch index ``slot``.
    Cache leaves have batch as the first axis after the optional group axis."""
    if full.ndim == one.ndim and full.shape == one.shape:
        return full  # scalar bookkeeping leaves
    # group-stacked leaves: (G, B, ...) vs (G, 1, ...)
    if full.ndim >= 2 and one.shape[0] == full.shape[0] and one.shape[1] == 1:
        return jax.lax.dynamic_update_slice_in_dim(full, one, slot, axis=1)
    if one.shape[0] == 1:
        return jax.lax.dynamic_update_slice_in_dim(full, one, slot, axis=0)
    return full
