"""Model composition: every assigned architecture as a pattern-scanned stack.

A model is ``(pattern, n_groups, tail)``: the *pattern* is a short list of
block kinds (e.g. gemma3's ``5×local + 1×global``), scanned ``n_groups``
times with stacked parameters, plus an unscanned *tail* (remainder layers).
This keeps the compiled HLO small (one pattern body) while allowing
heterogeneous stacks — and gives the HLO analyzer a single while-loop whose
trip count is ``n_groups`` (DESIGN.md §5).

Block kinds:
  dense   — attention + gated MLP            (qwen2, deepseek, llama3, chameleon)
  local   — sliding-window attention + MLP   (gemma3 local layers)
  global  — full attention + MLP             (gemma3 global layers)
  moe     — attention + mixture-of-experts   (mixtral [SWA], qwen2-moe)
  ssm     — Mamba-2 mixer [+ gated MLP]      (mamba2, zamba2 backbone;
            granite-4.0-h with ``ssm_mlp``)
  shared  — zamba2's *shared* attention+MLP block (one parameter set,
            invoked at every occurrence)
  enc/dec — whisper encoder / decoder (cross-attention) blocks
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.parallel.sharding import current_rules, logical
from .attention import KVCache, attention, gather_pages, init_attention
from .config import ModelConfig, round_up
from .layers import (apply_mlp, apply_norm, embed, init_embedding, init_mlp,
                     init_norm, truncated_normal, unembed)
from .moe import apply_moe, init_moe
from .ssm import SSMCache, apply_mamba2, init_mamba2, mamba2_decode_step

__all__ = [
    "layer_plan", "init_params", "forward", "loss_fn", "init_cache",
    "init_paged_cache", "prefill", "decode_step", "chunk_prefill_step",
    "param_count",
]


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    pattern: tuple[str, ...]
    n_groups: int
    tail: tuple[str, ...]
    enc_pattern: tuple[str, ...] = ()
    enc_groups: int = 0

    @property
    def scan_trips(self) -> int:
        return self.n_groups


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    L = cfg.n_layers
    if cfg.layer_pattern:
        # a period of block kinds (granite-4.0-h: 5 ssm, 1 dense, 4 ssm)
        k = len(cfg.layer_pattern)
        g, r = divmod(L, k)
        return LayerPlan(pattern=tuple(cfg.layer_pattern), n_groups=g,
                         tail=tuple(cfg.layer_pattern[:r]))
    if cfg.family == "encdec":
        return LayerPlan(pattern=("dec",), n_groups=L, tail=(),
                         enc_pattern=("enc",), enc_groups=cfg.n_encoder_layers)
    if cfg.family == "ssm":
        return LayerPlan(pattern=("ssm",), n_groups=L, tail=())
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        g, r = divmod(L, k)
        return LayerPlan(pattern=("ssm",) * k + ("shared",), n_groups=g,
                         tail=("ssm",) * r)
    if cfg.local_global_ratio:
        k = cfg.local_global_ratio + 1
        g, r = divmod(L, k)
        return LayerPlan(pattern=("local",) * cfg.local_global_ratio + ("global",),
                         n_groups=g, tail=("local",) * r)
    if cfg.is_moe:
        return LayerPlan(pattern=("moe",), n_groups=L, tail=())
    return LayerPlan(pattern=("dense",), n_groups=L, tail=())


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _has_attn(kind: str) -> bool:
    return kind in ("dense", "local", "global", "moe", "shared", "enc", "dec")


def init_block(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 6)
    if kind == "ssm":
        p = {"ln1": init_norm(cfg), "mixer": init_mamba2(ks[0], cfg)}
        if cfg.ssm_mlp:
            p["ln2"] = init_norm(cfg)
            p["mlp"] = init_mlp(ks[1], cfg)
        return p
    p = {"ln1": init_norm(cfg), "attn": init_attention(ks[0], cfg),
         "ln2": init_norm(cfg)}
    if kind == "moe":
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg)
    if kind == "dec":
        p["ln_cross"] = init_norm(cfg)
        p["cross"] = init_attention(ks[2], cfg, cross=True)
    return p


def _window_for(cfg: ModelConfig, kind: str) -> int | None:
    if kind == "local":
        return cfg.local_window
    if kind in ("dense", "moe", "global", "shared"):
        return cfg.sliding_window if kind in ("dense", "moe") else None
    return None


def _branch(cfg: ModelConfig, out):
    """A residual branch's output, scaled by ``residual_multiplier`` (no op
    at 1)."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return out


def apply_block(cfg: ModelConfig, kind: str, p: dict, x, *, positions,
                enc_out=None, cache=None, cache_len=None,
                impl: str = "auto", paged_impl: str = "ref",
                chunk_continue: bool = False, valid_len=None):
    """Returns (x, new_cache, aux_loss).

    ``chunk_continue``: S > 1 against a LIVE cache — chunked prefill: the
    block continues from the cache (attention over prior entries + itself;
    SSM from the cached conv tail + state) instead of starting fresh.
    ``valid_len``: true (unpadded) length of a bucketed prompt chunk.
    Paged serving engines pass attention caches either as pre-gathered
    per-slot VIEWS in the dense layout (reference path, see
    ``decode_step``) or — on the paged-kernel decode path — as the raw
    pools of every layer plus the page table and the layer's index under
    ``k_pool``/``v_pool``/``pages``/``layer``, which the attention layer
    hands to the paged flash-decode kernel.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    if kind == "ssm":
        h = apply_norm(cfg, p["ln1"], x)
        if cache is not None and x.shape[1] == 1:
            out, new_cache = mamba2_decode_step(cfg, p["mixer"], h, cache)
        elif cache is not None and chunk_continue:
            # chunked prefill: continue the conv + SSD scan from the cache
            out, new_cache = apply_mamba2(cfg, p["mixer"], h, cache=cache,
                                          valid_len=valid_len,
                                          return_cache=True)
        elif cache is not None:
            # batched prefill: run the chunked scan, emit a decode cache
            out, new_cache = apply_mamba2(cfg, p["mixer"], h,
                                          valid_len=valid_len,
                                          return_cache=True)
        else:
            out = apply_mamba2(cfg, p["mixer"], h)
        x = x + _branch(cfg, out)
        if "mlp" in p:
            h = apply_norm(cfg, p["ln2"], x)
            x = x + _branch(cfg, apply_mlp(cfg, p["mlp"], h))
        return x, new_cache, aux

    causal = kind != "enc"
    window = _window_for(cfg, kind)
    h = apply_norm(cfg, p["ln1"], x)
    sa_cache = cache.get("self") if cache is not None else None
    out, new_sa = attention(cfg, p["attn"], h, positions=positions,
                            causal=causal, window=window, cache=sa_cache,
                            cache_len=cache_len, impl=impl,
                            paged_impl=paged_impl,
                            chunk_continue=chunk_continue,
                            rope=cfg.use_rope and kind != "enc" and kind != "dec")
    x = x + logical(_branch(cfg, out), "batch", "seq", "embed")

    if kind == "dec" and enc_out is not None:
        h = apply_norm(cfg, p["ln_cross"], x)
        enc_len = enc_out.shape[1]
        # cross K/V recomputed per call (cacheing them is a serving-engine
        # optimisation; see repro/serve/engine.py)
        out, _ = attention(cfg, p["cross"], h, kv_x=enc_out,
                           positions=positions,
                           kv_positions=jnp.arange(enc_len),
                           causal=False, rope=False)
        x = x + out

    h = apply_norm(cfg, p["ln2"], x)
    if kind == "moe":
        out, aux = apply_moe(cfg, p["moe"], h)
    else:
        out = apply_mlp(cfg, p["mlp"], h)
    x = x + logical(_branch(cfg, out), "batch", "seq", "embed")

    if cache is not None and kind != "ssm":
        # return ONLY the update (deltas) — returning the old cache slices
        # would double-buffer them through the scan ys (§Perf)
        new_cache = {"self": new_sa} if new_sa is not None else {}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    plan = layer_plan(cfg)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {"embed": init_embedding(keys[0], cfg)}

    def stacked(key, kind, n):
        ks = jax.random.split(key, n)
        return jax.vmap(lambda k: init_block(k, cfg, kind))(ks)

    # scanned groups: one stacked param tree per pattern position
    gkeys = jax.random.split(keys[1], max(len(plan.pattern), 1))
    params["groups"] = [
        stacked(gkeys[i], kind, plan.n_groups) if kind != "shared" else {}
        for i, kind in enumerate(plan.pattern)
    ]
    if "shared" in plan.pattern:
        params["shared"] = init_block(keys[2], cfg, "shared")
    tkeys = jax.random.split(keys[3], max(len(plan.tail), 1))
    params["tail"] = [init_block(tkeys[i], cfg, kind)
                      for i, kind in enumerate(plan.tail)]
    params["norm_f"] = init_norm(cfg)

    if cfg.family == "encdec":
        ekeys = jax.random.split(keys[4], 3)
        params["enc_groups"] = [stacked(ekeys[0], "enc", plan.enc_groups)]
        params["enc_norm_f"] = init_norm(cfg)
        params["enc_pos"] = truncated_normal(
            ekeys[1], (cfg.max_seq, cfg.d_model), 0.02,
            jnp.dtype(cfg.param_dtype))
        params["dec_pos"] = truncated_normal(
            ekeys[2], (cfg.max_seq, cfg.d_model), 0.02,
            jnp.dtype(cfg.param_dtype))
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "save_dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def _constrain_block_params(p):
    """Re-assert the (FSDP/TP) sharding of per-layer params sliced out of the
    scan xs.  Without this GSPMD may all-gather the whole stacked weight
    array outside the loop ("wide" while), keeping every layer's gathered
    weights live simultaneously — §Perf iteration H7."""
    if current_rules() is None or p is None:
        return p
    from repro.parallel.partition import axes_for_path
    flat, treedef = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for path, leaf in flat:
        keys = tuple(str(getattr(x, "key", getattr(x, "idx", x))) for x in path)
        axes = axes_for_path(keys, getattr(leaf, "ndim", 0))
        out.append(logical(leaf, *axes) if hasattr(leaf, "ndim") else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _run_stack(cfg: ModelConfig, plan_pattern, groups, tail_kinds, tail,
               shared, x, positions, *, enc_out=None, impl="auto"):
    """Scan the pattern over groups, then the tail. Returns (x, aux)."""

    def group_body(carry, gparams):
        h, aux = carry
        for i, kind in enumerate(plan_pattern):
            # (H7 constraint on sliced params is applied only on decode
            # paths; in training it triggered GSPMD replicate-then-partition
            # weight all-reduces — §Perf)
            p = shared if kind == "shared" else gparams[i]
            h, _, a = apply_block(cfg, kind, p, h, positions=positions,
                                  enc_out=enc_out, impl=impl)
            aux = aux + a
        h = logical(h, "batch", "seq", "embed")
        return (h, aux), None

    aux0 = jnp.zeros((), jnp.float32)
    if groups and jax.tree.leaves(groups):
        n_groups = jax.tree.leaves(groups)[0].shape[0]
        if cfg.scan_layers and n_groups > 1:
            body = _remat(cfg, group_body)
            (x, aux0), _ = jax.lax.scan(body, (x, aux0), tuple(groups))
        else:
            for g in range(n_groups):
                gp = jax.tree.map(lambda t: t[g], tuple(groups))
                (x, aux0), _ = _remat(cfg, group_body)((x, aux0), gp)
    for i, kind in enumerate(tail_kinds):
        x, _, a = apply_block(cfg, kind, tail[i], x, positions=positions,
                              enc_out=enc_out, impl=impl)
        aux0 = aux0 + a
    return x, aux0


def forward(cfg: ModelConfig, params: dict, *, tokens=None, embeds=None,
            positions=None, enc_embeds=None, impl: str = "auto"):
    """Full-sequence forward (train / prefill).  Returns (logits, aux).

    ``tokens``: (B, S) int32 — LM input.
    ``embeds``: (B, S, d) — precomputed embeddings (stub modality frontend).
    ``enc_embeds``: (B, T, d) — encoder input for encdec (whisper frames).
    """
    plan = layer_plan(cfg)
    if embeds is None:
        x = embed(cfg, params["embed"], tokens)
    else:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    x = logical(x, "batch", "seq", "embed")

    enc_out = None
    if cfg.family == "encdec":
        assert enc_embeds is not None, "encdec model needs enc_embeds"
        e = enc_embeds.astype(jnp.dtype(cfg.compute_dtype))
        e = e + params["enc_pos"][: e.shape[1]].astype(e.dtype)[None]
        e = logical(e, "batch", "seq", "embed")
        e, _ = _run_stack(cfg, plan.enc_pattern, tuple(params["enc_groups"]),
                          (), (), None, e, jnp.arange(e.shape[1]), impl=impl)
        enc_out = apply_norm(cfg, params["enc_norm_f"], e)
        x = x + params["dec_pos"][positions].astype(x.dtype)

    x, aux = _run_stack(cfg, plan.pattern, tuple(params["groups"]),
                        plan.tail, params["tail"], params.get("shared"),
                        x, positions, enc_out=enc_out, impl=impl)
    x = apply_norm(cfg, params["norm_f"], x)
    logits = unembed(cfg, params["embed"], x)
    logits = logical(logits, "batch", None, "vocab")
    return logits, aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, *, impl="auto"):
    """Next-token cross-entropy (+ MoE aux).  batch: tokens, labels, mask
    (+ enc_embeds / embeds for stub-frontend families)."""
    logits, aux = forward(cfg, params,
                          tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"),
                          enc_embeds=batch.get("enc_embeds"),
                          impl=impl)
    labels = batch["labels"]
    mask = batch.get("mask")
    logits = logits.astype(jnp.float32)
    # mask out vocab padding
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e9, jnp.float32)
        logits = logits.at[..., cfg.vocab_size:].set(pad)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + cfg.router_aux_weight * aux
    return total, {"loss": loss, "aux": aux,
                   "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      dtype, enc_len: int = 0):
    if kind == "ssm":
        return SSMCache.init(cfg, batch)
    return {"self": KVCache.init(cfg, batch, max_len, dtype)}


def _is_delta(upd) -> bool:
    return isinstance(upd, dict) and "k_delta" in upd


def _write_kv(buf, delta, pos, *, batch_axis: int):
    """Write a K/V delta into a cache buffer at the token position.

    ``pos`` scalar — one dynamic-update-slice for the whole batch (training /
    uniform decode).  ``pos`` (B,) — per-slot positions (continuous batching:
    slots inserted at different times sit at different lengths), written as a
    vmap over the batch axis, one slice per slot.
    """
    idx = jnp.asarray(pos)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice(
            buf, delta, (0,) * (buf.ndim - 2) + (idx, 0))
    per_row = lambda c, d, i: jax.lax.dynamic_update_slice(
        c, d, (0,) * (c.ndim - 2) + (i, 0))
    return jax.vmap(per_row, in_axes=(batch_axis, batch_axis, 0),
                    out_axes=batch_axis)(buf, delta, idx)


def _pad_lanes(x, width: int):
    """``x`` padded with zeros on its last axis to ``width``: the head size
    a lane-dense page pool stores (``init_paged_cache``)."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _write_kv_paged(pool, delta, pos, pages, page_size, *, stacked: bool):
    """Scatter one decode step's K/V delta into a shared page pool.

    pool: (G, P, KV, ps, D) stacked or (P, KV, ps, D) unstacked; delta the
    matching (…, B, KV, 1, D); pos (B,) per-slot token positions; pages
    (B, n_pages) page table.  Logical position ``pos`` of slot ``b`` lives
    in physical page ``pages[b, pos // ps]`` at offset ``pos % ps``.  Slots
    never share pages (allocator invariant), so the scatter indices are
    unique across live slots; free slots all map to the reserved trash page,
    whose contents are never validly read.
    """
    pos = jnp.broadcast_to(jnp.asarray(pos), (pages.shape[0],))
    B = pages.shape[0]
    pid = jnp.take_along_axis(pages, (pos // page_size)[:, None],
                              axis=1)[:, 0]                       # (B,)
    off = pos % page_size
    val = _pad_lanes(delta.astype(pool.dtype), pool.shape[-1])
    if stacked:
        # (G, B, KV, 1, D) -> (B, G, KV, D); advanced indices (pid, off)
        # are separated by slices, so the batch axis moves to the front
        val = jnp.moveaxis(val[:, :, :, 0, :], 1, 0)
        out = pool.at[:, pid, :, off, :].set(val)
        return logical(out, None, "pages", "kv_heads", None, None)
    out = pool.at[pid, :, off, :].set(val[:, :, 0, :])
    return logical(out, "pages", "kv_heads", None, None)


def _write_kv_pages(pool, delta, pos, pages, page_size, *, stacked: bool):
    """``_write_kv_paged`` by whole pages, in the pool's own layout.

    Each slot's current page is gathered, the new token's row set in it,
    and the pages scattered back whole at their ids: the scatter indexes
    the page axis alone, as the chunk write does, so it runs in place in
    the pool's layout, where a row scatter indexing the page and the
    in-page offset makes XLA convert the whole pool to another layout and
    back.  Live slots never share the page they write (a shared partial
    page is copied first), so the pages scattered are distinct, but for
    the trash page, where every free slot writes.
    """
    pos = jnp.broadcast_to(jnp.asarray(pos), (pages.shape[0],))
    pid = jnp.take_along_axis(pages, (pos // page_size)[:, None],
                              axis=1)[:, 0]                       # (B,)
    off = pos % page_size
    val = _pad_lanes(delta.astype(pool.dtype), pool.shape[-1])
    if not stacked:
        pool, val = pool[None], val[None]
    page = pool[:, pid]                                   # (G, B, KV, ps, D)
    row = jnp.arange(page_size)[None, :] == off[:, None]  # (B, ps)
    page = jnp.where(row[None, :, None, :, None], val, page)
    out = pool.at[:, pid].set(page)
    if not stacked:
        return logical(out[0], "pages", "kv_heads", None, None)
    return logical(out, None, "pages", "kv_heads", None, None)


def _page_view_block(block_cache, pages):
    """Replace a block's attention page pools by per-slot gathered views in
    the dense (…, B, KV, T, D) layout; non-attention caches (SSM state) and
    dense caches pass through untouched."""
    if pages is None or not (isinstance(block_cache, dict)
                             and "self" in block_cache):
        return block_cache
    return {**block_cache,
            "self": {kk: gather_pages(block_cache["self"][kk], pages)
                     for kk in ("k", "v")}}


def _page_views(block_caches, pages):
    return tuple(_page_view_block(bc, pages) for bc in block_caches)


def _attn_pools(block_cache):
    """A block cache's attention pools ``{"k", "v"}``, or None for a block
    without attention (SSM state)."""
    if isinstance(block_cache, dict) and "self" in block_cache:
        return block_cache["self"]
    return None


def _page_pool_view_block(block_cache, pages, pools, layer):
    """Kernel-path counterpart of ``_page_view_block``: instead of
    materialising the gathered dense view, pass the pools of every layer
    through whole, with the page table and the layer's index alongside
    (``k_pool``/``v_pool``/``pages``/``layer``), so the paged flash-decode
    kernel copies that layer's pages in place.  ``pools`` None (a block
    without attention) passes ``block_cache`` through."""
    if pools is None:
        return block_cache
    return {**block_cache, "self": {"k_pool": pools["k"], "v_pool": pools["v"],
                                    "pages": pages, "layer": layer}}


def _apply_cache_update(old_layer_cache, upd, pos, *, pages=None,
                        page_size=None, update_mask=None, write_paged):
    """Apply a block's cache update to an UNSTACKED layer cache.
    ``write_paged``: the paged K/V write (``_write_kv_paged`` or
    ``_write_kv_pages``)."""
    if upd is None:
        return old_layer_cache
    out = {}
    for key, val in upd.items():
        if key == "self" and _is_delta(val):
            if pages is not None:
                out["self"] = {
                    kk: write_paged(old_layer_cache["self"][kk],
                                    val[f"{kk}_delta"], pos, pages,
                                    page_size, stacked=False)
                    for kk in ("k", "v")}
            else:
                out["self"] = {
                    kk: _write_kv(old_layer_cache["self"][kk],
                                  val[f"{kk}_delta"], pos, batch_axis=0)
                    for kk in ("k", "v")}
        else:
            val = val.astype(old_layer_cache[key].dtype)
            if update_mask is not None:
                m = update_mask.reshape((-1,) + (1,) * (val.ndim - 1))
                val = jnp.where(m, val, old_layer_cache[key])
            out[key] = val
    return out


def _apply_stacked_updates(stacked, updates, pos, *, pages=None,
                           page_size=None, update_mask=None, write_paged):
    """Apply scan-collected per-layer updates to a stacked cache.

    KV deltas (G,B,KV,S,D) are written with ONE dynamic-update-slice at the
    token position (or one per slot for per-slot ``pos`` vectors; one
    scatter through the page table for paged pools); SSM states come out of
    the scan already whole, stacked — they simply replace the old buffers.

    ``update_mask`` (B,) bool: slots whose NON-delta state (SSM conv tail +
    SSD state) may advance.  Attention K/V of masked-out slots is already
    harmless (paged decode writes them to the trash page), but SSM state is
    a dense per-slot buffer with no page indirection — a mid-prefill slot's
    carried state must not be advanced by interleaved decode steps of the
    live batch (DESIGN.md §9).  ``write_paged`` as in
    ``_apply_cache_update``."""
    if updates is None:
        return stacked
    new = dict(stacked)
    for key, val in updates.items():
        if key == "self" and _is_delta(val):
            if pages is not None:
                new["self"] = {
                    kk: write_paged(stacked["self"][kk],
                                    val[f"{kk}_delta"], pos, pages,
                                    page_size, stacked=True)
                    for kk in ("k", "v")}
            else:
                new["self"] = {
                    kk: _write_kv(stacked["self"][kk],
                                  val[f"{kk}_delta"].astype(stacked["self"][kk].dtype),
                                  pos, batch_axis=1)
                    for kk in ("k", "v")}
        else:
            val = val.astype(stacked[key].dtype)
            if update_mask is not None:
                # stacked leaves are (G, B, ...): batch is axis 1
                m = update_mask.reshape((1, -1) + (1,) * (val.ndim - 2))
                val = jnp.where(m, val, stacked[key])
            new[key] = val
    return new


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, enc_len: int = 0):
    plan = layer_plan(cfg)
    dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)

    def stacked_cache(kind):
        one = lambda: _init_block_cache(cfg, kind, batch, max_len, dtype, enc_len)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (plan.n_groups,) + x.shape).copy()
            if plan.n_groups > 1 else x[None], one())

    cache = {
        "groups": [stacked_cache(kind) for kind in plan.pattern],
        "tail": [_init_block_cache(cfg, kind, batch, max_len, dtype, enc_len)
                 for kind in plan.tail],
        "len": jnp.zeros((), jnp.int32),
    }
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, *, num_pages: int,
                     page_size: int, enc_len: int = 0,
                     lane_dense: bool = False):
    """Paged serving cache: attention layers hold ONE shared page pool
    (P, KV, page_size, D) per k/v instead of per-slot (B, KV, max_len, D)
    buffers — memory scales with pages in use, not batch × worst-case
    request.  SSM states are O(1) per slot and stay dense (B, …).  The
    page table mapping slots to pool pages lives host-side in the serving
    engine and is passed into each jitted program (DESIGN.md §9).

    ``lane_dense``: store D padded with zeros to whole 128-lane tiles, as
    the paged kernel's page copies need (its pages must span whole tiles
    of the layout it reads the pool in); a head size of 64 so takes twice
    the pool's memory.  Writes pad, reads slice back to the head size."""
    plan = layer_plan(cfg)
    dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
    D = round_up(cfg.hd, 128) if lane_dense else cfg.hd
    if cfg.family == "encdec":
        raise NotImplementedError("paged KV for encdec (cross-attention "
                                  "buffers) is not implemented")

    def block_cache(kind):
        if kind == "ssm":
            return SSMCache.init(cfg, batch)
        return {"self": {
            "k": jnp.zeros((num_pages, cfg.n_kv_heads, page_size, D), dtype),
            "v": jnp.zeros((num_pages, cfg.n_kv_heads, page_size, D), dtype),
        }}

    def stacked_cache(kind):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (plan.n_groups,) + x.shape).copy()
            if plan.n_groups > 1 else x[None], block_cache(kind))

    return {
        "groups": [stacked_cache(kind) for kind in plan.pattern],
        "tail": [block_cache(kind) for kind in plan.tail],
        "len": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(cfg: ModelConfig, params: dict, cache: dict, tokens, *,
                enc_out=None, embeds=None, impl: str = "auto",
                pages=None, page_size: int | None = None, valid_len=None,
                update_mask=None, paged_impl: str = "ref"):
    """One cache-extending step.  tokens: (B, S) int32 (or embeds (B,S,d));
    S == 1 is decode, S > 1 is batched prefill (cache must be fresh).
    ``pages``/``page_size``: the cache's attention buffers are shared page
    pools; reads gather per-slot views through the page table, writes
    scatter through it.  ``paged_impl``: resolved through
    ``resolve_paged_impl`` — on ``kernel``/``interpret`` the S == 1 decode
    path skips the gather entirely: the paged flash-decode kernel reads
    each layer's pages in place through the page table, and the new K/V
    is written by whole pages (DESIGN.md §15); ``ref``
    (and ``auto`` off-TPU) keeps ``gather_pages`` as the oracle path.
    ``valid_len``: true prompt length of a bucketed
    (right-padded) prefill — masks SSM state updates past the true end.
    ``update_mask`` (B,) bool: freeze the per-slot SSM state of masked-out
    slots (mid-prefill slots under chunk interleaving).
    Returns (logits (B, S, V), new_cache)."""
    plan = layer_plan(cfg)
    if embeds is None:
        x = embed(cfg, params["embed"], tokens)
    else:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    S = x.shape[1]
    pos = jnp.asarray(cache["len"])
    # ``len`` may be a scalar (uniform batch) or (B,) vector (per-slot
    # lengths under continuous batching — slots inserted at different times
    # sit at different positions).  Vector lengths are decode-only: batched
    # prefill always starts from a fresh (scalar, zero-length) cache.
    if pos.ndim == 1 and S > 1:
        raise ValueError("per-slot cache lengths only support single-token "
                         "decode (S == 1); prefill from a fresh cache")
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)   # (B, S)
    else:
        positions = pos + jnp.arange(S, dtype=jnp.int32)            # (S,)
    if cfg.family == "encdec":
        pe = params["dec_pos"][positions]
        x = x + (pe if positions.ndim > 1 else pe[None]).astype(x.dtype)

    # Cache-update architecture (§Perf iterations 2-8): during one step the
    # KV cache is READ-ONLY — the new token's contribution enters attention
    # through a log-sum-exp self term — so the stacked caches are scanned as
    # read-only xs (no while-carry copy hazards), per-layer updates come out
    # as small delta ys, and ONE batched dynamic-update-slice per cache
    # applies them afterwards into the donated input buffers.
    from repro.kernels.paged_attention import resolve_paged_impl
    use_paged_kernel = (pages is not None and S == 1
                        and resolve_paged_impl(
                            paged_impl, cfg.kv_cache_dtype or cfg.compute_dtype,
                            cfg.compute_dtype) != "ref")

    groups = tuple(params["groups"])
    gcaches = tuple(cache["groups"])
    # paged: gather each slot's pages into dense-layout K/V views ONCE per
    # pattern (outside the layer scan — the stacked gather covers every
    # group), so the blocks read a view indistinguishable from a dense
    # cache; writes go through the page table into the pools afterwards.
    # On the paged-kernel decode path no view is materialised at all: the
    # stacked pools stay out of the scan's xs (a layer's slice handed to
    # the kernel would be copied out whole), the body closes over them,
    # and the kernel copies the pages of the layer the xs index names.
    # That path writes whole pages, in the pools' own layout; the gather
    # path, which also runs sharded over a mesh, keeps the row scatter.
    if use_paged_kernel:
        write_paged = _write_kv_pages
        pools = tuple(_attn_pools(bc) for bc in gcaches)
        read_gcaches = tuple(
            bc if pool is None
            else {k: v for k, v in bc.items() if k != "self"}
            for bc, pool in zip(gcaches, pools))
        read_tail = [_page_pool_view_block(bc, pages, _attn_pools(bc), 0)
                     for bc in cache["tail"]]
    else:
        write_paged = _write_kv_paged
        pools = (None,) * len(gcaches)
        read_gcaches = _page_views(gcaches, pages)
        read_tail = [_page_view_block(bc, pages) for bc in cache["tail"]]

    def group_body(carry, xs):
        h = carry
        gparams, gcache, layer = xs
        updates = []
        for i, kind in enumerate(plan.pattern):
            p = (params.get("shared") if kind == "shared"
                 else _constrain_block_params(gparams[i]))
            bc = _page_pool_view_block(gcache[i], pages, pools[i], layer)
            h, nc, _ = apply_block(cfg, kind, p, h, positions=positions,
                                   enc_out=enc_out, cache=bc,
                                   cache_len=pos, impl=impl,
                                   paged_impl=paged_impl,
                                   valid_len=valid_len)
            updates.append(nc)
        return h, tuple(updates)

    if jax.tree.leaves(groups):
        n_groups = jax.tree.leaves(groups)[0].shape[0]
        if cfg.scan_layers and n_groups > 1:
            x, updates = jax.lax.scan(
                group_body, x,
                (groups, read_gcaches, jnp.arange(n_groups, dtype=jnp.int32)))
        else:
            outs = []
            for g in range(n_groups):
                gp = jax.tree.map(lambda t: t[g], groups)
                gc = jax.tree.map(lambda t: t[g], read_gcaches)
                x, upd = group_body(x, (gp, gc, g))
                outs.append(upd)
            updates = jax.tree.map(lambda *ts: jnp.stack(ts), *outs) \
                if outs else None
        new_gcaches = tuple(
            _apply_stacked_updates(gcaches[i], updates[i], pos,
                                   pages=pages, page_size=page_size,
                                   update_mask=update_mask,
                                   write_paged=write_paged)
            for i in range(len(plan.pattern)))
    else:
        new_gcaches = gcaches

    new_tail = []
    for i, kind in enumerate(plan.tail):
        x, nc, _ = apply_block(cfg, kind, params["tail"][i], x,
                               positions=positions, enc_out=enc_out,
                               cache=read_tail[i], cache_len=pos,
                               impl=impl, paged_impl=paged_impl,
                               valid_len=valid_len)
        new_tail.append(_apply_cache_update(cache["tail"][i], nc, pos,
                                            pages=pages, page_size=page_size,
                                            update_mask=update_mask,
                                            write_paged=write_paged))

    x = apply_norm(cfg, params["norm_f"], x)
    logits = unembed(cfg, params["embed"], x)
    logits = logical(logits, "batch", None, "vocab")
    new_cache = {"groups": list(new_gcaches), "tail": new_tail,
                 "len": pos + S}
    return logits, new_cache


def _write_kv_chunk_paged(pool, delta, start, pages_1d, page_size, *,
                          stacked: bool):
    """Write a whole prefill chunk's K/V into a slot's pages.

    Chunks are page-aligned by construction (``start`` and the chunk length
    are multiples of ``page_size``), so a chunk of C tokens is exactly
    C / page_size whole pages: reshape the delta into pages and scatter them
    at the slot's physical page ids — one scatter per chunk, not per token.
    """
    C = delta.shape[-2]
    n = C // page_size
    delta = _pad_lanes(delta, pool.shape[-1])
    pids = jax.lax.dynamic_slice_in_dim(pages_1d, start // page_size, n)
    if stacked:
        G, _, KV, _, D = delta.shape
        val = delta[:, 0].reshape(G, KV, n, page_size, D).swapaxes(1, 2)
        out = pool.at[:, pids].set(val.astype(pool.dtype))
        return logical(out, None, "pages", "kv_heads", None, None)
    _, KV, _, D = delta.shape
    val = delta[0].reshape(KV, n, page_size, D).swapaxes(0, 1)
    out = pool.at[pids].set(val.astype(pool.dtype))
    return logical(out, "pages", "kv_heads", None, None)


def chunk_prefill_step(cfg: ModelConfig, params: dict, cache: dict, tokens, *,
                       slot, start, valid_len, pages_row=None,
                       page_size: int | None = None, impl: str = "auto"):
    """One prompt chunk of a chunked prefill into batch slot ``slot``.

    tokens: (1, C) — the chunk, right-padded to its bucket; ``start`` is the
    chunk's first logical position, ``valid_len`` the true (unpadded) token
    count in this chunk.  The chunk attends over the slot's already-written
    cache (positions < start) plus itself, and SSM layers continue from the
    slot's cached conv tail + state — so N chunks produce exactly the state
    one full prefill would.  Attention K/V go through ``pages_row`` (the
    slot's page-table row, (1, n_pages)) into the shared pool; SSM state is
    sliced out of / written back into the slot's row of the dense per-slot
    buffers.  Padding past ``valid_len`` writes garbage K/V into the slot's
    own pages (positions ≥ the true length are never valid reads and are
    overwritten by decode) and is masked out of SSM state updates.

    Returns (last_logits (1, 1, V) at the true last chunk token, new_cache).
    The slot's cache ``len`` is set to ``start + valid_len`` — re-asserted
    every chunk, so decode steps interleaved between chunks (which bump
    every slot's length) cannot drift a mid-prefill slot.
    """
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill for encdec models")
    plan = layer_plan(cfg)
    x = embed(cfg, params["embed"], tokens)
    C = x.shape[1]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    x = logical(x, "batch", "seq", "embed")

    def slot_row(tree):
        return jax.tree.map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, slot, 1, axis=0), tree)

    def block_step(kind, p, h, bcache):
        """Run one block on the chunk; returns (h, update) where ``update``
        is an SSM 1-row cache or an attention K/V delta.  ``bcache`` holds
        gathered page VIEWS for attention kinds (reads only — writes go to
        the pools in ``apply_update``) and full per-slot buffers for SSM."""
        if kind == "ssm":
            # FIRST chunk (start == 0): the slot's dense SSM buffers still
            # hold the previous occupant's state — there is no splice step
            # in the paged engine to replace them, so continue from the
            # fresh-prefill zeros instead (attention needs no equivalent:
            # its first chunk skips the cache read behind a lax.cond)
            c = jax.tree.map(
                lambda t: jnp.where(jnp.asarray(start) > 0, t,
                                    jnp.zeros_like(t)),
                slot_row(bcache))
        else:
            c = bcache
        return apply_block(cfg, kind, p, h, positions=positions, cache=c,
                           cache_len=start, impl=impl,
                           chunk_continue=True, valid_len=valid_len)[:2]

    def apply_update(kind, bcache, upd, *, stacked):
        if upd is None:
            return bcache
        if kind == "ssm":
            # write the 1-row continuation state back into the slot's row
            axis = 1 if stacked else 0
            return jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=axis),
                bcache, upd)
        out = dict(bcache)
        for key, val in upd.items():
            if key == "self" and _is_delta(val):
                out["self"] = {
                    kk: _write_kv_chunk_paged(bcache["self"][kk],
                                              val[f"{kk}_delta"], start,
                                              pages_row[0], page_size,
                                              stacked=stacked)
                    for kk in ("k", "v")}
            else:
                out[key] = val
        return out

    def group_body(carry, xs):
        h = carry
        gparams, gcache = xs
        updates = []
        for i, kind in enumerate(plan.pattern):
            p = (params.get("shared") if kind == "shared"
                 else _constrain_block_params(gparams[i]))
            h, upd = block_step(kind, p, h, gcache[i])
            updates.append(upd)
        return h, tuple(updates)

    groups = tuple(params["groups"])
    gcaches = tuple(cache["groups"])
    # attention reads go through the slot's gathered page view (one stacked
    # gather per pattern, outside the scan); writes go into the pools
    read_gcaches = _page_views(gcaches, pages_row)
    read_tail = [_page_view_block(bc, pages_row) for bc in cache["tail"]]
    if jax.tree.leaves(groups):
        n_groups = jax.tree.leaves(groups)[0].shape[0]
        if cfg.scan_layers and n_groups > 1:
            x, updates = jax.lax.scan(group_body, x, (groups, read_gcaches))
        else:
            outs = []
            for g in range(n_groups):
                gp = jax.tree.map(lambda t: t[g], groups)
                gc = jax.tree.map(lambda t: t[g], read_gcaches)
                x, upd = group_body(x, (gp, gc))
                outs.append(upd)
            updates = jax.tree.map(lambda *ts: jnp.stack(ts), *outs) \
                if outs else None
        new_gcaches = tuple(
            apply_update(kind, gcaches[i], updates[i], stacked=True)
            for i, kind in enumerate(plan.pattern))
    else:
        new_gcaches = gcaches

    new_tail = []
    for i, kind in enumerate(plan.tail):
        x, upd = block_step(kind, params["tail"][i], x, read_tail[i])
        new_tail.append(apply_update(kind, cache["tail"][i], upd,
                                     stacked=False))

    x = apply_norm(cfg, params["norm_f"], x)
    # only the true last chunk token's logits are ever consumed (first-token
    # sampling after the final chunk) — slice BEFORE the unembed so
    # intermediate chunks never pay a (C, V) projection
    last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    logits = unembed(cfg, params["embed"], last)
    new_cache = {"groups": list(new_gcaches), "tail": new_tail,
                 "len": jnp.asarray(cache["len"]).at[slot].set(
                     start + valid_len)}
    return logits, new_cache


def prefill(cfg: ModelConfig, params: dict, tokens=None, *, embeds=None,
            enc_embeds=None, max_len: int | None = None, impl="auto"):
    """Run the prompt through the model, building a KV cache.

    Implemented as forward + cache-write: for attention layers we recompute
    K/V per layer into the cache.  (Serving engines use this for the prefill
    phase; decode then extends the cache.)  Returns (cache, last_logits).
    """
    # Simple reference implementation: step-by-step decode over the prompt.
    # The serving engine (repro/serve) overrides this with a batched
    # single-pass prefill; this function is the small-scale reference.
    B, S = (tokens.shape if tokens is not None else embeds.shape[:2])
    max_len = max_len or (S + 64)
    enc_out = None
    if cfg.family == "encdec":
        plan = layer_plan(cfg)
        e = enc_embeds.astype(jnp.dtype(cfg.compute_dtype))
        e = e + params["enc_pos"][: e.shape[1]].astype(e.dtype)[None]
        e, _ = _run_stack(cfg, plan.enc_pattern, tuple(params["enc_groups"]),
                          (), (), None, e, jnp.arange(e.shape[1]), impl=impl)
        enc_out = apply_norm(cfg, params["enc_norm_f"], e)
    cache = init_cache(cfg, B, max_len,
                       enc_len=enc_out.shape[1] if enc_out is not None else 0)

    def body(cache, t):
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1) \
            if tokens is not None else None
        emb = jax.lax.dynamic_slice_in_dim(embeds, t, 1, axis=1) \
            if embeds is not None else None
        logits, cache = decode_step(cfg, params, cache, tok, enc_out=enc_out,
                                    embeds=emb, impl=impl)
        return cache, logits

    cache, logits = jax.lax.scan(body, cache, jnp.arange(S))
    return cache, logits[-1], enc_out
