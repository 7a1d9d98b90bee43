"""What the serve drivers share: the system under test built from the
configuration, its warm-up, the harness loop's bookkeeping, the recorder
of calls into the engine, the model's FLOPs and the paged kernel's bytes
counted from the program's layer plan, and the correctness check against
the plain reference of the configuration's ``system``
(``bench/reference/<system>.py``, found by ``bench.reference.find``).

The system under test is ``ServeScheduler`` over ``PagedEngine`` with
``HyParRequestTracker``, as ``launch/serve.py --trace --paged --engine
hypar`` builds it; the harness drives it through ``submit`` and ``step``."""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time

import numpy as np

from bench import reference
from bench.lib import device, traffic
from bench.lib.cell import log

# the sizes every decoder's published config.json states -> ModelConfig
# field; what belongs to one system its reference checks (``check_config``)
DECODER = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
           "tie_word_embeddings": "tie_embeddings",
           "max_position_embeddings": "max_seq"}


def model_config(conf: dict):
    """The system's ``ModelConfig`` for this configuration: the repository's
    own preset with the served dtype, checked against each size of
    ``DECODER`` the configuration states, then by the reference of its
    ``system``, where that reference has a ``check_config``."""
    from repro.configs import get_config, get_smoke_config
    preset = (get_smoke_config if conf.get("program_size") == "smoke"
              else get_config)
    cfg = dataclasses.replace(preset(conf["program_arch"]),
                              param_dtype=conf["torch_dtype"],
                              compute_dtype=conf["torch_dtype"])
    for key, field in DECODER.items():
        if key in conf and getattr(cfg, field) != conf[key]:
            raise ValueError(f"{conf['program_arch']}: {field}="
                             f"{getattr(cfg, field)!r} but the published "
                             f"{key} is {conf[key]!r}")
    ref = reference.find(conf["system"])
    if hasattr(ref, "check_config"):
        ref.check_config(cfg, conf)
    return cfg


class Recorder:
    """Records each call the scheduler makes into the engine: chunk
    prefills ``(t, start, valid)`` and decodes ``(t, contexts)``, where
    ``contexts`` are the live slots' context lengths including the new
    token.  The model FLOPs and the kernel's bytes are counted from these."""

    def __init__(self, sched):
        self.chunks: list[tuple[float, int, int]] = []
        self.decodes: list[tuple[float, list[int]]] = []
        eng = sched.engine
        chunk, decode = eng.prefill_chunk, eng.decode

        def prefill_chunk(slot, toks, page_ids, start, valid):
            out = chunk(slot, toks, page_ids, start, valid)
            self.chunks.append((time.perf_counter(), int(start), int(valid)))
            return out

        def decode_(tokens, live_mask=None):
            ctx = [sched.slots[i].pos for i in range(eng.batch)
                   if live_mask is None or live_mask[i]]
            out = decode(tokens, live_mask)
            self.decodes.append((time.perf_counter(), ctx))
            return out

        eng.prefill_chunk = prefill_chunk
        eng.decode = decode_

    def in_span(self, lo: float, hi: float):
        return ([c for c in self.chunks if lo <= c[0] <= hi],
                [d for d in self.decodes if lo <= d[0] <= hi])


class Server:
    """The system under test, built and warmed from the cell's files."""

    def __init__(self, cell, jax):
        from repro.serve import (HyParRequestTracker, PagedEngine,
                                 RequestQueue, SamplingParams,
                                 ServeScheduler)
        conf, mix = cell.config, cell.mix
        srv = conf["serving"]
        self.jax = jax
        self.conf = conf
        self.ref = reference.find(conf["system"])
        self.cfg = model_config(conf)
        self.slots = int(mix["slots"])
        # the page pool is allocated before the weights: the program builds
        # its stacked pools eagerly (a broadcast, then a copy), so for a
        # moment it holds one and a half pools; with the weights already
        # resident that moment would not fit the pool this chip can keep
        eng = PagedEngine(self.cfg, None, batch=self.slots,
                          max_len=int(srv["max_len"]),
                          page_size=int(srv["page_size"]),
                          num_pages=int(srv["num_pages"]),
                          prefill_chunk=int(srv["prefill_chunk"]),
                          attn_impl=srv["paged_attention"])
        eng.ensure_batch()
        jax.block_until_ready(eng.cache)
        self.params = self.ref.program_params(
            jax, conf, cell.seed, padded_vocab=self.cfg.padded_vocab,
            dtype=conf["torch_dtype"])
        jax.block_until_ready(self.params)
        eng.params = self.params
        self.n_params = sum(int(x.size) for x in jax.tree.leaves(self.params))
        self.body = token_params(self.cfg, self.params)
        tracker = HyParRequestTracker(self.slots, strategy=srv["strategy"],
                                      flops_per_token=2.0 * self.n_params)
        self.sched = ServeScheduler(eng, sp=SamplingParams(), tracker=tracker,
                                    queue=RequestQueue(),
                                    reserve=srv["reserve"],
                                    prefix_cache=bool(srv["prefix_cache"]))
        self.engine = eng
        log(f"serve: slots={self.slots} max_len={eng.max_len} "
            f"pages={eng.num_pages} chunk={eng.chunk_len} "
            f"buckets={eng.chunk_buckets} attn={eng.attn_impl}")

    def warm(self, lengths) -> None:
        """Compile the decode program and each chunk bucket that prompts of
        ``lengths`` reach: one short request per bucket, drained, then the
        prefix cache flushed so the window starts cold."""
        eng = self.engine
        need = set()
        for n in lengths:
            from repro.serve.engine import chunk_plan
            need |= {b for _, b, _ in chunk_plan(int(n), eng.chunk_len,
                                                 eng.chunk_buckets)}
        rng = traffic.make_rng(0, "warm")
        for b in sorted(need):
            n = eng.chunk_len + b if b < eng.chunk_len else b
            n = min(n, eng.max_len - 2)
            toks = rng.integers(0, self.cfg.vocab_size, n, dtype=np.int32)
            self.sched.submit(toks, 2)
        while self.sched.step():
            pass
        self.sched.reset_metrics()
        self.sched.flush_prefix_cache()
        got = eng.trace_count("chunk_prefill"), eng.trace_count("decode")
        log(f"serve warm: chunk buckets {sorted(need)}, traces "
            f"chunk_prefill={got[0]} decode={got[1]}")

    # -- bookkeeping -----------------------------------------------------------
    def records(self, subs: dict) -> list[dict]:
        """One record per submitted request: ``subs`` maps rid -> its
        ``{"due", "submit", "prompt"}``; tokens and their times come from
        the finished results and the slots still in flight."""
        out = {}
        for r in self.sched.results:
            if r.rid in subs:
                out[r.rid] = {**subs[r.rid], "tokens": list(r.tokens),
                              "token_s": list(r.token_s), "finished": True}
        for st in self.sched.slots:
            req = st.request
            if req is not None and req.rid in subs and req.rid not in out:
                out[req.rid] = {**subs[req.rid], "tokens": list(st.tokens),
                                "token_s": list(st.token_s),
                                "finished": False}
        for rid, s in subs.items():
            out.setdefault(rid, {**s, "tokens": [], "token_s": [],
                                 "finished": False})
        return list(out.values())

    def n_failed(self, rids) -> int:
        """Requests of ``rids`` that ended in an outcome other than
        ``completed`` (shed, expired, failed)."""
        return sum(1 for rid in rids if rid in self.sched.outcomes
                   and self.sched.outcomes[rid].outcome != "completed")

    def release(self) -> None:
        """Free the system's device state before the reference runs."""
        self.engine.cache = self.engine.params = None
        self.engine._pt_device = None
        self.sched = self.engine = self.params = None
        gc.collect()


# ---------------------------------------------------------------------------
# model FLOPs and the paged kernel's bytes
# ---------------------------------------------------------------------------


# block kinds of the program's layer plan whose mixer is attention -> the
# window its keys lie in (None or 0: every key before the token); the
# other kinds' mixers (``ssm``) are counted by their parameters only
ATTENTION = {"dense": lambda cfg: cfg.sliding_window,
             "moe": lambda cfg: cfg.sliding_window,
             "local": lambda cfg: cfg.local_window,
             "global": lambda cfg: None,
             "shared": lambda cfg: None}
OTHER_MIXERS = ("ssm",)


def plan_kinds(cfg) -> list[str]:
    """The block kind of each layer a token passes through, in order: the
    program's layer plan (``repro.models.transformer.layer_plan``)
    unrolled, a shared block once per use."""
    from repro.models.transformer import layer_plan
    plan = layer_plan(cfg)
    kinds = list(plan.pattern) * plan.n_groups + list(plan.tail)
    unknown = set(kinds) - set(ATTENTION) - set(OTHER_MIXERS)
    if unknown:
        raise ValueError(f"{cfg.name}: no cost rule for layer kinds "
                         f"{sorted(unknown)}")
    return kinds


def attention_windows(cfg) -> dict:
    """{window (None: every key): attention layers a token passes through
    with that window}."""
    return dict(collections.Counter(ATTENTION[k](cfg) or None
                                    for k in plan_kinds(cfg)
                                    if k in ATTENTION))


def token_params(cfg, params) -> int:
    """Parameters one token's pass multiplies by, outside the embedding
    table and the output head: every leaf of ``params`` (the program's
    layout; arrays or shapes) but ``embed``, a shared block once per use,
    and of a mixture-of-experts layer's experts ``top_k`` of
    ``n_experts``."""
    import jax

    def size(tree) -> int:
        n = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            keys = [getattr(k, "key", None) for k in path]
            m = math.prod(leaf.shape)
            if len(keys) >= 2 and keys[-2] == "moe" and keys[-1] in (
                    "gate", "up", "down"):
                m = m * cfg.top_k // cfg.n_experts
            n += m
        return n

    rest = size({k: v for k, v in params.items() if k != "embed"})
    uses = plan_kinds(cfg).count("shared")
    return rest + (uses - 1) * size(params["shared"]) if uses else rest


def chunk_keys(start: int, valid: int, window) -> int:
    """Keys that positions ``start`` .. ``start + valid - 1`` attend to,
    each to itself and the keys before it inside ``window``."""
    lo, hi = start + 1, start + valid     # position p attends p + 1 keys
    if not window or hi <= window:
        return valid * start + valid * (valid + 1) // 2
    if lo > window:
        return window * valid
    return (window - lo + 1) * (lo + window) // 2 + window * (hi - window)


def flops(cfg, body: int, chunks, decodes) -> float:
    """Model FLOPs of the calls, from the program's layer plan:
    2 x ``body`` (``token_params``) per token processed; 2 x vocab x d
    per logit row computed (one per chunk, one per decoded token), tied
    head or not; and 4 x heads x head_dim per key that each attention
    layer of the plan attends to (a shared block once per use, a windowed
    layer only the keys inside its window, ``min(context, window)``).
    Mixers that are not attention (SSM) count by their parameters only:
    their state updates and scans are not counted."""
    d, V = cfg.d_model, cfg.padded_vocab
    per_key = 4 * cfg.n_heads * cfg.hd
    windows = attention_windows(cfg)
    total = 0.0
    for _, start, valid in chunks:
        keys = sum(n * chunk_keys(start, valid, w)
                   for w, n in windows.items())
        total += 2 * body * valid + 2 * V * d + per_key * keys
    for _, ctx in decodes:
        keys = sum(n * (sum(min(c, w) for c in ctx) if w else sum(ctx))
                   for w, n in windows.items())
        total += len(ctx) * (2 * body + 2 * V * d) + per_key * keys
    return total


def window_pages(ctx: int, window, page_size: int) -> tuple[int, int]:
    """(cached keys, pages holding them) that a token decoded at context
    ``ctx`` (itself included, merged outside the kernel) attends to in a
    layer with ``window``."""
    old = ctx - 1
    keys = min(old, window - 1) if window else old
    if keys <= 0:
        return 0, 0
    return keys, (ctx - 2) // page_size - (ctx - 1 - keys) // page_size + 1


def paged_kernel_cost(cfg, page_size: int, decodes) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the paged flash-decode kernel needs for the
    decode calls: per attention layer of the plan and live slot, the pages
    that hold the cached keys and values it attends to (context minus the
    new token; in a windowed layer only those inside the window), read
    once, plus the query and the (acc, m, l) outputs."""
    import jax.numpy as jnp
    KV, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.hd
    G = H // KV
    windows = attention_windows(cfg)
    L = sum(windows.values())
    kv_bytes = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype).itemsize
    q_bytes = jnp.dtype(cfg.compute_dtype).itemsize
    page_bytes = page_size * hd * kv_bytes    # K or V, one head
    fl = by = 0.0
    for _, ctx in decodes:
        for c in ctx:
            keys = pages = 0
            for w, n in windows.items():
                k, p = window_pages(c, w, page_size)
                keys, pages = keys + n * k, pages + n * p
            by += KV * 2 * pages * page_bytes
            by += L * H * hd * q_bytes + L * KV * G * (hd + 2 * 128) * 4
            fl += 4 * H * hd * keys
    return fl, by


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def pick_sample(records, seed: int, *, tokens: int, max_requests: int):
    """Requests to check, drawn from the seed: the longest finished one,
    then others at random until ``tokens`` served tokens are covered.
    Finished requests come first; served tokens of requests still in
    flight at the close are checked only where too few finished."""
    done = [r for r in records if r["finished"] and r["tokens"]]
    open_ = [r for r in records if not r["finished"] and r["tokens"]]
    rng = traffic.make_rng(seed, "check")
    out = []
    for pool in (done, open_):
        if not pool:
            continue
        pool = sorted(pool, key=lambda r: -(len(r["prompt"])
                                            + len(r["tokens"])))
        order = [0] + list(1 + rng.permutation(len(pool) - 1))
        for i in order:
            if (sum(len(r["tokens"]) for r in out) >= tokens
                    or len(out) >= max_requests):
                return out
            out.append(pool[i])
    return out


def check(cell, jax, sample) -> None:
    """Run the reference over each sampled prompt with its served tokens;
    the compared number is the widest gap by which a served token's
    reference logit lies below the reference's best.  In a control run
    (``cell.control``) the tokens compared are, at each of the same
    positions, the ones the control (float8 weights, computed in bfloat16)
    puts first."""
    conf = cell.config
    ref = reference.find(conf["system"])
    t0 = time.perf_counter()
    seqs, rows = [], []
    for r in sample:
        seqs.append(np.concatenate([r["prompt"],
                                    np.asarray(r["tokens"], np.int32)]))
        P = len(r["prompt"])
        rows.append(np.arange(P - 1, P - 1 + len(r["tokens"])))
    logits = ref.forward_logits(jax, conf, cell.seed, seqs, rows,
                                served_dtype=conf["torch_dtype"])
    served = [r["tokens"] for r in sample]
    if cell.control:
        prog = np.concatenate([ref.served_gaps(lg, t)
                               for lg, t in zip(logits, served)])
        log(f"control: program gap max {prog.max() if prog.size else None!r}")
        ctl = ref.forward_logits(jax, conf, cell.seed, seqs, rows,
                                 served_dtype=conf["torch_dtype"],
                                 quant="fp8")
        served = [lg.argmax(-1) for lg in ctl]
    gaps = np.concatenate([ref.served_gaps(lg, t)
                           for lg, t in zip(logits, served)])
    n_tok = int(gaps.size)
    log(f"check: {len(sample)} requests, {n_tok} served tokens, reference "
        f"{time.perf_counter() - t0:.2f} s, gap max "
        f"{gaps.max() if n_tok else None}, mean "
        f"{gaps.mean() if n_tok else None}, share at 0 "
        f"{np.mean(gaps == 0) if n_tok else None}")
    cell.data["check_tokens"] = n_tok
    value = float(gaps.max()) if n_tok else math.inf
    cell.check("logit_gap", value, float(conf["limits"]["logit_gap"]))
