"""What the serve drivers share: the system under test built from the
configuration, its warm-up, the harness loop's bookkeeping, the recorder
of calls into the engine, and the correctness check against the plain
reference.

The system under test is ``ServeScheduler`` over ``PagedEngine`` with
``HyParRequestTracker``, as ``launch/serve.py --trace --paged --engine
hypar`` builds it; the harness drives it through ``submit`` and ``step``."""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from bench.lib import device, traffic
from bench.lib.cell import log
from bench.reference import qwen2 as ref

# published config.json key -> ModelConfig field
PUBLISHED = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings",
             "max_position_embeddings": "max_seq"}


def model_config(conf: dict):
    """The system's ``ModelConfig`` for this configuration: the repository's
    own preset with the served dtype, checked against every published size."""
    from repro.configs import get_config, get_smoke_config
    preset = (get_smoke_config if conf.get("program_size") == "smoke"
              else get_config)
    cfg = dataclasses.replace(preset(conf["program_arch"]),
                              param_dtype=conf["torch_dtype"],
                              compute_dtype=conf["torch_dtype"])
    for key, field in PUBLISHED.items():
        if getattr(cfg, field) != conf[key]:
            raise ValueError(f"{conf['program_arch']}: {field}="
                             f"{getattr(cfg, field)!r} but the published "
                             f"{key} is {conf[key]!r}")
    if not cfg.qkv_bias or cfg.hd * cfg.n_heads != conf["hidden_size"]:
        raise ValueError("qwen2 needs q/k/v biases and hidden/heads head size")
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
        self.params = ref.program_params(
            jax, conf, cell.seed, padded_vocab=self.cfg.padded_vocab,
            dtype=conf["torch_dtype"])
        jax.block_until_ready(self.params)
        eng.params = self.params
        self.n_params = sum(int(x.size) for x in jax.tree.leaves(self.params))
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


def flops(cfg, n_params: int, chunks, decodes) -> float:
    """Model FLOPs of the calls: 2 x (parameters outside the embedding) per
    token processed, 2 x vocab x d per logit row computed (one per chunk,
    one per decoded token), and attention at each token's context
    (4 x layers x heads x head_dim per attended position)."""
    d, V = cfg.d_model, cfg.padded_vocab
    body = n_params - V * d
    attn = 4 * cfg.n_layers * cfg.n_heads * cfg.hd
    total = 0.0
    for _, start, valid in chunks:
        # positions start .. start+valid-1 attend to p+1 keys each
        keys = valid * start + valid * (valid + 1) / 2
        total += 2 * body * valid + 2 * V * d + attn * keys
    for _, ctx in decodes:
        total += len(ctx) * (2 * body + 2 * V * d) + attn * sum(ctx)
    return total


def paged_kernel_cost(cfg, page_size: int, decodes) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the paged flash-decode kernel needs for the
    decode calls: per layer and live slot, the pages that hold its cached
    keys and values (context minus the new token, which is merged outside
    the kernel), read once, plus the query and the (acc, m, l) outputs."""
    L, KV, H, hd = cfg.n_layers, cfg.n_kv_heads, cfg.n_heads, cfg.hd
    G = H // KV
    page_bytes = page_size * hd * 2           # bf16 K or V, one head
    fl = by = 0.0
    for _, ctx in decodes:
        for c in ctx:
            old = c - 1
            pages = -(-old // page_size)
            by += L * KV * 2 * pages * page_bytes
            by += L * H * hd * 2 + L * KV * G * (hd + 2 * 128) * 4
            fl += L * 4 * H * hd * old
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
