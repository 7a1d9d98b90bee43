"""Serving CLI: drive the serving stack by hand on a full or ``--smoke``
model and print what it did.

The benchmark's numbers come from ``bench/run.py`` (``BENCHMARK.json``);
the times printed here are one run's wall clock, a quick look only.

Two modes:

* **wave** (default) — fixed-size request waves through ``Engine.generate``
  (batched prefill, token-by-token decode, greedy & temperature sampling).
  Throughput counts *real* generated tokens (stop-token padding rows after
  early termination are excluded) and the first wave — which pays jit
  compilation — is reported separately and excluded from the steady-state
  tok/s.
* **trace** (``--trace``) — request-level continuous batching through
  ``ServeScheduler``: Poisson arrivals, mixed prompt lengths, admission
  control, prefill-into-free-slot / decode-live-batch / retire lifecycle.
  ``--engine hypar`` routes every request through the core job machinery
  (dynamic control-spawned jobs, MasterScheduler placement, ResultStore
  retention) — see DESIGN.md §8.  ``--paged`` swaps the dense per-slot KV
  cache for the paged pool + chunked-prefill path (admission by free pages,
  long prompts interleaved with decode steps) — see DESIGN.md §9.

Examples::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --batch 4 --prompt-len 32 --max-new 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --trace --engine hypar --n-requests 32 --rate 64
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --trace --paged --prefill-chunk 32 --prompt-lens 8 16 96
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import (Engine, HyParRequestTracker, PagedEngine, Request,
                         RequestQueue, SamplingParams, ServeScheduler,
                         count_generated)


def build_trace(rng: np.random.Generator, cfg, *, n_requests: int,
                rate_per_s: float, prompt_lens: list[int],
                max_new, budget_new: int | None = None,
                shared_prefix_len: int = 0,
                ttft_deadline_s: float | None = None,
                total_deadline_s: float | None = None) -> list[Request]:
    """Open-loop request trace: Poisson arrivals (exponential gaps at
    ``rate_per_s``), prompt lengths drawn uniformly from ``prompt_lens``.

    ``max_new`` may be a single realised length or a mix to draw from per
    request; ``budget_new`` is the declared generation cap clients submit
    alongside (admission must provision for it — full-lifetime reservation
    pays its pages even when the realised length stops far short, which is
    the over-provisioning reserve-on-demand exists to reclaim).

    ``shared_prefix_len`` > 0 makes every prompt open with the SAME token
    prefix (a system prompt) followed by a random remainder — the workload
    shape prefix caching exists for.

    ``ttft_deadline_s`` / ``total_deadline_s`` stamp the same SLO onto every
    request; the scheduler sheds requests predicted to miss the TTFT
    deadline and retires ones past the total deadline (DESIGN.md §14)."""
    t = 0.0
    mix = [int(m) for m in np.atleast_1d(max_new)]
    prefix = None
    if shared_prefix_len > 0:
        if min(prompt_lens) <= shared_prefix_len:
            raise ValueError(f"every prompt length {tuple(prompt_lens)} must "
                             f"exceed shared_prefix_len {shared_prefix_len} "
                             f"(each prompt = prefix + random remainder)")
        prefix = rng.integers(0, cfg.vocab_size - 1,
                              (shared_prefix_len,)).astype(np.int32)
    reqs = []
    for rid in range(n_requests):
        t += rng.exponential(1.0 / rate_per_s) if rate_per_s > 0 else 0.0
        S = int(rng.choice(prompt_lens))
        toks = rng.integers(0, cfg.vocab_size - 1, (S,)).astype(np.int32)
        if prefix is not None:
            toks = np.concatenate([prefix, toks[shared_prefix_len:]])
        enc = None
        if cfg.family == "encdec":
            enc = jnp.asarray(rng.standard_normal(
                (1, 64, cfg.d_model), dtype=np.float32))
        reqs.append(Request(rid=rid, tokens=toks,
                            max_new=int(rng.choice(mix)),
                            budget_new=budget_new,
                            arrival_s=t, enc_embeds=enc,
                            ttft_deadline_s=ttft_deadline_s,
                            total_deadline_s=total_deadline_s))
    return reqs


def warmup_requests(rng: np.random.Generator, cfg, *, prompt_lens,
                    ) -> list[Request]:
    """One request per distinct prompt length, all arriving at t=0 — pays
    every per-bucket slot-prefill compilation before the measured run."""
    reqs = []
    for rid, S in enumerate(sorted(set(int(l) for l in prompt_lens))):
        toks = rng.integers(0, cfg.vocab_size - 1, (S,)).astype(np.int32)
        enc = None
        if cfg.family == "encdec":
            enc = jnp.asarray(rng.standard_normal(
                (1, 64, cfg.d_model), dtype=np.float32))
        reqs.append(Request(rid=rid, tokens=toks, max_new=2, arrival_s=0.0,
                            enc_embeds=enc))
    return reqs


def make_scheduler(cfg, params, args, *, sp: SamplingParams,
                   max_len: int) -> ServeScheduler:
    mesh = None
    device_groups = 1
    if getattr(args, "mesh", None):
        from repro.serve.mesh import MeshSpec, build_serve_mesh
        spec = MeshSpec.parse(args.mesh)
        mesh = build_serve_mesh(spec)
        device_groups = spec.dp
    if getattr(args, "paged", False):
        eng = PagedEngine(cfg, params, batch=args.batch, max_len=max_len,
                          page_size=args.page_size,
                          num_pages=args.num_pages,
                          prefill_chunk=args.prefill_chunk,
                          mesh=mesh,
                          attn_impl=getattr(args, "paged_attn_impl",
                                            "auto"))
    else:
        eng = Engine(cfg, params, batch=args.batch, max_len=max_len)
    tracker = None
    if args.engine == "hypar":
        jobstore = None
        if getattr(args, "store", ""):
            from repro.core.store import JobStore
            jobstore = JobStore(args.store)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        tracker = HyParRequestTracker(args.batch, strategy=args.strategy,
                                      flops_per_token=2.0 * n_params,
                                      jobstore=jobstore)
    buckets = sorted({1 << (int(l) - 1).bit_length() for l in args.prompt_lens
                      if l < max_len} | {16})
    return ServeScheduler(eng, sp=sp, tracker=tracker, buckets=buckets,
                          queue=RequestQueue(max_pending=args.max_pending),
                          reserve=getattr(args, "reserve", "lifetime"),
                          preempt_policy=getattr(args, "preempt_policy",
                                                 "fewest"),
                          admit_watermark=getattr(args, "admit_watermark", 0),
                          prefix_cache=getattr(args, "prefix_cache", False),
                          prefix_admit=getattr(args, "prefix_admit", 1),
                          device_groups=device_groups,
                          enforce_deadlines=getattr(args, "enforce_deadlines",
                                                    True),
                          watchdog_budget_s=getattr(args, "watchdog_budget",
                                                    None),
                          max_restarts=getattr(args, "max_restarts", None))


def prepare_trace(cfg, params, args, *, sp: SamplingParams):
    """Build a warmed scheduler + the request trace for it.

    Warmup runs on the SAME scheduler: Engine jit caches are per-instance,
    so a throwaway warmup engine would leave the measured replay to pay
    every prefill/decode/splice compilation they claim to have excluded.
    """
    max_len = max(args.prompt_lens) + args.max_new + 8
    rng = np.random.default_rng(args.seed)
    sched = make_scheduler(cfg, params, args, sp=sp, max_len=max_len)
    # the trace is drawn BEFORE the warmup touches the rng: warmup length
    # sets vary per engine configuration (e.g. the chunk-bucket warmup
    # below), and compared variants must replay the IDENTICAL trace
    mix = getattr(args, "max_new_mix", None)
    reqs = build_trace(rng, cfg, n_requests=args.n_requests,
                       rate_per_s=args.rate, prompt_lens=list(args.prompt_lens),
                       max_new=(mix if mix else args.max_new),
                       budget_new=(args.max_new if mix else None),
                       shared_prefix_len=getattr(args, "shared_prefix_len",
                                                 0),
                       ttft_deadline_s=getattr(args, "ttft_deadline", None),
                       total_deadline_s=getattr(args, "total_deadline", None))
    warm_lens = list(args.prompt_lens)
    if getattr(sched, "demand", False):
        # resume re-prefills (prompt + retained tokens) land in arbitrary
        # chunk buckets, not just the trace's prompt lengths — warm every
        # bucket so no measured replay pays a chunk-program compile
        warm_lens += [b for b in sched.engine.chunk_buckets
                      if b + 2 <= sched.engine.max_len]
    sched.run(warmup_requests(rng, cfg, prompt_lens=warm_lens))
    sched.reset_metrics()          # warmup rids recur in the second pass
    # second, compile-free pass: the first pass's steps are dominated by
    # compiles, and the step/retire EWMAs (which deliberately survive
    # reset_metrics as shedding calibration, DESIGN.md §14) would otherwise
    # enter the measured replay 100-1000x above steady state
    sched.run(warmup_requests(rng, cfg, prompt_lens=list(args.prompt_lens)))
    sched.reset_metrics()
    if getattr(sched, "prefix_cache_active", False):
        # drop the warmup prompts' cache entries (and their held pages):
        # the measured replay starts from a cold cache and earns its hits from
        # the trace's own shared prefixes
        sched.flush_prefix_cache()
    return sched, reqs


def run_trace(cfg, params, args, *, sp: SamplingParams) -> dict:
    """One measured replay of the trace on a warmed scheduler, and its
    stats.  (``run()`` rebases each request's arrival onto the live clock,
    so the replay gets fresh Request copies.)"""
    sched, reqs = prepare_trace(cfg, params, args, sp=sp)
    if getattr(args, "resume", False):
        # master restart: re-seed suspended-request records from the durable
        # store — resubmitted rids resume by recompute (DESIGN.md §12)
        n = sched.restore_suspended()
        print(f"restored {n} suspended request(s) from {args.store}")
    replay = [dataclasses.replace(r) for r in reqs]
    t0 = time.perf_counter()
    results = sched.run(replay)
    wall = time.perf_counter() - t0
    return trace_stats(args, sched, results, wall)


def trace_stats(args, sched, results, wall: float) -> dict:
    """The stats dict of a replay: ``results`` and its ``wall`` seconds,
    and the scheduler's counters since its last ``reset_metrics``."""
    n_tok = sum(r.n_generated for r in results)
    # NaN, not 0.0, when nothing completed: a broken/all-shed run must not
    # report perfect-looking latencies
    ttfts = (np.array([r.ttft_s for r in results]) if results
             else np.array([np.nan]))
    lats = (np.array([l for r in results for l in r.step_latencies_s])
            if any(r.step_latencies_s for r in results)
            else np.array([np.nan]))
    outcome_hist: dict[str, int] = {}
    for o in sched.outcomes.values():
        outcome_hist[o.outcome] = outcome_hist.get(o.outcome, 0) + 1
    n_lookups, n_hits = sched.n_prefix_lookups, sched.n_prefix_hits
    eng = sched.engine
    trace_counts = ({"chunk_prefill": eng.trace_count("chunk_prefill"),
                     "decode": eng.trace_count("decode")}
                    if sched.paged else
                    {"prefill": eng.trace_count("prefill"),
                     "decode": eng.trace_count("decode"),
                     "splice": eng.trace_count("splice")})
    stats = {
        "engine": args.engine,
        "paged": sched.paged,
        "n_requests": len(results),
        "n_rejected": sched.queue.n_rejected,
        "gen_tokens": n_tok,
        "wall_s": wall,
        "tok_per_s": n_tok / wall if wall > 0 else 0.0,
        "ttft_p50_s": float(np.percentile(ttfts, 50)),
        "ttft_p95_s": float(np.percentile(ttfts, 95)),
        "lat_p50_s": float(np.percentile(lats, 50)),
        "lat_p95_s": float(np.percentile(lats, 95)),
        "occupancy": sched.occupancy,
        "trace_counts": trace_counts,
        # the decode attention the run actually executed (PagedEngine
        # resolves "auto" from the backend and the pool dtype)
        "paged_attn_impl": eng.attn_impl if sched.paged else None,
        # recurrent state beside the pages: bytes a slot holds, and the
        # prefills that started a reused slot's state from zero
        "ssm_state_bytes": eng.ssm_state_bytes if sched.paged else None,
        "ssm_state_resets": eng.ssm_state_resets if sched.paged else None,
        # generated ids per request: what mesh/impl parity checks compare
        "outputs": {int(r.rid): [int(t) for t in r.tokens] for r in results},
        "reserve": getattr(sched, "reserve", "lifetime"),
        "preempt_count": sched.n_preempted,
        "resume_tokens_recomputed": sched.resume_tokens_recomputed,
        "admit_deferred": sched.n_admit_deferred,
        "prefix_cache": sched.prefix_cache_active,
        "prefix_hit_rate": (n_hits / n_lookups if n_lookups else 0.0),
        "pages_shared": sched.pages_shared,
        "cow_copies": sched.n_cow_copies,
        "cache_insert_deferred": sched.n_cache_insert_deferred,
        "mesh": getattr(args, "mesh", None) or None,
        "device_groups": len(sched.groups),
        "group_occupancy": [float(x) for x in sched.group_occupancy],
        # robustness surface (DESIGN.md §14): typed shed counters, terminal
        # outcome histogram, deadline goodput, watchdog/failover counts
        "shed_queue_full": sched.queue.shed_queue_full,
        "shed_never_fits": sched.queue.shed_never_fits,
        "shed_deadline": sched.queue.shed_deadline,
        "outcomes": outcome_hist,
        "goodput_tokens": sched.goodput_tokens,
        "watchdog_trips": sched.watchdog_trips,
        "n_expired": sched.n_expired,
        "n_failed": sched.n_failed,
        "group_failovers": sched.n_group_failovers,
        "group_rejoins": sched.n_group_rejoins,
        "rejoin_backoff_s": sched.rejoin_backoff_s,
        "suspended_rids": sorted(sched._suspended),
        "goodput_tok_per_s": (sched.goodput_tokens / wall
                              if wall > 0 else 0.0),
        "enforce_deadlines": getattr(sched, "enforce_deadlines", True),
    }
    if sched.paged:
        # per-device KV budget: pool tokens scaled by the byte fraction one
        # device holds — TP=2 halves it over kv_heads; DP=2 halves it over
        # pages whenever the pool size divides (odd pools stay replicated)
        total_b = eng.total_pool_bytes()
        dev_b = eng.per_device_pool_bytes()
        pool_tokens = eng.num_pages * eng.page_size
        stats["total_pool_bytes"] = total_b
        stats["per_device_pool_bytes"] = dev_b
        stats["kv_budget_tokens"] = (
            int(round(pool_tokens * dev_b / total_b)) if total_b else
            pool_tokens)
    return stats


def run_waves(cfg, params, args, *, sp: SamplingParams) -> None:
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    max_len = args.prompt_len + args.max_new + 8
    eng = Engine(cfg, params, batch=args.batch, max_len=max_len)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M batch={args.batch} "
          f"max_len={max_len}")

    total_tokens = steady_tokens = 0
    steady_s = 0.0
    for wave in range(args.requests):
        prompts = rng.integers(
            0, cfg.vocab_size - 1, (args.batch, args.prompt_len)).astype(np.int32)
        enc = None
        if cfg.family == "encdec":
            enc = jnp.asarray(rng.standard_normal(
                (args.batch, 64, cfg.d_model), dtype=np.float32))
        t0 = time.perf_counter()
        out = eng.generate(jnp.asarray(prompts), max_new=args.max_new, sp=sp,
                           key=jax.random.fold_in(key, wave), enc_embeds=enc)
        dt = time.perf_counter() - t0
        n_real = count_generated(out, sp.stop_token)
        total_tokens += n_real
        if wave == 0:
            print(f"wave 0 (compile): {n_real} tokens in {dt:.1f}s "
                  f"(excluded from steady-state tok/s)")
        else:
            steady_tokens += n_real
            steady_s += dt
        print(f"wave {wave}: generated {out.shape} -> {n_real} real tokens; "
              f"sample row: {out[0, :10].tolist()}")
    if steady_s > 0:
        print(f"throughput: {steady_tokens / steady_s:.1f} tok/s steady-state "
              f"({steady_tokens} tokens in {steady_s:.1f}s; "
              f"{total_tokens} total incl. compile wave)")
    else:
        print(f"throughput: n/a (single wave pays compilation; "
              f"{total_tokens} tokens)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=2,
                    help="waves of requests (wave mode)")
    # request-trace mode
    ap.add_argument("--trace", action="store_true",
                    help="request-level continuous batching from a trace")
    ap.add_argument("--engine", choices=["direct", "hypar"], default="direct",
                    help="trace mode: direct slot filling vs HyPar "
                         "dynamic-job scheduling")
    ap.add_argument("--strategy", choices=["greedy", "cost"], default="greedy",
                    help="hypar engine: MasterScheduler placement strategy")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests/s (0 = all at once)")
    ap.add_argument("--prompt-lens", type=int, nargs="+", default=[8, 16, 24],
                    help="trace mode: mixed prompt lengths")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission control: shed beyond this queue depth")
    ap.add_argument("--max-new-mix", type=int, nargs="+", default=None,
                    help="trace mode: realised generation lengths drawn "
                         "per request; --max-new then acts as the declared "
                         "cap admission provisions for")
    # paged KV + chunked prefill (trace mode)
    ap.add_argument("--paged", action="store_true",
                    help="trace mode: paged KV cache + chunked prefill "
                         "(admission by free pages)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged: pool size incl. the trash page (default: "
                         "the dense engine's batch x max_len footprint)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="paged: prompt chunk length interleaved with "
                         "decode steps (multiple of --page-size)")
    ap.add_argument("--paged-attn-impl", default="auto",
                    choices=["auto", "kernel", "interpret", "ref"],
                    help="paged: decode attention impl — auto runs the "
                         "paged flash-decode Pallas kernel on TPU and the "
                         "gather_pages path elsewhere")
    ap.add_argument("--reserve", choices=["lifetime", "demand"],
                    default="lifetime",
                    help="paged: reserve a request's full prompt+budget "
                         "page span at admission (lifetime) or only its "
                         "prompt span, appending decode pages on demand "
                         "with vLLM-style preemption on exhaustion (demand)")
    ap.add_argument("--preempt-policy", choices=["fewest", "lifo"],
                    default="fewest",
                    help="demand: victim choice on pool exhaustion — "
                         "fewest generated tokens (LIFO tiebreak) or "
                         "latest admitted")
    ap.add_argument("--admit-watermark", type=int, default=0,
                    help="demand: free pages held back from admissions as "
                         "decode-append headroom")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged: share cache-hit prompt prefixes across "
                         "slots (copy-on-write pages)")
    ap.add_argument("--prefix-admit", type=int, default=1,
                    help="prefix cache: insert a prefix only on its Nth "
                         "sighting (N=1 inserts immediately); first "
                         "sightings hash host-side without taking pool "
                         "references")
    ap.add_argument("--mesh", default=None, metavar="TP,DP",
                    help="paged: shard the engine over a TPxDP device mesh "
                         "— KV heads over TP (one model replica), batch "
                         "slots + page pool over DP device groups "
                         "(DESIGN.md §13).  '1,1' forces the mesh code "
                         "path on one device (bit-identical to no mesh)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="trace mode: every prompt opens with the same "
                         "token prefix of this length (system-prompt "
                         "workload; pairs with --prefix-cache)")
    ap.add_argument("--store", default="",
                    help="hypar engine: durable job-store path — suspended "
                         "requests' host-retained tokens persist, so "
                         "recovery survives a master restart (DESIGN.md "
                         "§12)")
    ap.add_argument("--resume", action="store_true",
                    help="hypar engine: re-seed suspended requests from "
                         "--store before replaying (requires --reserve "
                         "demand)")
    ap.add_argument("--store-gc", type=float, default=None, metavar="SECS",
                    help="after the run, prune done job-store rows older "
                         "than this many seconds (and their spill files)")
    ap.add_argument("--store-gc-rows", type=int, default=None, metavar="N",
                    help="after the run, keep at most N most-recent done "
                         "job-store rows")
    # deadline-aware serving + robustness (DESIGN.md §14)
    ap.add_argument("--ttft-deadline", type=float, default=None,
                    metavar="SECS",
                    help="trace mode: stamp this first-token deadline on "
                         "every request; admission sheds requests whose "
                         "predicted TTFT already exceeds it")
    ap.add_argument("--total-deadline", type=float, default=None,
                    metavar="SECS",
                    help="trace mode: stamp this whole-answer deadline on "
                         "every request; requests past it are retired as "
                         "expired")
    ap.add_argument("--no-enforce-deadlines", dest="enforce_deadlines",
                    action="store_false",
                    help="observe deadlines in the goodput metric but never "
                         "shed or expire on them (the no-shedding baseline)")
    ap.add_argument("--watchdog-budget", type=float, default=None,
                    metavar="SECS",
                    help="wall-clock budget per prefill chunk / decode wave; "
                         "a step over budget trips the watchdog, frees the "
                         "slot and re-queues the request")
    ap.add_argument("--max-restarts", type=int, default=None, metavar="N",
                    help="fault-eviction budget per request; a request "
                         "evicted more than N times fails terminally "
                         "(default: unlimited)")
    args = ap.parse_args(argv)
    if (args.store or args.resume) and args.engine != "hypar":
        ap.error("--store/--resume require --engine hypar (the tracker "
                 "owns the durable store)")
    if args.resume and not (args.store and args.reserve == "demand"):
        ap.error("--resume needs --store and --reserve demand (resume "
                 "recompute is the demand-mode recovery path)")
    if args.paged and not args.trace:
        ap.error("--paged requires --trace (wave mode is dense-only)")
    if args.reserve == "demand" and not args.paged:
        ap.error("--reserve demand requires --paged")
    if args.admit_watermark and args.reserve != "demand":
        ap.error("--admit-watermark requires --reserve demand")
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (dense slots have no "
                 "pages to share)")
    if args.shared_prefix_len and not args.trace:
        ap.error("--shared-prefix-len requires --trace")
    if args.prefix_admit < 1:
        ap.error("--prefix-admit must be >= 1")
    if args.mesh:
        from repro.serve.mesh import MeshSpec
        try:
            spec = MeshSpec.parse(args.mesh)
        except ValueError as e:
            ap.error(str(e))
        if spec.size > 1 and not args.paged:
            ap.error("--mesh with more than one device requires --paged "
                     "(the sharding rules cover the paged pool)")
    if not args.trace and (args.ttft_deadline is not None
                           or args.total_deadline is not None
                           or args.watchdog_budget is not None
                           or args.max_restarts is not None):
        ap.error("--ttft-deadline/--total-deadline/--watchdog-budget/"
                 "--max-restarts require --trace (wave mode has no "
                 "scheduler)")
    if (args.store_gc is not None or args.store_gc_rows is not None) \
            and not args.store:
        ap.error("--store-gc/--store-gc-rows need --store (nothing to "
                 "prune otherwise)")

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    from repro.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    sp = SamplingParams(temperature=args.temperature)

    if args.trace:
        stats = run_trace(cfg, params, args, sp=sp)
        kind = "paged" if stats["paged"] else "dense"
        print(f"engine={stats['engine']} ({kind}) "
              f"requests={stats['n_requests']} "
              f"(+{stats['n_rejected']} shed) tokens={stats['gen_tokens']} "
              f"traces={stats['trace_counts']}"
              + (f" paged_attn_impl={stats['paged_attn_impl']}"
                 if stats["paged"] else ""))
        if stats.get("mesh"):
            occ = ", ".join(f"{x*100:.0f}%" for x in stats["group_occupancy"])
            print(f"mesh={stats['mesh']} groups={stats['device_groups']} "
                  f"group_occupancy=[{occ}] per_device_pool="
                  f"{stats.get('per_device_pool_bytes', 0)}B "
                  f"kv_budget={stats.get('kv_budget_tokens', 0)} tokens")
        if stats["paged"]:
            print(f"reserve={stats['reserve']} "
                  f"preempts={stats['preempt_count']} "
                  f"resume_tokens_recomputed="
                  f"{stats['resume_tokens_recomputed']} "
                  f"admit_deferred={stats['admit_deferred']}")
        if stats["prefix_cache"]:
            print(f"prefix_cache: hit_rate={stats['prefix_hit_rate']*100:.0f}% "
                  f"pages_shared={stats['pages_shared']} "
                  f"cow_copies={stats['cow_copies']}")
        if (args.ttft_deadline is not None or args.total_deadline is not None
                or args.watchdog_budget is not None):
            print(f"deadlines: enforce={stats['enforce_deadlines']} "
                  f"goodput={stats['goodput_tok_per_s']:.1f} tok/s "
                  f"shed(queue={stats['shed_queue_full']} "
                  f"never_fits={stats['shed_never_fits']} "
                  f"deadline={stats['shed_deadline']}) "
                  f"expired={stats['n_expired']} failed={stats['n_failed']} "
                  f"watchdog_trips={stats['watchdog_trips']} "
                  f"failovers={stats['group_failovers']}")
        print(f"tok/s={stats['tok_per_s']:.1f} "
              f"ttft p50={stats['ttft_p50_s']*1e3:.1f}ms "
              f"p95={stats['ttft_p95_s']*1e3:.1f}ms "
              f"lat p50={stats['lat_p50_s']*1e3:.1f}ms "
              f"p95={stats['lat_p95_s']*1e3:.1f}ms "
              f"occupancy={stats['occupancy']*100:.0f}%")
        _maybe_store_gc(args, live_rids=stats.get("suspended_rids", ()))
        return stats
    run_waves(cfg, params, args, sp=sp)
    _maybe_store_gc(args)
    return None


def _maybe_store_gc(args, live_rids=()) -> None:
    """Post-run job-store hygiene (``--store-gc`` / ``--store-gc-rows``).

    ``live_rids`` — rids still suspended on THIS run's scheduler; their
    durable recovery rows are exempt from the age prune (they are live
    recovery state, not orphans of a dead master)."""
    if args.store_gc is None and getattr(args, "store_gc_rows", None) is None:
        return
    from repro.core.store import JobStore
    from repro.serve import HyParRequestTracker
    store = JobStore(args.store)
    try:
        exempt = [f"{HyParRequestTracker.STORE_PREFIX}{rid}"
                  for rid in live_rids]
        pruned = store.gc(max_age_s=args.store_gc,
                          max_rows=args.store_gc_rows,
                          exempt_requests=exempt)
        print(f"store gc: pruned {pruned['rows']} done row(s), "
              f"{pruned['spill_files']} spill file(s), "
              f"{pruned['request_rows']} stale request row(s) from "
              f"{args.store}")
    finally:
        store.close()


if __name__ == "__main__":
    main()
