"""Serving benchmark: request-level continuous batching, dense vs paged.

Two traces, each replayed through ``ServeScheduler`` (the measurement is
``launch/serve.py::run_trace`` — same code path and metric definitions as
the CLI):

* the **smoke trace** (short prompts, PR-3 continuity): ``serve_direct``
  and ``serve_hypar`` rows — the hypar row's ``overhead_pct`` is the cost
  of routing every request through the job machinery, with admission waves
  placed by ONE batched ``plan_segment`` call (PR 4; was one call per
  request at ~25%).
* the **mixed trace** (short + long prompts — the ragged workload the
  paper's job model exists for): ``serve_direct_mixed`` is the dense
  baseline, ``serve_direct_paged``/``serve_hypar_paged`` run the paged KV
  cache + chunked prefill path at the SAME batch and the dense engine's
  exact KV byte budget; ``serve_paged_preempt`` reruns the mixed trace on
  a page pool HALVED to below the working set, comparing full-lifetime
  reservation (which must defer admissions) against reserve-on-demand +
  vLLM-style preemption at equal pool bytes — extras ``preempt_count``,
  ``resume_tokens_recomputed`` and ``speedup_vs_lifetime_pct``
  (DESIGN.md §10).
* the mixed trace again as ``serve_paged_kernel``: paged decode through
  the in-kernel page gather (DESIGN.md §15, ``attn_impl="auto"``) vs the
  materialising gather path at equal pool bytes — a tie on CPU (auto
  resolves to gather off-TPU), the real comparison on TPU; extras
  ``gather_tok_per_s``, ``speedup_vs_gather_pct``.
* the **shared-prefix trace** (every prompt opens with the same system
  prefix): ``serve_prefix_cache`` compares the paged engine with prefix
  caching + copy-on-write page sharing on vs off at equal pool bytes —
  a cache hit's admission prefills only the tail chunk, so the row's
  ``ttft_p50_s``/``ttft_p95_s`` undercut the no-cache references; extras
  ``prefix_hit_rate``, ``pages_shared``, ``cow_copies`` (DESIGN.md §11).  A paged insert is ONE chunk-prefill call writing
  straight into the slot's pages, vs the dense trio (fresh mini-cache +
  bucket-padded prefill + whole-cache splice), at equal decode cost —
  the measured tok/s and TTFT-tail edge.  Paged rows carry
  ``kv_budget_tokens`` (identical to the dense row's), ``n_slots`` and
  the engine trace counters (``chunk_traces``/``decode_traces`` —
  bounded: one chunk program per chunk-length bucket, ONE decode
  program).  Variants under comparison are measured by round-robined
  replays (``compare_engines``) so minute-scale machine drift cannot
  land on one engine.

Row schema (via ``kernel_bench.bench_row``; ``median_s`` is the median
per-token decode latency so the serve trajectory is comparable across PRs
like every other suite)::

    name=serve_<variant>  median_s=<p50 token latency>
    extras: tok_per_s, ttft_p50_s, ttft_p95_s, lat_p50_s, lat_p95_s,
            occupancy, n_requests, gen_tokens, overhead_pct vs direct

Run via ``python -m benchmarks.run --suite serve [--smoke]``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import jax

from .kernel_bench import bench_row


@dataclasses.dataclass
class _Args:
    """The subset of launch/serve.py CLI args run_trace consumes."""
    engine: str
    batch: int
    strategy: str
    prompt_lens: tuple
    max_pending: int | None
    n_requests: int
    rate: float
    max_new: int
    seed: int
    paged: bool = False
    page_size: int = 16
    num_pages: int | None = None
    prefill_chunk: int = 64
    reserve: str = "lifetime"
    preempt_policy: str = "fewest"
    admit_watermark: int = 0
    max_new_mix: tuple | None = None
    prefix_cache: bool = False
    shared_prefix_len: int = 0
    # paged decode attention impl (DESIGN.md §15): auto = kernel on TPU,
    # gather path on CPU
    paged_attn_impl: str = "auto"
    # deadline-aware serving (DESIGN.md §14)
    ttft_deadline: float | None = None
    total_deadline: float | None = None
    enforce_deadlines: bool = True
    watchdog_budget: float | None = None
    max_restarts: int | None = None


def _smoke_args():
    # 24 requests ≈ 200 ms of measured decode — long enough that
    # overhead_pct reflects scheduling cost, not wall-clock noise (the PR-3
    # 8-request trace measured ~45 ms walls, where one OS hiccup was ±20%)
    return dict(batch=4, n_requests=24, max_new=8, prompt_lens=(6, 10, 14))


def _full_args():
    return dict(batch=8, n_requests=48, max_new=32, prompt_lens=(16, 32, 64))


def _smoke_mixed():
    # short + long prompts at batch 8, identical KV byte budget.  Dense pays
    # three dispatches per insert (fresh mini-cache + bucket-padded prefill
    # + whole-cache splice); a paged insert is ONE chunk-prefill call
    # writing straight into the slot's pages (96-token prompts are a single
    # 96 chunk here — multi-chunk interleaving is exercised by the full
    # suite's 256-token prompts and the paged unit tests), which is what
    # buys the tok/s and TTFT-tail edge at equal decode cost.
    return dict(batch=8, n_requests=48, max_new=32, prompt_lens=(8, 16, 96),
                page_size=16, prefill_chunk=96)


def _full_mixed():
    # 256-token prompts split into 2 x 128 chunks with decode steps between
    # them: the long-prompt stall the chunk interleaving policy exists for
    return dict(batch=8, n_requests=48, max_new=32, prompt_lens=(16, 32, 256),
                page_size=16, prefill_chunk=128)


def _smoke_prefix():
    # the system-prompt workload: every prompt opens with the same 64-token
    # prefix (4 pages, 2 chunks) followed by a random remainder.  With the
    # cache on, a hit's admission prefills ONLY the tail chunk (1 x 32 vs
    # 3 x 32 chunks for 80-96-token prompts) — the ttft_p50/p95 edge the
    # acceptance row asserts, at equal pool bytes
    return dict(batch=8, n_requests=24, max_new=16, prompt_lens=(80, 96),
                page_size=16, prefill_chunk=32, shared_prefix_len=64)


def _full_prefix():
    return dict(batch=8, n_requests=48, max_new=32, prompt_lens=(160, 192),
                page_size=16, prefill_chunk=64, shared_prefix_len=128)


def _smoke_constrained():
    # the preemption trace: clients declare a 64-token cap but realised
    # lengths average ~30 (the max_new_mix), so full-lifetime reservation
    # provisions pages most requests never touch; the pool is 40% of the
    # dense footprint — small enough that lifetime must defer admissions
    # and demand must preempt at least once, large enough that recompute
    # stays a sliver of the useful work
    return dict(batch=8, n_requests=24, max_new=64, prompt_lens=(8, 16, 96),
                page_size=16, prefill_chunk=96, max_new_mix=(8, 16, 32, 64))


def _full_constrained():
    return dict(batch=8, n_requests=48, max_new=64,
                prompt_lens=(16, 32, 256), page_size=16, prefill_chunk=128,
                max_new_mix=(8, 16, 32, 64))


def _smoke_overload():
    # the shedding trace: Poisson arrivals at 2x the calibrated service
    # capacity with a TTFT deadline every request declares.  Without
    # shedding the queue grows linearly and late requests burn decode slots
    # on answers nobody is waiting for; deadline-aware admission drops them
    # up front, so the slots serve requests that can still meet their SLO
    return dict(batch=4, n_requests=24, max_new=8, prompt_lens=(8, 16),
                page_size=16, prefill_chunk=32)


def _full_overload():
    return dict(batch=8, n_requests=48, max_new=16, prompt_lens=(8, 16, 32),
                page_size=16, prefill_chunk=64)


def _make_args(engine: str, *, batch, n_requests, max_new, prompt_lens,
               rate_per_s: float = 0.0, seed: int = 0, paged: bool = False,
               page_size: int = 16, num_pages: int | None = None,
               prefill_chunk: int = 64, reserve: str = "lifetime",
               preempt_policy: str = "fewest",
               admit_watermark: int = 0,
               max_new_mix: tuple | None = None,
               prefix_cache: bool = False,
               shared_prefix_len: int = 0,
               paged_attn_impl: str = "auto",
               ttft_deadline: float | None = None,
               total_deadline: float | None = None,
               enforce_deadlines: bool = True,
               watchdog_budget: float | None = None,
               max_restarts: int | None = None) -> _Args:
    return _Args(engine=engine, batch=batch, strategy="greedy",
                 prompt_lens=tuple(prompt_lens), max_pending=None,
                 n_requests=n_requests, rate=rate_per_s, max_new=max_new,
                 seed=seed, paged=paged, page_size=page_size,
                 num_pages=num_pages, prefill_chunk=prefill_chunk,
                 reserve=reserve, preempt_policy=preempt_policy,
                 admit_watermark=admit_watermark, max_new_mix=max_new_mix,
                 prefix_cache=prefix_cache,
                 shared_prefix_len=shared_prefix_len,
                 paged_attn_impl=paged_attn_impl,
                 ttft_deadline=ttft_deadline, total_deadline=total_deadline,
                 enforce_deadlines=enforce_deadlines,
                 watchdog_budget=watchdog_budget, max_restarts=max_restarts)


def run_engine(engine: str, *, cfg, params, repeats: int = 1, **kw) -> dict:
    from repro.launch.serve import run_trace
    from repro.serve import SamplingParams

    return run_trace(cfg, params, _make_args(engine, **kw),
                     sp=SamplingParams(), repeats=repeats)


def compare_engines(variants: dict[str, _Args], *, cfg, params,
                    rounds: int = 3) -> dict[str, dict]:
    """Measure several engine configurations AGAINST machine drift.

    All variants are warmed first, then their measured replays are
    round-robined (A B C A B C …) so a slow minute on a shared box hits
    every variant instead of whichever ran last; each variant reports its
    best replay.  This is what makes overhead_pct / speedup_vs_dense_pct
    numbers in BENCH_serve.json comparisons rather than coin flips.
    """
    from repro.launch.serve import prepare_trace, replay_trace, trace_stats
    from repro.serve import SamplingParams

    prepared = {name: (args, *prepare_trace(cfg, params, args,
                                            sp=SamplingParams()))
                for name, args in variants.items()}
    snaps: dict[str, list] = {name: [] for name in variants}
    for _ in range(max(1, rounds)):
        for name, (_, sched, reqs) in prepared.items():
            snaps[name].append(replay_trace(sched, reqs))
    return {name: trace_stats(args, sched,
                              max(snaps[name], key=lambda s: s[0]))
            for name, (args, sched, _) in prepared.items()}


def _row(name, batch, max_new, s, overhead=0.0, **extra):
    return bench_row(
        name, (batch, max_new), "int32", s["lat_p50_s"],
        tok_per_s=s["tok_per_s"],
        ttft_p50_s=s["ttft_p50_s"], ttft_p95_s=s["ttft_p95_s"],
        lat_p50_s=s["lat_p50_s"], lat_p95_s=s["lat_p95_s"],
        occupancy=s["occupancy"], n_requests=s["n_requests"],
        gen_tokens=s["gen_tokens"], overhead_pct=overhead, **extra)


def _overhead(direct_tok_s, s) -> float:
    if direct_tok_s and s["tok_per_s"] > 0:
        return (direct_tok_s / s["tok_per_s"] - 1.0) * 100.0
    return 0.0


def _run_sharded_variant(name: str, extra_cli: list[str], *,
                         trace_cli: list[str], tmpdir: str) -> dict:
    """One ``launch/serve.py`` run in a forced-2-device subprocess (the
    XLA device-count flag must be set before jax initialises, which this
    already-running process is long past) — returns its ``--stats-json``.

    The child runs on forced host CPU devices, so on an accelerator host it
    would time the CPU under the accelerator's name: refuse there."""
    from repro.kernels.runtime import on_tpu
    if on_tpu():
        raise RuntimeError(
            f"serve_sharded variant {name!r} runs on forced host CPU devices "
            f"in a child process; on this TPU host it would measure the CPU, "
            f"not the chips.  Run the row on a CPU-only host, or drive the "
            f"mesh on the chips in one process (chip_smoke.py --four-chips)")
    out = os.path.join(tmpdir, f"{name}.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(repo, "src"),
                    env.get("PYTHONPATH", "")] if p)
    cmd = [sys.executable, "-m", "repro.launch.serve", "--smoke", "--trace",
           "--paged", "--reserve", "demand", "--admit-watermark", "1",
           "--page-size", "8", "--prefill-chunk", "16",
           "--stats-json", out] + trace_cli + extra_cli
    proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded variant {name!r} failed "
                           f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    with open(out) as f:
        return json.load(f)


def run_sharded(smoke: bool = False) -> dict:
    """The multi-device row (DESIGN.md §13): one 2-device comparison of

    * ``base``  — 1 device, no mesh, pool of P pages on batch B;
    * ``tp2``   — ``--mesh 2,1``: the SAME pool TP-sharded over kv_heads —
      per-device pool bytes must halve;
    * ``dp2``   — ``--mesh 1,2``: batch 2B and pool ~2P split into two
      device groups at EQUAL per-device pool bytes — both groups must do
      nonzero work (``group_occupancy``).

    Forced host devices share one CPU's FLOPs, so ``speedup_vs_1dev_pct``
    records scheduling/collective overhead rather than real speedup — the
    row's value is the invariants (byte halving, group balance) tracked
    over PRs."""
    n_req = 12 if smoke else 24
    base_batch, base_pages = 4, 18          # even page count so DP shards
    trace = ["--prompt-lens", "8", "16", "--max-new", "8",
             "--n-requests", str(n_req), "--seed", "0"]
    with tempfile.TemporaryDirectory() as td:
        base = _run_sharded_variant(
            "base", ["--batch", str(base_batch),
                     "--num-pages", str(base_pages)],
            trace_cli=trace, tmpdir=td)
        tp2 = _run_sharded_variant(
            "tp2", ["--batch", str(base_batch),
                    "--num-pages", str(base_pages), "--mesh", "2,1"],
            trace_cli=trace, tmpdir=td)
        dp2 = _run_sharded_variant(
            "dp2", ["--batch", str(2 * base_batch),
                    "--num-pages", str(2 * base_pages), "--mesh", "1,2"],
            trace_cli=trace, tmpdir=td)
    base_tok_s = base["tok_per_s"]
    return _row(
        "serve_sharded", 2 * base_batch, 8, dp2,
        mesh=dp2["mesh"], device_groups=dp2["device_groups"],
        group_occupancy=dp2["group_occupancy"],
        kv_budget_tokens=dp2["kv_budget_tokens"],
        per_device_pool_bytes=dp2["per_device_pool_bytes"],
        base_per_device_pool_bytes=base["per_device_pool_bytes"],
        tp2_per_device_pool_bytes=tp2["per_device_pool_bytes"],
        tp2_pool_halved=(2 * tp2["per_device_pool_bytes"]
                         == base["per_device_pool_bytes"]),
        base_tok_per_s=base_tok_s,
        tp2_tok_per_s=tp2["tok_per_s"],
        speedup_vs_1dev_pct=(dp2["tok_per_s"] / base_tok_s - 1.0) * 100.0
        if base_tok_s else 0.0)


def run(smoke: bool = False) -> list[dict]:
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params

    kw = _smoke_args() if smoke else _full_args()
    mx = _smoke_mixed() if smoke else _full_mixed()
    cfg = get_smoke_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rows = []

    # -- smoke trace: direct vs hypar (batched-placement overhead) ----------
    stats = compare_engines(
        {"direct": _make_args("direct", **kw),
         "hypar": _make_args("hypar", **kw)}, cfg=cfg, params=params)
    rows.append(_row("serve_direct", kw["batch"], kw["max_new"],
                     stats["direct"]))
    rows.append(_row("serve_hypar", kw["batch"], kw["max_new"],
                     stats["hypar"],
                     _overhead(stats["direct"]["tok_per_s"],
                               stats["hypar"])))

    # -- mixed trace: dense baseline vs paged + chunked prefill -------------
    batch = mx["batch"]
    max_len = max(mx["prompt_lens"]) + mx["max_new"] + 8   # = run_trace's
    kv_budget_tokens = batch * max_len
    # same pool bytes as the dense engine's batch x max_len reservation,
    # split into pages (+ the trash page)
    num_pages = 1 + batch * (-(-max_len // mx["page_size"]))
    paged = dict(mx, paged=True, num_pages=num_pages)
    stats = compare_engines(
        {"dense": _make_args("direct", **mx),
         "paged": _make_args("direct", **paged),
         "hypar_paged": _make_args("hypar", **paged)},
        cfg=cfg, params=params)

    dense_tok_s = stats["dense"]["tok_per_s"]
    rows.append(_row("serve_direct_mixed", batch, mx["max_new"],
                     stats["dense"], kv_budget_tokens=kv_budget_tokens))
    for name, key in (("serve_direct_paged", "paged"),
                      ("serve_hypar_paged", "hypar_paged")):
        s = stats[key]
        rows.append(_row(
            name, batch, mx["max_new"], s,
            _overhead(stats["paged"]["tok_per_s"], s)
            if key == "hypar_paged" else 0.0,
            kv_budget_tokens=kv_budget_tokens, n_slots=batch,
            speedup_vs_dense_pct=(s["tok_per_s"] / dense_tok_s - 1.0)
            * 100.0 if dense_tok_s else 0.0,
            chunk_traces=s["trace_counts"]["chunk_prefill"],
            decode_traces=s["trace_counts"]["decode"]))

    # -- paged-kernel trace: in-kernel page gather (DESIGN.md §15) vs the
    # materialising gather path, same mixed trace at EQUAL pool bytes.
    # attn_impl="auto" resolves to the Pallas kernel on TPU and to the
    # gather path on CPU (the interpreter cannot serve), so on CPU CI the
    # variants tie within round-robin noise — the row exists to carry the
    # TPU comparison and to keep the dispatch plumbing measured.
    stats = compare_engines(
        {"gather": _make_args("direct",
                              **dict(paged, paged_attn_impl="ref")),
         "kernel": _make_args("direct",
                              **dict(paged, paged_attn_impl="auto"))},
        cfg=cfg, params=params)
    ga, kn = stats["gather"], stats["kernel"]
    rows.append(_row(
        "serve_paged_kernel", batch, mx["max_new"], kn,
        kv_budget_tokens=kv_budget_tokens, n_slots=batch,
        attn_impl="auto",
        gather_tok_per_s=ga["tok_per_s"],
        speedup_vs_gather_pct=(kn["tok_per_s"] / ga["tok_per_s"] - 1.0)
        * 100.0 if ga["tok_per_s"] else 0.0,
        chunk_traces=kn["trace_counts"]["chunk_prefill"],
        decode_traces=kn["trace_counts"]["decode"]))

    # -- page-constrained trace: full-lifetime reservation vs
    # reserve-on-demand + preemption at EQUAL pool bytes.  The pool holds
    # 40% of the dense footprint, so lifetime reservation (provisioning the
    # declared 64-token cap) must defer admissions while demand mode admits
    # prompt spans, appends decode pages as realised lengths grow, and
    # preempts (recompute-resume) on exhaustion — more live slots per
    # (full-batch) decode step is the tok/s and TTFT edge.
    cn = _smoke_constrained() if smoke else _full_constrained()
    cbatch = cn["batch"]
    cmax_len = max(cn["prompt_lens"]) + cn["max_new"] + 8
    con_pages = 1 + int(cbatch * (-(-cmax_len // cn["page_size"])) * 0.4)
    con = dict(cn, paged=True, num_pages=con_pages)
    stats = compare_engines(
        {"lifetime": _make_args("direct", **con),
         "preempt": _make_args("direct", **dict(con, reserve="demand"))},
        cfg=cfg, params=params)
    lt, s = stats["lifetime"], stats["preempt"]
    rows.append(_row(
        "serve_paged_preempt", cbatch, cn["max_new"], s,
        kv_budget_tokens=(con_pages - 1) * cn["page_size"],
        pool_pages=con_pages, n_slots=cbatch,
        preempt_count=s["preempt_count"],
        resume_tokens_recomputed=s["resume_tokens_recomputed"],
        admit_deferred=s["admit_deferred"],
        lifetime_tok_per_s=lt["tok_per_s"],
        lifetime_ttft_p95_s=lt["ttft_p95_s"],
        lifetime_admit_deferred=lt["admit_deferred"],
        speedup_vs_lifetime_pct=(s["tok_per_s"] / lt["tok_per_s"] - 1.0)
        * 100.0 if lt["tok_per_s"] else 0.0,
        chunk_traces=s["trace_counts"]["chunk_prefill"],
        decode_traces=s["trace_counts"]["decode"]))

    # -- shared-prefix trace: prefix caching + COW page sharing vs the
    # no-cache paged engine at EQUAL pool bytes (DESIGN.md §11).  Every
    # prompt repeats the same system prefix; with the cache on, admissions
    # after the first wave map the prefix onto shared pool pages and
    # prefill only the tail chunk — lower ttft at the same tok/s budget.
    pf = _smoke_prefix() if smoke else _full_prefix()
    pbatch = pf["batch"]
    pmax_len = max(pf["prompt_lens"]) + pf["max_new"] + 8
    pf_pages = 1 + pbatch * (-(-pmax_len // pf["page_size"]))
    pbase = dict(pf, paged=True, num_pages=pf_pages)
    stats = compare_engines(
        {"nocache": _make_args("direct", **pbase),
         "cache": _make_args("direct", **dict(pbase, prefix_cache=True))},
        cfg=cfg, params=params)
    nc, pc = stats["nocache"], stats["cache"]
    rows.append(_row(
        "serve_prefix_cache", pbatch, pf["max_new"], pc,
        kv_budget_tokens=(pf_pages - 1) * pf["page_size"],
        pool_pages=pf_pages, n_slots=pbatch,
        shared_prefix_len=pf["shared_prefix_len"],
        prefix_hit_rate=pc["prefix_hit_rate"],
        pages_shared=pc["pages_shared"],
        cow_copies=pc["cow_copies"],
        nocache_tok_per_s=nc["tok_per_s"],
        nocache_ttft_p50_s=nc["ttft_p50_s"],
        nocache_ttft_p95_s=nc["ttft_p95_s"],
        ttft_p50_gain_pct=(nc["ttft_p50_s"] / pc["ttft_p50_s"] - 1.0)
        * 100.0 if pc["ttft_p50_s"] else 0.0,
        speedup_vs_nocache_pct=(pc["tok_per_s"] / nc["tok_per_s"] - 1.0)
        * 100.0 if nc["tok_per_s"] else 0.0,
        chunk_traces=pc["trace_counts"]["chunk_prefill"],
        decode_traces=pc["trace_counts"]["decode"]))

    # -- overload trace: deadline-aware shedding vs serve-everything under
    # a 2x-overloaded Poisson trace at EQUAL pool bytes (DESIGN.md §14).
    # A calibration run (same engine, closed-loop) measures sustainable
    # tok/s; the overload trace then arrives at twice the implied request
    # rate with a TTFT deadline sized so roughly the first half of the
    # backlog is meetable.  ``goodput_tok_per_s`` counts only tokens of
    # requests that met every declared deadline — the shedding scheduler
    # must beat the no-shedding baseline on it (tok_per_s alone would
    # reward the baseline for generating tokens nobody is waiting for).
    ov = _smoke_overload() if smoke else _full_overload()
    obatch = ov["batch"]
    omax_len = max(ov["prompt_lens"]) + ov["max_new"] + 8
    ov_pages = 1 + obatch * (-(-omax_len // ov["page_size"]))
    ovp = dict(ov, paged=True, num_pages=ov_pages)
    calib = run_engine("direct", cfg=cfg, params=params, **ovp)
    cap_tok_s = calib["tok_per_s"] or 1.0
    rate = 2.0 * cap_tok_s / ov["max_new"]          # 2x sustainable req/s
    ttft = ov["n_requests"] * ov["max_new"] / (4.0 * cap_tok_s)
    over = dict(ovp, rate_per_s=rate, ttft_deadline=ttft)
    stats = compare_engines(
        {"shed": _make_args("direct", **over),
         "noshed": _make_args("direct",
                              **dict(over, enforce_deadlines=False))},
        cfg=cfg, params=params)
    sh, ns = stats["shed"], stats["noshed"]
    rows.append(_row(
        "serve_overload", obatch, ov["max_new"], sh,
        kv_budget_tokens=(ov_pages - 1) * ov["page_size"],
        pool_pages=ov_pages, n_slots=obatch,
        offered_rate_req_s=rate,
        capacity_tok_per_s=cap_tok_s,
        ttft_deadline_s=ttft,
        goodput_tok_per_s=sh["goodput_tok_per_s"],
        shed_deadline=sh["shed_deadline"],
        shed_queue_full=sh["shed_queue_full"],
        shed_never_fits=sh["shed_never_fits"],
        n_expired=sh["n_expired"],
        noshed_goodput_tok_per_s=ns["goodput_tok_per_s"],
        noshed_tok_per_s=ns["tok_per_s"],
        goodput_gain_pct=(sh["goodput_tok_per_s"]
                          / ns["goodput_tok_per_s"] - 1.0) * 100.0
        if ns["goodput_tok_per_s"] else 0.0))

    # -- sharded trace: TP/DP device-mesh serving in forced-2-device
    # subprocesses (DESIGN.md §13)
    rows.append(run_sharded(smoke))
    return rows
