#!/usr/bin/env python3
"""Smoke check that both main paths of the system run on a TPU.

    python chip_smoke.py               # one chip: all one-chip phases
    python chip_smoke.py --four-chips  # only the phases that span four chips

One chip, each phase through the entry point a user calls:

* ``device`` — fail unless JAX's first device is a TPU.
* ``jacobi`` — the paper's Jacobi solver at its largest Fig. 3 size
  (``make_system(7209)``): ``jacobi_hypar`` (the HyPar job graph on the
  ``LocalExecutor``, 4 row chunks) and ``jacobi_tailored(kernel=True)`` (the
  fused Pallas sweep), each checked against a plain float32 numpy loop of
  the same iteration count.
* ``serve`` — ``repro.launch.serve.main`` on qwen2-1.5b at its published
  widths: paged KV, HyPar request scheduling, ``--paged-attn-impl auto``.
  Every request must complete, with no watchdog trip, failover or failure,
  through one compiled decode program running the paged Pallas kernel.
* ``kernel`` — one decode step's logits on one paged cache, computed by the
  paged flash-decode kernel and by the gather path, compared.

Four chips (``--four-chips``): ``jacobi_hypar`` with its workers on four
chips against the same graph on one chip, and serving on a ``2,2`` mesh
(TP 2 over qwen2's 2 KV heads x 2 data-parallel device groups) against a
``1,1`` mesh.

Everything runs in this one process: a chip belongs to one process at a
time.  Weights come from ``init_params`` and ``--seed``, data from
``make_system``; nothing is read from outside the checkout (the kernel tune
cache is pointed at an empty file under the output directory, so every
kernel runs its default blocks).  A failed check raises, so the script exits
non-zero and prints no result line.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

JACOBI_N = 7209          # largest Fig. 3 size of the paper
JACOBI_ITERS = 20
# float32 matmuls on a TPU may round their operands to bfloat16 at default
# precision (2^-9 relative per product); a wrong block, pad or chunk
# boundary shows up as an O(1) error
JACOBI_RTOL = 1e-2
# the same job graph on one chip and on four runs the same programs on the
# same data: only placement differs
JACOBI_PLACEMENT_RTOL = 1e-5
# kernel vs gather: both paths accumulate in float32 but round attention
# outputs and logits to bfloat16 (8 mantissa bits), and 28 layers carry
# those roundings to the logits; 3% of the largest logit is ~8 bfloat16
# ulps there, while a wrong page, mask or head mapping moves logits by
# their own scale
LOGIT_RTOL = 3e-2

SERVE_ARGS = ["--arch", "qwen2-1.5b", "--trace", "--paged",
              "--engine", "hypar", "--batch", "8", "--n-requests", "8",
              "--prompt-lens", "64", "256", "--max-new", "16",
              "--page-size", "16", "--prefill-chunk", "64"]
N_REQUESTS, MAX_NEW, PROMPT_LENS = 8, 16, (64, 256)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


class Clock:
    """Wall time and JAX compile time (tracing, lowering and backend
    compilation, from JAX's monitoring events) per phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.compile_s = 0.0
        self.phases: dict[str, dict] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        rec = {"wall_s": time.perf_counter() - t0,
               "compile_s": self.compile_s - c0}
        self.phases[name] = rec
        print(f"== {name} done: wall {rec['wall_s']:.2f} s, "
              f"compile {rec['compile_s']:.2f} s", flush=True)


def device_check(jax, need: int):
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is on "
                 f"platform {d.platform!r}")
    if len(devs) < need:
        sys.exit(f"chip_smoke: --four-chips needs {need} TPU chips, JAX "
                 f"sees {len(devs)}")
    return devs


def memory_line(devs) -> str:
    parts = []
    for d in devs:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use', 0)} "
                     f"peak={st.get('peak_bytes_in_use', 0)}")
    return "memory_stats " + " | ".join(parts)


def release() -> None:
    """Engines hold their weights through reference cycles (jitted closures
    over the engine); collect them before the next phase allocates."""
    gc.collect()


# ---------------------------------------------------------------------------
# Jacobi
# ---------------------------------------------------------------------------


def numpy_jacobi(A, b, iters: int):
    import numpy as np
    A = np.asarray(A, np.float32)
    b = np.asarray(b, np.float32)
    d = np.diag(A).copy()
    x = np.zeros_like(b)
    for _ in range(iters):
        x = x + (b - A @ x) / d
    return x, float(np.linalg.norm(b - A @ x))


def rel_err(x, ref) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(x, np.float64) - ref))
                 / np.max(np.abs(ref)))


def sweep_devices(job_devices) -> dict:
    """Chunk index -> sorted devices its sweep jobs (``S<k>_<i>``) ran on."""
    out: dict[int, set] = {}
    for name, dev in job_devices.items():
        if name.startswith("S"):
            out.setdefault(int(name.split("_")[1]), set()).add(str(dev))
    return {i: sorted(v) for i, v in sorted(out.items())}


def phase_jacobi(seed: int) -> None:
    from repro.apps.jacobi import jacobi_hypar, jacobi_tailored, make_system
    A, b, _ = make_system(JACOBI_N, seed=seed)
    x_ref, res_ref = numpy_jacobi(A, b, JACOBI_ITERS)
    print(f"jacobi n={JACOBI_N} iters={JACOBI_ITERS} numpy float32 residual "
          f"{res_ref:.6e}")
    hyp = jacobi_hypar(A, b, iters=JACOBI_ITERS, n_chunks=4)
    tail = jacobi_tailored(A, b, iters=JACOBI_ITERS, kernel=True)
    for name, r in (("jacobi_hypar", hyp), ("jacobi_tailored_kernel", tail)):
        err = rel_err(r.x, x_ref)
        print(f"{name}: iters={r.iters} residual={r.residual:.6e} "
              f"max_rel_err_vs_numpy={err:.3e} (tol {JACOBI_RTOL}) "
              f"wall={r.seconds:.4f} s")
        check(r.iters == JACOBI_ITERS, f"{name} ran {r.iters} iterations")
        check(err <= JACOBI_RTOL, f"{name} differs from numpy by {err:.3e}")
    print(f"jacobi_hypar report: {hyp.extra['report'].summary()} "
          f"moved_bytes={hyp.extra['moved_bytes']} "
          f"sweep devices={sweep_devices(hyp.extra['job_devices'])}")


def phase_jacobi_four(seed: int, devs) -> None:
    from repro.apps.jacobi import jacobi_hypar, make_system
    from repro.core import VirtualCluster
    A, b, _ = make_system(JACOBI_N, seed=seed)
    x_ref, _ = numpy_jacobi(A, b, JACOBI_ITERS)
    runs = {}
    for label, ds in (("one_chip", devs[:1]), ("four_chips", devs[:4])):
        r = jacobi_hypar(A, b, iters=JACOBI_ITERS, n_chunks=4,
                         cluster=VirtualCluster(ds, max_workers=4))
        runs[label] = r
        where = sweep_devices(r.extra["job_devices"])
        print(f"jacobi_hypar[{label}]: residual={r.residual:.6e} "
              f"max_rel_err_vs_numpy={rel_err(r.x, x_ref):.3e} "
              f"wall={r.seconds:.4f} s moved_bytes={r.extra['moved_bytes']}")
        for i, dl in where.items():
            print(f"  sweep chunk {i}: devices {dl}")
        check(rel_err(r.x, x_ref) <= JACOBI_RTOL,
              f"jacobi_hypar[{label}] differs from numpy")
    used = {d for dl in sweep_devices(
        runs["four_chips"].extra["job_devices"]).values() for d in dl}
    print(f"jacobi_hypar[four_chips] sweep jobs used {len(used)} devices")
    check(len(used) == 4, f"four-chip sweeps ran on {sorted(used)}")
    err = rel_err(runs["four_chips"].x, runs["one_chip"].x)
    print(f"jacobi_hypar four_chips vs one_chip: max_rel_err={err:.3e} "
          f"(tol {JACOBI_PLACEMENT_RTOL})")
    check(err <= JACOBI_PLACEMENT_RTOL, "four-chip Jacobi differs from one")


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def run_serve(extra: list[str], name: str, seed: int) -> dict:
    from repro.launch import serve as serve_cli
    stats = serve_cli.main(SERVE_ARGS + ["--seed", str(seed), "--stats-json",
                                         os.path.join(OUT, f"{name}.json")]
                           + extra)
    release()
    outcomes = stats["outcomes"]
    print(f"{name}: outcomes={outcomes} watchdog_trips="
          f"{stats['watchdog_trips']} failovers={stats['group_failovers']} "
          f"n_failed={stats['n_failed']} trace_counts={stats['trace_counts']} "
          f"paged_attn_impl={stats['paged_attn_impl']} "
          f"tok/s={stats['tok_per_s']:.1f} "
          f"ttft_p50={stats['ttft_p50_s'] * 1e3:.1f} ms "
          f"per_device_pool_bytes={stats['per_device_pool_bytes']}")
    check(outcomes == {"completed": N_REQUESTS},
          f"{name}: outcomes {outcomes}")
    check(stats["watchdog_trips"] == 0 and stats["group_failovers"] == 0
          and stats["n_failed"] == 0, f"{name}: a watchdog trip, failover "
          f"or failure happened")
    check(stats["trace_counts"]["decode"] == 1,
          f"{name}: decode traced {stats['trace_counts']['decode']} times")
    check(all(len(t) == MAX_NEW for t in stats["outputs"].values()),
          f"{name}: a request generated other than {MAX_NEW} tokens")
    return stats


def phase_serve(seed: int) -> None:
    stats = run_serve([], "serve", seed)
    check(stats["paged_attn_impl"] == "kernel",
          f"serve resolved paged attention to {stats['paged_attn_impl']}")


def phase_kernel_vs_gather(seed: int, kernel_impl: str = "kernel") -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.transformer import decode_step, init_params
    from repro.serve import PagedEngine

    cfg = get_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    eng = PagedEngine(cfg, params, batch=N_REQUESTS,
                      max_len=max(PROMPT_LENS) + MAX_NEW + 8, page_size=16,
                      prefill_chunk=64, attn_impl=kernel_impl,
                      donate_cache=False)
    check(eng.attn_impl == kernel_impl, "engine did not resolve to the kernel")
    rng = np.random.default_rng(seed)
    first = []
    for slot in range(N_REQUESTS):
        L = PROMPT_LENS[slot % len(PROMPT_LENS)]
        toks = rng.integers(0, cfg.vocab_size - 1, (L,)).astype(np.int32)
        page_ids = range(1 + slot * eng.max_pages,
                         1 + (slot + 1) * eng.max_pages)
        logits = eng.insert(slot, toks, page_ids=list(page_ids),
                            max_new=MAX_NEW)
        first.append(int(jnp.argmax(logits[0, -1])))
    step = jax.jit(functools.partial(decode_step, cfg, page_size=16),
                   static_argnames=("paged_impl",))
    pages = jnp.asarray(eng.page_table)

    def greedy(impl):
        """First-step logits and MAX_NEW greedy tokens from the same cache."""
        cache, tok, seq = eng.cache, jnp.asarray(first, jnp.int32)[:, None], []
        for _ in range(MAX_NEW):
            lg, cache = step(params, cache, tok, pages=pages, paged_impl=impl)
            if not seq:
                first_logits = np.asarray(lg[:, -1], np.float32)
            tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
            seq.append(np.asarray(tok[:, 0]))
        return first_logits, np.stack(seq, axis=1)

    lk, tk = greedy(kernel_impl)
    lr, tr = greedy("ref")
    diff = float(np.max(np.abs(lk - lr)))
    scale = float(np.max(np.abs(lr)))
    first_match = float(np.mean(np.argmax(lk, -1) == np.argmax(lr, -1)))
    seq_match = float(np.mean(tk == tr))
    print(f"kernel vs gather: max|dlogit|={diff:.4e} max|logit|={scale:.4e} "
          f"ratio={diff / scale:.3e} (tol {LOGIT_RTOL}) "
          f"first-step greedy match={first_match:.3f} "
          f"{MAX_NEW}-step greedy match={seq_match:.3f}")
    check(np.isfinite(lk).all(), "kernel logits not finite")
    check(diff <= LOGIT_RTOL * scale,
          f"kernel logits differ from gather by {diff:.4e}")
    del eng, params
    release()


def phase_mesh(seed: int, devs) -> None:
    # an even pool shards over the 2 groups; both meshes run the gather
    # path, since the kernel cannot be partitioned over a mesh (auto
    # resolves to ref on 2,2), so the comparison isolates the mesh
    pool = ["--num-pages", "146", "--paged-attn-impl", "ref"]
    one = run_serve(pool + ["--mesh", "1,1"], "serve_mesh_1x1", seed)
    print(memory_line(devs))
    four = run_serve(pool + ["--mesh", "2,2"], "serve_mesh_2x2", seed)
    print(memory_line(devs))
    same = [one["outputs"][r] == four["outputs"][r] for r in one["outputs"]]
    print(f"mesh 2,2 vs 1,1: identical requests {sum(same)}/{len(same)} "
          f"per_device_pool_bytes {four['per_device_pool_bytes']} vs "
          f"{one['per_device_pool_bytes']} group_occupancy "
          f"{four['group_occupancy']}")
    check(all(same), "mesh 2,2 tokens differ from mesh 1,1")
    check(2 * four["per_device_pool_bytes"] <= one["per_device_pool_bytes"],
          "mesh 2,2 did not halve the per-device pool bytes")
    for d in devs[:4]:
        st = d.memory_stats() or {}
        check(st.get("peak_bytes_in_use", 0) > 0, f"device {d.id} held "
              f"no data")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the phases that span four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    clock = Clock(jax)
    with clock.phase("device"):
        devs = device_check(jax, 4 if args.four_chips else 1)
        os.makedirs(OUT, exist_ok=True)
        tune = os.path.join(OUT, "kernel_tune.json")
        if os.path.exists(tune):
            os.remove(tune)
        os.environ["REPRO_TUNE_CACHE"] = tune
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        with clock.phase("serve_mesh"):
            phase_mesh(args.seed, devs)
        with clock.phase("jacobi_four_chips"):
            phase_jacobi_four(args.seed, devs)
    else:
        with clock.phase("jacobi"):
            phase_jacobi(args.seed)
        with clock.phase("serve"):
            phase_serve(args.seed)
        with clock.phase("kernel_vs_gather"):
            phase_kernel_vs_gather(args.seed)
    print(memory_line(devs))
    print("phases: " + json.dumps(clock.phases))
    print(f"compile seconds total: {clock.compile_s:.2f}")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
