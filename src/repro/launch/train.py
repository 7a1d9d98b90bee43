"""Training driver.

Two schedulers (selectable): the *fused* SPMD step (tailored) and the
*HyPar* job-graph loop (the paper's framework).  Includes checkpointing
(async, elastic restore), straggler-free deterministic stepping, and a
crash-recovery path: on restart the driver resumes from the newest complete
checkpoint.

Example (the end-to-end deliverable — ~100M-param model, a few hundred
steps)::

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-1.5b --smoke-scale 0 --steps 300 --batch 8 --seq 512 \
        --ckpt-dir /tmp/run1 --data-axis 1 --model-axis 1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.data import DataConfig, Prefetcher, SyntheticLMStream
from repro.models.config import ModelConfig
from repro.optim import OptimizerSpec, cosine_schedule
from repro.parallel.partition import tree_shardings
from repro.parallel.sharding import DEFAULT_RULES, ShardingRules, use_rules
from repro.train import TrainState, make_train_step


def scale_config(cfg: ModelConfig, *, layers: int, d_model: int,
                 seq: int) -> ModelConfig:
    """Scale an assigned arch down to a trainable-on-CPU size (~100M)."""
    repl = {"n_layers": layers, "d_model": d_model, "max_seq": max(seq * 2, 256)}
    if cfg.family in ("ssm", "hybrid"):
        repl["ssm_chunk"] = min(cfg.ssm_chunk, 64)
    if cfg.n_heads > 1:
        repl["n_heads"] = max(4, min(cfg.n_heads, d_model // 64))
        repl["n_kv_heads"] = max(1, min(cfg.n_kv_heads, repl["n_heads"]))
        while repl["n_heads"] % repl["n_kv_heads"]:
            repl["n_kv_heads"] -= 1
        repl["head_dim"] = d_model // repl["n_heads"]
    if cfg.d_ff:
        repl["d_ff"] = d_model * 4
    if cfg.is_moe:
        repl["n_experts"] = min(cfg.n_experts, 8)
        repl["top_k"] = min(cfg.top_k, 2)
        repl["moe_d_ff"] = d_model * 2
    if cfg.family == "encdec":
        repl["n_encoder_layers"] = layers
    return dataclasses.replace(cfg, **repl)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0 = use all devices on the data axis")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config unchanged")
    ap.add_argument("--engine", choices=["fused", "hypar", "proc"],
                    default="fused",
                    help="fused = tailored SPMD step; hypar = the paper's "
                         "job-graph runtime (BaseExecutor, DESIGN.md §2); "
                         "proc = the same job graph on real multiprocessing "
                         "workers with a durable job store (DESIGN.md §12)")
    ap.add_argument("--dispatch", choices=["sync", "pipelined", "dataflow"],
                    default="sync", help="LocalExecutor dispatch mode "
                                         "(hypar/proc engines)")
    ap.add_argument("--placement", choices=["greedy", "cost"], default="greedy",
                    help="master-scheduler placement strategy (hypar engine)")
    ap.add_argument("--store", default="",
                    help="proc engine: sqlite job-store path — results "
                         "persist under content identity, so a killed run "
                         "restarted with --resume skips every job already "
                         "done (default: a fresh temporary store)")
    ap.add_argument("--resume", action="store_true",
                    help="proc engine: reuse an existing --store instead of "
                         "starting it fresh (memoised jobs are served from "
                         "the store, not re-executed)")
    ap.add_argument("--proc-workers", type=int, default=2,
                    help="proc engine: number of worker processes")
    ap.add_argument("--store-gc", type=float, default=None, metavar="SECS",
                    help="after the run, prune done job-store rows older "
                         "than this many seconds (and their spill files)")
    ap.add_argument("--store-gc-rows", type=int, default=None, metavar="N",
                    help="after the run, keep at most N most-recent done "
                         "job-store rows")
    args = ap.parse_args(argv)
    if (args.store or args.resume) and args.engine != "proc":
        ap.error("--store/--resume require --engine proc")
    if args.resume and not args.store:
        ap.error("--resume needs --store (a temporary store has no "
                 "previous run to resume from)")
    if (args.store_gc is not None or args.store_gc_rows is not None) \
            and not args.store:
        ap.error("--store-gc/--store-gc-rows need --store (a temporary "
                 "store is deleted whole when the run ends)")

    enable_compile_cache()
    base = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = base if args.smoke else scale_config(
        base, layers=args.layers, d_model=args.d_model, seq=args.seq)
    n_dev = len(jax.devices())
    data_ax = args.data_axis or max(1, n_dev // args.model_axis)
    mesh = jax.make_mesh((data_ax, args.model_axis), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = ShardingRules(mesh=mesh, rules=dict(DEFAULT_RULES))

    spec = OptimizerSpec(kind=cfg.optimizer, lr=args.lr)
    sched = lambda s: cosine_schedule(s, base_lr=args.lr, warmup=50,
                                      total=args.steps)
    dc = DataConfig(seed=args.seed, global_batch=args.batch, seq_len=args.seq)
    stream = SyntheticLMStream(cfg, dc)

    if args.engine in ("hypar", "proc"):
        return _run_hypar(cfg, spec, stream, args)

    with use_rules(mesh, rules.rules):
        step_fn = make_train_step(cfg, spec, grad_accum=args.grad_accum,
                                  schedule=sched)
        state_struct = jax.eval_shape(
            lambda k: TrainState.create(cfg, spec, k), jax.random.PRNGKey(args.seed))
        state_sh = tree_shardings(state_struct, rules, kind="state")
        jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None), donate_argnums=(0,))

        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            state, start = restore_checkpoint(
                args.ckpt_dir, state_struct,
                sharding_fn=lambda key, leaf: _lookup(state_sh, key))
            print(f"resumed from checkpoint step {start}")
        else:
            state = jax.jit(
                lambda k: TrainState.create(cfg, spec, k),
                out_shardings=state_sh)(jax.random.PRNGKey(args.seed))

        ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        print(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={n_dev} "
              f"mesh=({data_ax},{args.model_axis}) steps={args.steps}")

        it = Prefetcher((stream.batch(s) for s in range(start, args.steps)),
                        depth=2)
        t0 = time.time()
        tokens_done = 0
        for s, host_batch in zip(range(start, args.steps), it):
            batch = jax.tree.map(jnp.asarray, host_batch)
            state, metrics = jitted(state, batch)
            tokens_done += args.batch * args.seq
            if (s + 1) % args.log_every == 0 or s + 1 == args.steps:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                print(f"step {s + 1:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"tok/s {tokens_done / dt:9.0f}")
            if ckpt and (s + 1) % args.ckpt_every == 0:
                ckpt.save(state, s + 1)
        if ckpt:
            ckpt.save(state, args.steps)
            ckpt.wait()
        final_loss = float(metrics["loss"])
        print(f"done: final loss {final_loss:.4f} "
              f"({tokens_done / (time.time() - t0):.0f} tok/s)")
        return final_loss


def _run_hypar(cfg, spec, stream, args) -> float:
    """Drive training through the paper's job-graph runtime.

    Same BaseExecutor contract as every other consumer: the dispatch mode
    and placement strategy are plain LocalExecutor knobs, nothing here
    special-cases them.  ``--engine proc`` swaps the thread workers for the
    durable ProcessExecutor (DESIGN.md §12): the trainer's functions run in
    spawn children via ``repro.train.procfns`` and every job result lands in
    the sqlite store, so a killed run restarted with ``--resume`` replays
    the done prefix as memo hits.
    """
    from repro.train import HyParTrainer

    n_micro = max(1, args.grad_accum)
    mb = max(1, args.batch // n_micro)
    batches = []
    for s in range(args.steps):
        b = stream.batch(s)
        batches.append([{k: jnp.asarray(v[m * mb:(m + 1) * mb])
                         for k, v in b.items()} for m in range(n_micro)])

    factory, made = None, []
    if args.engine == "proc":
        from repro.core import ProcessExecutor, VirtualCluster
        from repro.train import procfns

        store = args.store or None
        if store and not args.resume:
            for stale in (store, store + "-wal", store + "-shm"):
                if os.path.exists(stale):
                    os.remove(stale)
        procfns.export_env(cfg, spec, batch_keys=batches[0][0])
        proc_cluster = VirtualCluster(n_schedulers=1,
                                      max_workers=args.proc_workers)

        def factory(cluster, registry):
            ex = ProcessExecutor(
                cluster, registry, procfns.WORKER_FNS_SPEC, store=store,
                mode=("pipelined" if args.dispatch == "sync"
                      else args.dispatch),
                strategy=args.placement)
            made.append(ex)
            return ex

    trainer = HyParTrainer(cfg, spec, n_micro=n_micro,
                           cluster=(proc_cluster if factory else None),
                           mode=args.dispatch, strategy=args.placement,
                           executor_factory=factory)
    t0 = time.time()
    params, _, report = trainer.run(batches, key=jax.random.PRNGKey(args.seed))
    dt = time.time() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.engine} engine: {args.steps} steps x {n_micro} micro in "
          f"{dt:.1f}s ({args.steps * args.batch * args.seq / dt:.0f} tok/s) "
          f"params={n_params / 1e6:.1f}M | {report.summary()}")
    if made:
        ex = made[0]
        print(f"job store: {ex.n_executed} executed, "
              f"{ex.n_memoised} memoised"
              + (f" (durable at {args.store})" if args.store else ""))
    if args.store and (args.store_gc is not None
                       or args.store_gc_rows is not None):
        from repro.core.store import JobStore
        gc_store = JobStore(args.store)
        try:
            pruned = gc_store.gc(max_age_s=args.store_gc,
                                 max_rows=args.store_gc_rows)
            print(f"store gc: pruned {pruned['rows']} done row(s), "
                  f"{pruned['spill_files']} spill file(s)")
        finally:
            gc_store.close()
    return dt


def _lookup(sh_tree, key: str):
    flat, _ = jax.tree_util.tree_flatten_with_path(sh_tree)
    for path, leaf in flat:
        k = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if k == key:
            return leaf
    raise KeyError(key)


if __name__ == "__main__":
    main()
