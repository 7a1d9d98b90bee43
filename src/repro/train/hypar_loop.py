"""Training expressed as a HyPar job graph — the paper's technique applied
to the LM workload (first-class integration, DESIGN.md §4).

Per optimisation step:

  segment GRAD_s : one job per microbatch, ``no_send_back=True`` — gradients
                   are *retained on the workers* (the paper's
                   communication-avoidance) and only fetched by the OPT job;
  segment OPT_s  : reduce + optimizer update, consuming ``R_grad[*]`` and
                   the previous parameters ``R_opt_{s-1}``;
  (optional) a control job re-enqueues the next step's segments — the exact
  dynamic-job pattern the paper introduces for its Jacobi solver.

Pytrees travel through the graph as ChunkedData of flattened leaves; the
treedefs are closed over by the registered functions (workers are "fat":
they contain all user functions, paper §3.2).

The fused SPMD step (repro/train/step.py) is the "tailored" implementation
this is compared against — the shape of the paper's Fig. 3 experiment on
the LM workload.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ChunkedData, ChunkRef, FunctionRegistry, Job, JobGraph,
                        LocalExecutor, VirtualCluster)
from repro.models.config import ModelConfig
from repro.models.transformer import init_params, loss_fn
from repro.optim import OptimizerSpec, init_opt_state, opt_update

__all__ = ["HyParTrainer"]


class HyParTrainer:
    """Paper-faithful scheduled training on the LocalExecutor."""

    def __init__(self, cfg: ModelConfig, spec: OptimizerSpec, *,
                 n_micro: int = 2, cluster: VirtualCluster | None = None,
                 dynamic: bool = True, mode: str = "sync",
                 strategy: str = "greedy",
                 executor_factory: Callable[..., Any] | None = None):
        self.cfg, self.spec, self.n_micro = cfg, spec, n_micro
        self.dynamic = dynamic
        self.mode, self.strategy = mode, strategy
        # executor injection: ``factory(cluster, registry) -> BaseExecutor``
        # swaps the thread-worker LocalExecutor for e.g. the durable
        # ProcessExecutor without the trainer special-casing either
        self.executor_factory = executor_factory
        self.cluster = cluster or VirtualCluster(n_schedulers=1)
        self.registry = FunctionRegistry()
        self._params_def = None
        self._opt_def = None
        self._batches: dict[int, list[dict]] = {}
        self._register()

    # -- registered user functions (paper §3.2) -----------------------------
    def _register(self):
        cfg, spec = self.cfg, self.spec

        def grad_fn(params_cd: ChunkedData, micro_cd: ChunkedData) -> ChunkedData:
            params = jax.tree_util.tree_unflatten(
                self._params_def, params_cd.arrays())
            batch = jax.tree_util.tree_unflatten(
                self._batch_def, micro_cd.arrays())
            (_, _), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
            return ChunkedData.from_arrays(jax.tree.leaves(grads))

        def opt_fn(*cds: ChunkedData) -> ChunkedData:
            params_cd, opt_cd, *grad_cds = cds
            params = jax.tree_util.tree_unflatten(
                self._params_def, params_cd.arrays())
            opt_state = jax.tree_util.tree_unflatten(
                self._opt_def, opt_cd.arrays())
            grads_sum = None
            for gcd in grad_cds:
                g = jax.tree_util.tree_unflatten(self._params_def, gcd.arrays())
                grads_sum = g if grads_sum is None else jax.tree.map(
                    jnp.add, grads_sum, g)
            grads = jax.tree.map(lambda g: g / len(grad_cds), grads_sum)
            new_p, new_o, _ = opt_update(spec, grads, opt_state, params)
            return ChunkedData.from_arrays(
                jax.tree.leaves(new_p) + jax.tree.leaves(new_o))

        def split_state(cd: ChunkedData, which: str) -> ChunkedData:
            n_p = self._params_def.num_leaves
            return ChunkedData(list(cd)[:n_p] if which == "p" else list(cd)[n_p:])

        self.registry.register("grad", grad_fn, kind="whole")
        self.registry.register("opt", opt_fn, kind="whole")
        self.registry.register("take_params",
                               lambda cd: split_state(cd, "p"), kind="whole")
        self.registry.register("take_opt",
                               lambda cd: split_state(cd, "o"), kind="whole")

    # -- graph construction ----------------------------------------------------
    def _one_step_segments(self, graph: JobGraph, s: int, *,
                           params_ref: str, opt_ref: str) -> tuple[str, str]:
        grad_jobs = []
        for m in range(self.n_micro):
            name = f"G{s}_{m}"
            job = Job(name, "grad", 0,
                      (ChunkRef(params_ref), ChunkRef(f"D{s}_{m}")),
                      no_send_back=True)   # paper: grads stay on workers
            grad_jobs.append(job)
        graph.add_segment(grad_jobs)
        opt_name = f"O{s}"
        graph.add_segment([Job(opt_name, "opt", 0,
                               (ChunkRef(params_ref), ChunkRef(opt_ref)) +
                               tuple(ChunkRef(j.name) for j in grad_jobs))])
        p_name, o_name = f"P{s + 1}", f"S{s + 1}"
        graph.add_segment([
            Job(p_name, "take_params", 1, (ChunkRef(opt_name),)),
            Job(o_name, "take_opt", 1, (ChunkRef(opt_name),)),
        ])
        return p_name, o_name

    def run(self, batches: list[list[dict]], key=None) -> tuple[Any, Any, Any]:
        """batches[s][m] = microbatch dict for step s. Returns
        (params, opt_state, report)."""
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        opt_state = init_opt_state(self.spec, params)
        p_leaves, self._params_def = jax.tree_util.tree_flatten(params)
        o_leaves, self._opt_def = jax.tree_util.tree_flatten(opt_state)
        _, self._batch_def = jax.tree_util.tree_flatten(batches[0][0])

        graph = JobGraph()
        graph.add_segment([Job("P0", "take_params", 1, ()),
                           Job("S0", "take_opt", 1, ())])
        full0 = ChunkedData.from_arrays(p_leaves + o_leaves)
        graph.bind_input("P0", full0)
        graph.bind_input("S0", full0)

        p_ref, o_ref = "P0", "S0"
        for s, step_batches in enumerate(batches):
            for m, mb in enumerate(step_batches):
                name = f"D{s}_{m}"
                # data jobs: identity chunkwise over microbatch leaves
                if "data" not in self.registry:
                    self.registry.register("data", lambda *xs: xs[0]
                                           if len(xs) == 1 else xs,
                                           kind="whole")
                graph.add_segment([Job(name, "data", 1, ())])
                graph.bind_input(name, ChunkedData.from_arrays(
                    jax.tree.leaves(mb)))
            p_ref, o_ref = self._one_step_segments(graph, s, params_ref=p_ref,
                                                   opt_ref=o_ref)

        if self.executor_factory is not None:
            executor = self.executor_factory(self.cluster, self.registry)
        else:
            executor = LocalExecutor(self.cluster, self.registry,
                                     mode=self.mode, strategy=self.strategy)
        try:
            results, report = executor.run(graph)
        finally:
            close = getattr(executor, "close", None)
            if close is not None:
                close()
        final_p = jax.tree_util.tree_unflatten(self._params_def,
                                               results[p_ref].arrays())
        final_o = jax.tree_util.tree_unflatten(self._opt_def,
                                               results[o_ref].arrays())
        return final_p, final_o, report
