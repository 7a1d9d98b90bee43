"""Train-step construction: grad accumulation, remat, optimizer, schedule.

``make_train_step`` builds the fused SPMD step (one jit'd program) — this is
what the paper's framework would assemble from the job graph
(DATA → GRAD×microbatches (no_send_back) → OPT); the HyPar-scheduled
variant that literally goes through the JobGraph/SpmdExecutor lives in
``repro/train/hypar_loop.py`` and is compared against this fused step
(framework-vs-tailored, the paper's Fig. 3 experiment shape).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.transformer import init_params, loss_fn
from repro.optim import OptimizerSpec, cosine_schedule, init_opt_state, opt_update
from repro.parallel.sharding import current_rules, logical


def _constrain_like_params(tree):
    """Pin a params-shaped tree (e.g. per-microbatch gradients) to the
    parameter shardings.  Without this GSPMD all-reduces FULL fp32 weight
    gradients per microbatch per layer instead of reduce-scattering to the
    FSDP shard — a 16x collective-bytes difference on the 16x16 mesh
    (EXPERIMENTS.md §Perf, llama3 train H-grad)."""
    if current_rules() is None:
        return tree
    from repro.parallel.partition import tree_logical_axes
    axes = tree_logical_axes(tree, kind="params")
    return jax.tree.map(
        lambda x, a: logical(x, *a) if hasattr(x, "ndim") else x,
        tree, axes, is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))

__all__ = ["TrainState", "make_train_step", "make_init_fn"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @staticmethod
    def create(cfg: ModelConfig, spec: OptimizerSpec, key) -> "TrainState":
        params = init_params(cfg, key)
        return TrainState(params=params,
                          opt_state=init_opt_state(spec, params),
                          step=jnp.zeros((), jnp.int32))


def make_init_fn(cfg: ModelConfig, spec: OptimizerSpec):
    def init_fn(key):
        return TrainState.create(cfg, spec, key)
    return init_fn


def _split_microbatches(batch: dict, n: int) -> dict:
    def re(x):
        B = x.shape[0]
        assert B % n == 0, f"batch {B} not divisible by {n} microbatches"
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(re, batch)


def make_train_step(cfg: ModelConfig, spec: OptimizerSpec, *,
                    grad_accum: int | None = None,
                    schedule: Callable | None = None,
                    impl: str = "auto"):
    """Returns ``step(state, batch) -> (state, metrics)`` — pure, jit-able.

    grad_accum > 1: microbatches are scanned with fp32 gradient
    accumulation; the cross-replica gradient reduction happens once per
    step (communication-avoidance — the paper's ``no_send_back`` applied to
    gradients, DESIGN.md §4).
    """
    accum = grad_accum if grad_accum is not None else cfg.grad_accum

    def lf(params, batch):
        return loss_fn(cfg, params, batch, impl=impl)

    vg = jax.value_and_grad(lf, has_aux=True)

    def step_fn(state: TrainState, batch: dict):
        if accum <= 1:
            (loss, metrics), grads = vg(state.params, batch)
        else:
            micro = _split_microbatches(batch, accum)

            def one(carry, mb):
                gacc, lacc = carry
                (l, m), g = vg(state.params, mb)
                # NOTE: pinning g to the param shardings here was tried and
                # REFUTED (+22% HBM, no AR->RS conversion) — see §Perf
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state.params)
            (gsum, lsum), ms = jax.lax.scan(one, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
            metrics = jax.tree.map(lambda x: x[-1], ms)

        lr = schedule(state.step) if schedule is not None else spec.lr
        new_params, new_opt, om = opt_update(spec, grads, state.opt_state,
                                             state.params, lr)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss_total"] = loss
        return TrainState(params=new_params, opt_state=new_opt,
                          step=state.step + 1), metrics

    return step_fn
