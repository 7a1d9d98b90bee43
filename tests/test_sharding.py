"""Sharding-rule properties + multi-device subprocess tests (the main
pytest process keeps 1 device; mesh cases run in children with
--xla_force_host_platform_device_count)."""
import subprocess
import sys
import textwrap

import hypothesis.strategies as st
import jax
import pytest
from hypothesis import given, settings

from repro.parallel.sharding import DEFAULT_RULES, ShardingRules


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def rules(meshshape):
    return ShardingRules(mesh=FakeMesh(meshshape), rules=dict(DEFAULT_RULES))


@given(st.integers(1, 64), st.integers(1, 64), st.integers(0, 4))
@settings(max_examples=60, deadline=None)
def test_spec_never_violates_divisibility(d0, d1, pick):
    """For any tensor dims, every mesh axis in the resolved spec divides the
    corresponding dim — the safety property GSPMD requires."""
    r = rules({"pod": 2, "data": 4, "model": 8})
    names = [("batch", None), ("batch", "d_ff"), ("vocab", "embed_fsdp"),
             ("heads", None), ("experts", "d_ff")][pick]
    spec = r.spec_for(names, dims=(d0, d1))
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= r.mesh.shape[a]
        assert (d0, d1)[i] % size == 0


def test_no_axis_reused_across_dims():
    r = rules({"data": 4, "model": 4})
    spec = r.spec_for(("seq", "heads"), dims=(16, 16))  # both want "model"
    axes = [a for entry in spec if entry
            for a in (entry if isinstance(entry, tuple) else (entry,))]
    assert len(axes) == len(set(axes))


def test_missing_mesh_axis_is_dropped():
    r = rules({"data": 4, "model": 4})          # no "pod"
    spec = r.spec_for(("batch", None), dims=(8, 8))
    assert spec == jax.sharding.PartitionSpec("data")


def test_param_tree_axes_cover_all_leaves():
    """Every leaf of every arch's param tree resolves to a sharding."""
    from repro.configs import ARCHS, get_smoke_config
    from repro.models.transformer import init_params
    from repro.parallel.partition import tree_logical_axes

    for arch in ARCHS[:4]:
        cfg = get_smoke_config(arch)
        params = jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.random.PRNGKey(0))
        axes = tree_logical_axes(params, kind="params")
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_p) == len(flat_a)
        for leaf, ax in zip(flat_p, flat_a):
            assert len(ax) == len(leaf.shape), (arch, ax, leaf.shape)


def test_vocab_and_ff_sharded_on_model():
    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.parallel.partition import tree_logical_axes
    cfg = get_config("qwen2-1.5b")
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    axes = tree_logical_axes(params, kind="params")
    assert axes["embed"]["table"] == ("vocab", "embed_fsdp")
    up = axes["groups"][0]["mlp"]["up"]["w"]
    assert up == (None, "embed_fsdp", "d_ff")   # stacked layer dim + TP


SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import dataclasses
    from repro.models.config import ModelConfig
    from repro.models.transformer import init_params, forward
    from repro.parallel.sharding import use_rules, DEFAULT_RULES
    from repro.parallel.partition import tree_shardings
    from repro.parallel.sharding import ShardingRules
    from repro.train import TrainState, make_train_step
    from repro.optim import OptimizerSpec
    from repro.data import SyntheticLMStream, DataConfig

    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      n_experts=4, top_k=2, moe_d_ff=96,
                      compute_dtype="float32")
    spec = OptimizerSpec(kind="adamw", lr=1e-3)
    dc = DataConfig(global_batch=8, seq_len=32)
    stream = SyntheticLMStream(cfg, dc)
    batches = [jax.tree.map(jnp.asarray, stream.batch(s)) for s in range(3)]

    # single-device reference
    state0 = TrainState.create(cfg, spec, jax.random.PRNGKey(0))
    step0 = jax.jit(make_train_step(cfg, spec))
    s_ref = state0
    for b in batches:
        s_ref, m_ref = step0(s_ref, b)

    # 4x2 mesh (EP over model for 4 experts? model=2 divides 4: EP engaged)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = ShardingRules(mesh=mesh, rules=dict(DEFAULT_RULES))
    with use_rules(mesh, rules.rules):
        state_struct = jax.eval_shape(
            lambda k: TrainState.create(cfg, spec, k), jax.random.PRNGKey(0))
        sh = tree_shardings(state_struct, rules, kind="state")
        step1 = jax.jit(make_train_step(cfg, spec),
                        in_shardings=(sh, None), out_shardings=(sh, None))
        s_mesh = jax.jit(lambda k: TrainState.create(cfg, spec, k),
                         out_shardings=sh)(jax.random.PRNGKey(0))
        for b in batches:
            s_mesh, m_mesh = step1(s_mesh, b)

    d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(s_ref.params),
                            jax.tree.leaves(s_mesh.params)))
    assert d < 5e-3, f"param divergence {d}"
    print("MESH_PARITY_OK", d)
""")


def test_sharded_training_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin"}, cwd=".")
    assert "MESH_PARITY_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


SPMD_EXEC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (ChunkedData, ChunkRef, FunctionRegistry, Job,
                            JobGraph, SpmdExecutor, IterativeSpec)

    reg = FunctionRegistry()
    @reg.chunkwise(1)
    def square(c):
        return c * c

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = JobGraph()
    g.add_segment([Job("J1", 1, 0), Job("J2", 1, 0)])
    g.bind_input("J1", np.arange(16, dtype=np.float32).reshape(16, 1), n_chunks=16)
    g.bind_input("J2", np.arange(8, dtype=np.float32).reshape(8, 1), n_chunks=8)
    ex = SpmdExecutor(mesh, reg, chunk_axes=("data",))
    res, report = ex.run(g)
    assert report.mode == "spmd" and len(report.segments) == 1
    np.testing.assert_allclose(np.asarray(res["J1"]).ravel(),
                               (np.arange(16) ** 2))
    # fused while_loop iteration
    spec = IterativeSpec(body=lambda c: c * 0.5,
                         cond=lambda c: jnp.max(c) > 1.0, max_iters=100)
    final, iters = ex.run_iterative(spec, jnp.asarray([64.0]))
    assert iters == 6 and float(final[0]) == 1.0, (iters, final)
    print("SPMD_EXEC_OK")
""")


def test_spmd_executor_multidevice():
    r = subprocess.run([sys.executable, "-c", SPMD_EXEC], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin"}, cwd=".")
    assert "SPMD_EXEC_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
