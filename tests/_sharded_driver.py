"""Forced-2-device serving parity driver (run as a subprocess).

The XLA host-device-count flag must be set before jax initialises, and the
main pytest process is long past that — so test_serve_sharded.py runs this
file with ``python tests/_sharded_driver.py <arch> [<arch> ...]``.

For each arch it replays the SAME request trace through a PagedEngine +
ServeScheduler three ways on one two-device process:

* ``base`` — no mesh (today's single-device path),
* ``tp2``  — mesh ``(dp=1, tp=2)``: KV pools sharded over kv_heads,
* ``dp2``  — mesh ``(dp=2, tp=1)`` + two scheduler device groups,

and asserts the generated token streams are identical (TP reassociates the
output-projection reduction, so the guarantee across meshes is
token-identity, not bit-identity of logits — mesh size 1 vs None bit
identity is asserted in-process by test_serve_sharded.py).  For attention
models it also asserts TP=2 halves the per-device page-pool bytes.

Prints ``SHARDED_OK <json>`` on success.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402


def run_variant(cfg, params, mesh, *, device_groups=1, batch=2, num_pages=26):
    from repro.serve import PagedEngine, SamplingParams, ServeScheduler

    eng = PagedEngine(cfg, params, batch=batch, max_len=64, page_size=8,
                      num_pages=num_pages, prefill_chunk=16, mesh=mesh)
    if mesh is not None:
        # weights live replicated on every device of the mesh, not on the
        # one they were made on
        for leaf in jax.tree.leaves(eng.params):
            assert leaf.sharding.device_set == set(mesh.devices.flat)
            assert leaf.sharding.is_fully_replicated
    sched = ServeScheduler(eng, sp=SamplingParams(), reserve="demand",
                           admit_watermark=1, device_groups=device_groups)
    rng = np.random.default_rng(7)
    for _ in range(2 * batch):
        sched.submit(rng.integers(1, 50, 12).astype(np.int32), 6)
    toks = [tuple(r.tokens) for r in sorted(sched.run(), key=lambda r: r.rid)]
    return toks, eng.per_device_pool_bytes(), sched


def main() -> None:
    assert len(jax.devices()) == 2, jax.devices()
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params
    from repro.serve.mesh import MeshSpec, build_serve_mesh

    report = {}
    for arch in sys.argv[1:]:
        cfg = dataclasses.replace(get_smoke_config(arch),
                                  compute_dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        base, base_bytes, _ = run_variant(cfg, params, None)
        tp2, tp2_bytes, _ = run_variant(
            cfg, params, build_serve_mesh(MeshSpec(tp=2, dp=1)))
        dp2, dp2_bytes, dp_sched = run_variant(
            cfg, params, build_serve_mesh(MeshSpec(tp=1, dp=2)),
            device_groups=2)
        assert base == tp2, f"{arch}: TP=2 tokens diverged from 1-device"
        assert base == dp2, f"{arch}: DP=2 tokens diverged from 1-device"
        if base_bytes:          # pure-SSM models have no attention pools
            assert 2 * tp2_bytes == base_bytes, \
                f"{arch}: TP=2 pool bytes {tp2_bytes} not half of {base_bytes}"
        assert len(dp_sched.groups) == 2
        for g in dp_sched.groups:
            assert g.allocator.n_outstanding == 0, \
                f"{arch}: group {g.gid} leaked pages after drain"
        report[arch] = {"n_tokens": sum(len(t) for t in base),
                        "base_bytes": base_bytes, "tp2_bytes": tp2_bytes,
                        "dp2_bytes": dp2_bytes}
    print("SHARDED_OK", json.dumps(report))


if __name__ == "__main__":
    main()
