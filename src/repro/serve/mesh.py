"""Serve-side device mesh + paged-cache sharding (DESIGN.md §13).

One :class:`~repro.serve.engine.PagedEngine` instance spans a device mesh
``(dp, tp)`` with axes ``("data", "model")``:

* **tensor parallel** (``"model"`` axis) — the KV page pools shard over
  their ``kv_heads`` axis, so one model replica's decode splits per-KV-head
  attention across devices (GQA groups are device-local; only the output
  projection reduces across the axis).  Params stay replicated — this is
  honest TP of the cache + compute, not model replication.
* **data parallel** (``"data"`` axis) — batch slots and the page pool
  partition over device groups.  Each group owns a contiguous slot range
  and a private page range behind its own ``PageAllocator``
  (:class:`~repro.serve.scheduler.DeviceGroup`), so allocation, prefix
  caching, COW and preemption never cross a group boundary.

The constraints themselves live in the model code as ``logical(...)``
annotations (``gather_pages``, ``_write_kv_paged``/``_write_kv_chunk_paged``)
that are no-ops outside a ``use_rules`` context — single-device serving
compiles byte-identical HLO to before.  A mesh of total size 1 resolves
every rule to a trivial (fully-replicated) spec, so mesh==1 is bit-identical
to mesh==None by construction (asserted in tests/test_serve_sharded.py).
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding

from repro.parallel.sharding import DEFAULT_RULES, ShardingRules

__all__ = ["MeshSpec", "build_serve_mesh", "serve_rules", "shard_paged_cache",
           "per_device_pool_bytes"]


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Parsed ``--mesh TP,DP``: tensor-parallel × data-parallel extents."""

    tp: int = 1
    dp: int = 1

    @property
    def size(self) -> int:
        return self.tp * self.dp

    @classmethod
    def parse(cls, text: str) -> "MeshSpec":
        parts = [p.strip() for p in str(text).split(",")]
        if len(parts) != 2:
            raise ValueError(f"--mesh wants 'TP,DP' (e.g. '2,1'), got "
                             f"{text!r}")
        try:
            tp, dp = int(parts[0]), int(parts[1])
        except ValueError:
            raise ValueError(f"--mesh wants two integers 'TP,DP', got "
                             f"{text!r}") from None
        if tp < 1 or dp < 1:
            raise ValueError(f"mesh extents must be >= 1, got tp={tp} dp={dp}")
        return cls(tp=tp, dp=dp)


def build_serve_mesh(spec: MeshSpec) -> Mesh:
    """Mesh ``(dp, tp)`` over axes ``("data", "model")`` — the same axis
    names training uses, so ``DEFAULT_RULES`` applies unchanged."""
    n_dev = len(jax.devices())
    if spec.size > n_dev:
        raise ValueError(
            f"mesh {spec.tp}x{spec.dp} needs {spec.size} devices, "
            f"{n_dev} visible — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={spec.size} "
            f"before jax initialises")
    return jax.make_mesh((spec.dp, spec.tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def serve_rules() -> dict:
    """Logical-axis rules for serving: DEFAULT_RULES already carries the
    serve axes (``kv_heads`` -> model, ``slots``/``pages`` -> data)."""
    return dict(DEFAULT_RULES)


def _put(x, rules: ShardingRules, names):
    return jax.device_put(
        x, NamedSharding(rules.mesh, rules.spec_for(names, x.shape)))


def _shard_block(bc, rules: ShardingRules, *, stacked: bool):
    """Shard one block's cache leaves.  Attention pools ``(…, P, KV, ps, D)``
    shard pages->data, kv_heads->model; SSM blocks are dense per-slot state
    whose batch axis shards slots->data.  ``stacked`` prepends a group axis."""
    lead = [None] if stacked else []
    if isinstance(bc, dict) and "self" in bc:
        names = lead + ["pages", "kv_heads", None, None]
        return {**bc, "self": {k: _put(v, rules, names)
                               for k, v in bc["self"].items()}}

    def ssm_leaf(x):
        ax = 1 if stacked else 0
        names = [None] * x.ndim
        if x.ndim > ax:
            names[ax] = "slots"
        return _put(x, rules, names)

    return jax.tree.map(ssm_leaf, bc)


def shard_paged_cache(cache, rules: ShardingRules):
    """Place an ``init_paged_cache`` tree onto the mesh.  Non-dividing axes
    (odd page counts, kv_heads < tp) fall back to replication leaf-by-leaf
    — ``spec_for`` drops them — so this never fails, it just shards less."""
    return {
        "groups": [_shard_block(bc, rules, stacked=True)
                   for bc in cache["groups"]],
        "tail": [_shard_block(bc, rules, stacked=False)
                 for bc in cache["tail"]],
        "len": _put(cache["len"], rules, ["slots"]),
    }


def per_device_pool_bytes(cache) -> int:
    """Max bytes of attention page pool resident on any one device — the
    per-device KV budget the ``serve_sharded`` BENCH row compares (TP=2
    halves it when kv_heads divides; 1 device == total pool bytes)."""
    per_dev: dict = {}
    for part in ("groups", "tail"):
        for bc in cache[part]:
            if not (isinstance(bc, dict) and "self" in bc):
                continue
            for arr in bc["self"].values():
                for sh in arr.addressable_shards:
                    per_dev[sh.device] = (per_dev.get(sh.device, 0)
                                          + sh.data.nbytes)
    return max(per_dev.values()) if per_dev else 0
