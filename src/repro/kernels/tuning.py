"""Kernel autotuning subsystem.

The four Pallas kernel packages ship sensible default block sizes, but the
paper's headline claim (framework within ~10 % of a tailored code) only
holds when the inner loops run at machine speed — and the best block
shape is a property of the *machine*, not the code (OpenFPM makes the
same point for reusable frameworks).  This module provides

* a **block-size search**: time each candidate config with the
  ``_time``-style harness used by ``benchmarks/kernel_bench`` and keep the
  fastest (:meth:`Autotuner.tune`),
* a **persistent JSON cache** keyed by ``(kernel, backend, shape-bucket,
  dtype)`` so the search runs once per machine (:class:`TuningCache`;
  corrupt or truncated cache files are discarded, never fatal),
* **transparent consultation** from every kernel ``ops.py`` wrapper:
  when the caller does not pin block sizes, :meth:`Autotuner.lookup`
  supplies the tuned config (cache-only — wrappers never *time* anything,
  so consulting is safe at jit trace time),
* the **cost-model bridge**: measured kernel times calibrate
  :class:`repro.core.scheduler.CostModelParams`
  (:func:`calibrated_cost_params`) and seed the master scheduler's
  observed-time table (:func:`observed_fn_times` in ``apps/jacobi``), so
  placement uses observed rather than roofline-guessed costs.

Tuning itself is driven from outside jit (``benchmarks/kernel_bench``,
``benchmarks/run --suite kernels``); timing inside a trace would record
tracing time, not kernel time.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import time
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax

__all__ = [
    "DEFAULT_CANDIDATES",
    "TuningCache",
    "Autotuner",
    "get_tuner",
    "shape_bucket",
    "cache_key",
    "default_impl",
    "calibrated_cost_params",
]

# Candidate grids per kernel.  Entries must be valid kwargs of the kernel's
# ops-level wrapper; invalid combinations for a given shape are skipped at
# tune time (the wrapper raises, the tuner moves on).
DEFAULT_CANDIDATES: dict[str, list[dict[str, int]]] = {
    "jacobi_sweep": [{"row_block": r, "col_block": c}
                     for r in (128, 256, 512) for c in (128, 256, 512)],
    "rmsnorm": [{"row_block": r} for r in (64, 128, 256, 512)],
    "flash_attention": [{"q_block": q, "kv_block": k}
                        for q in (128, 256, 512) for k in (128, 256, 512)],
    # the SSD kernel tiles by its (chunk, head) grid and the paged kernel's
    # block follows from its shapes — nothing to search, but timing them
    # populates the cost-model bridge
    "ssd_scan": [{}],
    "paged_attention": [{}],
}

_ENV_CACHE = "REPRO_TUNE_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "kernel_tune.json")


def shape_bucket(shape: Sequence[int]) -> tuple[int, ...]:
    """Round every dim up to the next power of two — one cache entry serves
    the whole bucket, so ragged workload shapes don't explode the cache."""
    return tuple(1 if d <= 1 else 2 ** math.ceil(math.log2(d)) for d in shape)


def default_impl(backend: str) -> str:
    """The impl a *real* run on ``backend`` resolves ``auto`` to — the only
    impl whose timings describe that backend's hardware."""
    return "kernel" if backend == "tpu" else "interpret"


def cache_key(kernel: str, backend: str, shape: Sequence[int], dtype,
              impl: str | None = None) -> str:
    """Five-part key ``kernel|backend|impl|bucket|dtype``.

    ``impl`` is the *resolved* execution path the timing was taken under
    (kernel vs interpret).  Keying by it is what stops backend poisoning:
    a forced-interpret debug run on a TPU host records
    ``...|tpu|interpret|...`` entries that a real kernel lookup
    (``...|tpu|kernel|...``) can never hit.  Unset, it defaults to the
    backend's real impl (:func:`default_impl`).
    """
    bucket = "x".join(str(d) for d in shape_bucket(shape))
    impl = impl or default_impl(backend)
    return (f"{kernel}|{backend}|{impl}|{bucket}|"
            f"{jax.numpy.dtype(dtype).name}")


class TuningCache:
    """Persistent JSON store: key -> {config, median_s, flops, bytes}."""

    def __init__(self, path: str | None = None):
        self.path = path or os.environ.get(_ENV_CACHE) or _DEFAULT_CACHE
        self._entries: dict[str, dict] = {}
        self._loaded = False

    def load(self) -> dict[str, dict]:
        if self._loaded:
            return self._entries
        self._loaded = True
        try:
            with open(self.path) as f:
                raw = json.load(f)
            if isinstance(raw, dict):
                # schema-validate each entry too: a hand-edited or
                # foreign-schema entry must be dropped here, not crash
                # lookup()/observed_s() in every ops wrapper later.
                # Legacy 4-part keys (pre impl-keying) are dropped rather
                # than migrated: they can't say whether they were timed
                # under interpret or the real kernel, which is exactly the
                # ambiguity that poisoned real-backend calibration.
                self._entries = {
                    k: v for k, v in raw.get("entries", raw).items()
                    if isinstance(v, dict)
                    and isinstance(v.get("config"), dict)
                    and isinstance(v.get("median_s"), (int, float))
                    and len(k.split("|")) == 5}
        except (OSError, ValueError):
            # missing, unreadable or corrupt cache — start fresh; tuning is
            # an optimisation, never a correctness dependency
            self._entries = {}
        return self._entries

    def save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # atomic replace so a crash mid-write can't corrupt the cache;
        # never fatal (e.g. read-only FS, or a non-JSON-serializable config
        # value raising TypeError from json.dump) and never leaks the tmp
        fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": 1, "entries": self._entries}, f, indent=1)
            os.replace(tmp, self.path)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def get(self, key: str) -> dict | None:
        return self.load().get(key)

    def put(self, key: str, entry: dict, *, persist: bool = True) -> None:
        self.load()
        self._entries[key] = entry
        if persist:
            self.save()

    def __len__(self) -> int:
        return len(self.load())


class Autotuner:
    """Block-size search + cache consultation.

    ``timer`` is injectable (tests use a seeded stub so selection is
    deterministic); it must behave like ``time.perf_counter``.
    """

    def __init__(self, cache: TuningCache | None = None, *,
                 timer: Callable[[], float] | None = None, iters: int = 3):
        # `is not None`, not truthiness: an empty TuningCache has len 0
        self.cache = cache if cache is not None else TuningCache()
        self.timer = timer or time.perf_counter
        self.iters = iters

    # -- timing ----------------------------------------------------------------
    def _time_call(self, fn: Callable[[], Any], iters: int | None = None) -> float:
        """Median wall time of ``fn`` (first call excluded: compile)."""
        iters = iters or self.iters
        jax.block_until_ready(fn())
        samples = []
        for _ in range(iters):
            t0 = self.timer()
            jax.block_until_ready(fn())
            samples.append(self.timer() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    # -- search ----------------------------------------------------------------
    def tune(self, kernel: str, make_call: Callable[[dict], Callable[[], Any]],
             *, shape: Sequence[int], dtype,
             candidates: Iterable[Mapping[str, int]] | None = None,
             backend: str | None = None, impl: str | None = None,
             flops: float = 0.0,
             bytes_moved: float = 0.0, force: bool = False) -> dict:
        """Find (or recall) the fastest config for ``kernel`` at ``shape``.

        ``make_call(config)`` returns a zero-arg callable running the kernel
        with that config.  Configs that raise are skipped.  The winning
        entry — ``{config, median_s, flops, bytes, backend, impl, timed}``
        — is persisted under the impl-resolved key; a later call with the
        same key returns it without any timing (the cache round-trip the
        benchmarks rely on).  ``impl`` must be the resolved execution path
        ``make_call`` actually runs (defaults to the backend's real impl).
        """
        backend = backend or jax.default_backend()
        impl = impl or default_impl(backend)
        key = cache_key(kernel, backend, shape, dtype, impl)
        if not force:
            hit = self.cache.get(key)
            if hit is not None:
                return hit
        cands = list(candidates if candidates is not None
                     else DEFAULT_CANDIDATES.get(kernel, [{}]))
        best_cfg, best_t, timed, last_exc = None, float("inf"), 0, None
        for cfg in cands:
            try:
                fn = make_call(dict(cfg))
                t = self._time_call(fn)
            except Exception as e:            # config invalid for this shape
                last_exc = e
                continue
            timed += 1
            if t < best_t:
                best_cfg, best_t = dict(cfg), t
        if best_cfg is None:
            raise RuntimeError(
                f"autotune({kernel}): no candidate ran for shape "
                f"{tuple(shape)}") from last_exc
        entry = {"config": best_cfg, "median_s": best_t, "flops": flops,
                 "bytes": bytes_moved, "backend": backend, "impl": impl,
                 "timed": timed}
        self.cache.put(key, entry)
        return entry

    # -- consultation (cache-only: safe at trace time) -------------------------
    def lookup(self, kernel: str, shape: Sequence[int], dtype,
               backend: str | None = None,
               impl: str | None = None) -> dict | None:
        """Tuned config for (kernel, backend, impl, bucket, dtype), or
        None.  Interpret-tuned configs never answer a kernel lookup."""
        backend = backend or jax.default_backend()
        entry = self.cache.get(cache_key(kernel, backend, shape, dtype,
                                         impl))
        return dict(entry["config"]) if entry else None

    def observed_s(self, kernel: str, shape: Sequence[int], dtype,
                   backend: str | None = None, impl: str | None = None,
                   nearest: bool = False) -> float | None:
        """Measured median seconds for the tuned config, or None.

        With ``nearest=True`` a miss falls back to the closest tuned
        bucket of the same kernel/backend/dtype, scaling the time by the
        element-count ratio (work ∝ ∏dims for the kernels tuned here) —
        the benchmark tunes one bucket per kernel, while workloads land in
        whatever bucket their size hits (n=2709 buckets to 4096, the tune
        at 2048 would otherwise never be consulted)."""
        backend = backend or jax.default_backend()
        impl = impl or default_impl(backend)
        entry = self.cache.get(cache_key(kernel, backend, shape, dtype,
                                         impl))
        if entry is not None:
            return float(entry["median_s"])
        if not nearest:
            return None
        want = shape_bucket(shape)
        dtype_name = jax.numpy.dtype(dtype).name
        best = None
        for key, e in self.cache.load().items():
            parts = key.split("|")
            if (len(parts) != 5 or parts[0] != kernel
                    or parts[1] != backend or parts[2] != impl
                    or parts[4] != dtype_name):
                continue
            try:
                bucket = tuple(int(d) for d in parts[3].split("x"))
            except ValueError:
                continue
            if len(bucket) != len(want):
                continue
            dist = abs(math.log(math.prod(want) / math.prod(bucket)))
            if best is None or dist < best[0]:
                best = (dist, bucket, e)
        if best is None:
            return None
        _, bucket, e = best
        # scale by true element counts, not bucket counts: the caller's
        # actual work is ∏shape, the measurement's is ∏bucket
        return float(e["median_s"]) * math.prod(shape) / math.prod(bucket)


# ---------------------------------------------------------------------------
# Module singleton (per cache path, so REPRO_TUNE_CACHE redirects in tests)
# ---------------------------------------------------------------------------

_tuners: dict[str, Autotuner] = {}


def get_tuner(cache_path: str | None = None) -> Autotuner:
    path = cache_path or os.environ.get(_ENV_CACHE) or _DEFAULT_CACHE
    t = _tuners.get(path)
    if t is None:
        t = Autotuner(TuningCache(path))
        _tuners[path] = t
    return t


# ---------------------------------------------------------------------------
# Cost-model bridge (tuned timings -> scheduler)
# ---------------------------------------------------------------------------


def calibrated_cost_params(base=None, tuner: Autotuner | None = None,
                           backend: str | None = None):
    """Derive ``CostModelParams`` from *observed* kernel rates.

    Every cache entry for the **current backend** that recorded its
    flops/bytes yields an achieved compute rate ``flops / median_s`` and
    memory rate ``bytes / median_s``; the best achieved rates replace the
    roofline guesses in ``base``, so the cost-model placement strategy
    prices jobs with what this machine was *measured* to deliver.  Entries
    from other backends are ignored — the cache is persistent and shared,
    and e.g. TPU rates would collapse the compute term of a CPU run to
    nothing.  Entries recorded under an impl other than the backend's real
    one (:func:`default_impl`) are ignored too: a forced-interpret debug
    run on a TPU host times the Pallas *interpreter*, not the hardware,
    and would poison the calibration the same way a foreign backend
    would.  With no usable entries ``base`` is returned as-is.
    """
    from repro.core.scheduler import CostModelParams
    base = base or CostModelParams()
    tuner = tuner or get_tuner()
    backend = backend or jax.default_backend()
    want_impl = default_impl(backend)
    peak, bw = 0.0, 0.0
    for entry in tuner.cache.load().values():
        if entry.get("backend") != backend:
            continue
        if entry.get("impl") != want_impl:
            continue
        t = float(entry.get("median_s") or 0.0)
        if t <= 0:
            continue
        peak = max(peak, float(entry.get("flops") or 0.0) / t)
        bw = max(bw, float(entry.get("bytes") or 0.0) / t)
    if peak <= 0.0 and bw <= 0.0:
        return base
    return CostModelParams(
        peak_flops=peak or base.peak_flops,
        mem_bw=bw or base.mem_bw,
        link_bw=base.link_bw,
        dispatch_s=base.dispatch_s,
    )
