"""Process set-up shared by every driver: caches at fixed checkout paths,
the chip check, compile counting and the device record of the result line.

Import order matters: :func:`configure_caches` runs before JAX is
imported, so the environment it sets is what JAX and the program read."""
from __future__ import annotations

import logging
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(CHECKOUT, ".bench_cache")


class NoChip(SystemExit):
    """Raised (exit code 3) when JAX finds no TPU or too few chips."""

    def __init__(self, msg: str):
        super().__init__(3)
        self.msg = msg


def configure_caches() -> dict:
    """Point the kernel tune cache and JAX's compilation cache at fixed
    paths inside the checkout (``.bench_cache/``).  The tune cache is only
    read: no run tunes.  ``JAX_COMPILATION_CACHE_DIR``, where the machine
    sets it, names the compile cache instead."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    tune = os.path.join(CACHE_DIR, "kernel_tune.json")
    os.environ["REPRO_TUNE_CACHE"] = tune
    jax_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
               or os.path.join(CACHE_DIR, "jax"))
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return {"tune_cache": tune, "jax_cache": jax_dir}


def enable_jax_cache(jax, path: str) -> None:
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # every program, however quick to compile, goes to the cache: the
    # program re-traces some jits per call and must find them there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chip(jax, chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is on platform "
                     f"{devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} TPU chips, JAX sees {len(devs)}")
    return list(devs[:chips])


def device_record(devs) -> dict:
    d = devs[0]
    peak = 0
    for dev in devs:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileWatch:
    """Counts JAX compilations from its monitoring events (the arithmetic
    of the repository's chip smoke script's ``Clock``): seconds spent
    tracing, lowering and compiling, compiles that missed the persistent
    cache (with their module names), and programs loaded from it."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.compile_s = 0.0
        self.misses = 0
        self.hits = 0
        self.missed: list[str] = []    # module names that missed the cache
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.addHandler(_MissHandler(self.missed))

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "misses": self.misses,
                "hits": self.hits, "missed": list(self.missed)}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        out = {k: b[k] - a[k] for k in a if k != "missed"}
        out["missed"] = b["missed"][len(a["missed"]):]
        return out


class _MissHandler(logging.Handler):
    """Keeps the module name of each persistent-cache miss that JAX logs
    (at debug level), and lets nothing through to the console."""

    PATTERN = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']*)'")

    def __init__(self, sink: list):
        super().__init__(logging.DEBUG)
        self.sink = sink

    def emit(self, record):
        m = self.PATTERN.search(record.getMessage())
        if m:
            self.sink.append(m.group(1))
