"""HLO analyzer validation: parser vs XLA cost_analysis, scan correction,
trip-count parsing, collective accounting (multi-device cases run in a
subprocess so the main pytest process keeps 1 device)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo, xla_cost_analysis
from repro.analysis.roofline import V5E, RooflineTerms, roofline_from_compiled


def test_unrolled_dot_flops_match_cost_analysis():
    W = jax.ShapeDtypeStruct((4, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128,), jnp.float32)

    def f(ws, x):
        for i in range(4):
            x = jnp.tanh(ws[i] @ x)
        return x

    c = jax.jit(f).lower(W, x).compile()
    a = analyze_hlo(c.as_text())
    assert a.flops == pytest.approx(xla_cost_analysis(c)["flops"], rel=1e-6)
    assert a.flops == pytest.approx(4 * 2 * 128 * 128, rel=1e-6)


def test_scan_trip_multiplier():
    L = 12
    W = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((64,), jnp.float32)

    def f(ws, x):
        def body(c, w):
            return jnp.tanh(w @ c), None
        return jax.lax.scan(body, x, ws)[0]

    c = jax.jit(f).lower(W, x).compile()
    a = analyze_hlo(c.as_text())
    assert list(a.while_trips.values()) == [L]
    assert a.flops == pytest.approx(L * 2 * 64 * 64, rel=1e-6)
    # XLA's own analysis counts the body once — the discrepancy this module
    # exists to fix
    assert xla_cost_analysis(c)["flops"] < a.flops / 2


def test_nested_scan_multipliers():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ c2), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        return jax.lax.scan(outer, x, None, length=5)[0]

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    a = analyze_hlo(c.as_text())
    assert a.flops == pytest.approx(5 * 3 * 2 * 32 ** 3, rel=1e-6)


def test_trip_override():
    def f(ws, x):
        def body(c, w):
            return w @ c, None
        return jax.lax.scan(body, x, ws)[0]

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((6, 32, 32), jnp.float32),
                         jax.ShapeDtypeStruct((32,), jnp.float32)).compile()
    a6 = analyze_hlo(c.as_text())
    body = list(a6.while_trips)[0]
    a2 = analyze_hlo(c.as_text(), trip_overrides={body: 2})
    assert a2.flops == pytest.approx(a6.flops / 3, rel=1e-6)


def test_traffic_scan_consistent_with_unrolled():
    L = 8

    def scan_f(ws, x):
        def body(c, w):
            return jnp.tanh(w @ c), None
        return jax.lax.scan(body, x, ws)[0]

    def unroll_f(ws, x):
        for i in range(L):
            x = jnp.tanh(ws[i] @ x)
        return x

    W = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128,), jnp.float32)
    a_s = analyze_hlo(jax.jit(scan_f).lower(W, x).compile().as_text())
    a_u = analyze_hlo(jax.jit(unroll_f).lower(W, x).compile().as_text())
    assert a_s.traffic_bytes == pytest.approx(a_u.traffic_bytes, rel=0.25)


def test_roofline_terms_and_dominance():
    t = RooflineTerms(compute_s=1e-3, memory_s=5e-3, collective_s=2e-3,
                      flops=1.0, traffic_bytes=1.0, collective_bytes=1.0,
                      model_flops=100.0)
    assert t.dominant == "memory"
    assert t.step_s == 5e-3


MULTIDEV_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.hlo import analyze_hlo, xla_cost_analysis
mesh = jax.make_mesh((8,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
f = jax.jit(lambda a, b: a @ b,
            in_shardings=(NamedSharding(mesh, P(None, "d")),
                          NamedSharding(mesh, P("d", None))),
            out_shardings=NamedSharding(mesh, P()))
c = f.lower(jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
a = analyze_hlo(c.as_text())
# per-device partial matmul: 2*256*256*(256/8)
assert abs(a.flops - 2 * 256 * 256 * 32) / a.flops < 1e-6, a.flops
assert a.collectives.counts["all-reduce"] == 1, a.collectives.counts
# ring all-reduce bytes ~ 2 x buffer
assert abs(a.collectives.total_bytes - 2 * 256 * 256 * 4) < 1e3
print("MULTIDEV_OK")
"""


def test_collective_accounting_multidevice():
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=".")
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr
