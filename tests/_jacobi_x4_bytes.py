"""Forced-4-device Jacobi byte accounting (run as a subprocess).

The XLA host-device-count flag must be set before jax initialises, so
test_spans.py runs this file with ``python tests/_jacobi_x4_bytes.py``.

It runs ``jacobi_hypar`` with 4 row chunks over 4 workers and checks, for
every iteration's sweep segment, that ``moved_bytes`` counts each bound row
chunk (rows of A, b, the diagonal; x0 in iteration 0) that ``device_put``
sends off device 0, plus the previous iterate for each sweep placed away
from it.  Prints ``X4_OK <json>`` on success.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402

import jax  # noqa: E402


def main() -> None:
    from repro.apps.jacobi import jacobi_hypar, make_system
    from repro.core import VirtualCluster

    devs = jax.devices()
    assert len(devs) == 4, devs
    n, n_chunks, iters = 64, 4, 5
    A, b, _ = make_system(n)
    r = jacobi_hypar(A, b, iters=iters, n_chunks=n_chunks,
                     cluster=VirtualCluster(devs, max_workers=4))
    report, where = r.extra["report"], r.extra["job_devices"]
    home = A.devices().pop()
    rows = n // n_chunks
    bound = 4 * (rows * n + 2 * rows)         # A_i, b_i, d_i in float32
    x_bytes = 4 * n                           # the iterate (x0 or X's x)
    res_bytes = 4                             # X's residual scalar
    sweeps = {tuple(s.jobs): s for s in report.segments
              if s.jobs and s.jobs[0].startswith("S")}
    assert len(sweeps) == iters, [s.jobs for s in report.segments]
    off_home = []
    for k in range(iters):
        names = tuple(f"S{k}_{i}" for i in range(n_chunks))
        seg = sweeps[names]
        away = [nm for nm in names if where[nm] != home]
        want = len(away) * (bound + (x_bytes if k == 0 else 0))
        if k:
            x_dev = where[f"X{k - 1}"]
            want += sum(x_bytes + res_bytes for nm in names
                        if where[nm] != x_dev)
        assert seg.moved_bytes == want, (k, seg.moved_bytes, want)
        off_home.append(len(away))
    assert min(off_home) > 0, off_home
    print("X4_OK " + json.dumps({"off_home": off_home,
                                 "moved_bytes": report.moved_bytes}))


if __name__ == "__main__":
    main()
