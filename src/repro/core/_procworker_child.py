"""Worker-process entry point for :class:`repro.core.procworker.ProcessExecutor`.

Runs inside a ``multiprocessing`` *spawn* child — the paper's 'fat worker'
that "registers functions before recompiling the framework": the child
resolves its function table from a ``"module:attr"`` spec at startup and
never sees the master's registry (whose functions may close over jitted
callables and device handles that don't pickle).

Deliberately **jax-free** (like :mod:`repro.core.store`): spawn children pay
full import cost per process, and the numpy-level worker functions need no
device.  A function table that does import jax runs on the host CPU: the
master spawns every child with ``JAX_PLATFORMS=cpu``.

Protocol (one request/response pair in flight per worker — the master's
per-worker dispatch queues already serialise placements per worker):

    ("job", seq, key, fid, kind, inputs)  →  ("ok", seq, key, arrays)
                                          |  ("err", seq, key, traceback)
    ("stop",)                             →  child exits

``inputs`` is one list of numpy chunk arrays per input ref; ``kind`` is
"chunkwise" (fn applied per zipped chunk tuple) or "whole" (fn over the full
chunk lists).  The result is persisted to the :class:`JobStore` **before**
the reply is sent — a master that dies between child completion and reply
delivery still finds the row ``done`` on resume.
"""
from __future__ import annotations

import importlib
import os
import threading
import time
import traceback

import numpy as np

from .store import JobStore

__all__ = ["resolve_fns", "worker_main"]


def resolve_fns(spec: str) -> dict:
    """``"package.module:ATTR"`` → the module-level function table (a dict
    mapping registry fid strings to plain numpy functions)."""
    modname, _, attr = spec.partition(":")
    if not attr:
        raise ValueError(f"worker fn spec {spec!r} must be 'module:attr'")
    table = getattr(importlib.import_module(modname), attr)
    if not isinstance(table, dict):
        raise TypeError(f"{spec} must resolve to a dict, got {type(table)}")
    return table


def _run_job(fn, kind: str, inputs: list[list[np.ndarray]]) -> list[np.ndarray]:
    if kind == "chunkwise":
        return [np.asarray(fn(*args)) for args in zip(*inputs)]
    out = fn(*inputs)  # whole: fn sees every input's full chunk list
    if isinstance(out, (list, tuple)):
        return [np.asarray(a) for a in out]
    return [np.asarray(out)]


def worker_main(wid: int, store_path: str, fns_spec: str,
                hb_interval: float, req_q, resp_q) -> None:
    fns = resolve_fns(fns_spec)
    store = JobStore(store_path)
    store.register_worker(wid, os.getpid())
    stop = threading.Event()

    def _beat() -> None:
        while not stop.wait(hb_interval):
            store.beat(wid)

    beater = threading.Thread(target=_beat, daemon=True,
                              name=f"proc-w{wid}-beat")
    beater.start()
    try:
        while True:
            msg = req_q.get()
            if msg[0] == "stop":
                break
            _, seq, key, fid, kind, inputs = msg
            try:
                arrays = _run_job(fns[fid], kind, inputs)
                # durable BEFORE the reply: the master may die in between
                store.put_result(key, arrays, fn=str(fid), worker=wid)
                resp_q.put(("ok", seq, key, arrays))
            except Exception:
                resp_q.put(("err", seq, key, traceback.format_exc()))
    finally:
        stop.set()
        store.mark_worker_dead(wid)
        store.close()
