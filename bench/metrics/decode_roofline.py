"""Share of the HBM roofline reached by the decode program in the traced
window: the bytes one decode step must move (:func:`step_bytes`) at the
chip's HBM bandwidth, over the device time of one call of the decode
module ``jit__decode``, both as means over the window.

What a step must move: every weight once (the parameters outside the
embedding, ``serve_common.token_params``, and the embedding table and
output head, which the head reads whole); each live slot's recurrent
state read and written (``PagedEngine.ssm_state_bytes``: SSD state and
conv tail); and the KV pages of the attention layers
(``serve_common.paged_kernel_cost``).
Where the program has no ``ssm_state_bytes`` the reader finds nothing."""
import jax.numpy as jnp

from bench.drivers import serve_common
from bench.lib import trace

MODULE = "jit__decode"


def slot_state_bytes(cfg):
    """Bytes of recurrent state the program keeps for one slot, read off
    its engine (nothing is allocated); None where the engine does not
    report it."""
    from repro.serve.engine import PagedEngine
    eng = PagedEngine(cfg, None, batch=1, max_len=16, page_size=16,
                      num_pages=2, prefill_chunk=16)
    return getattr(eng, "ssm_state_bytes", None)


def step_bytes(cfg, body: int, slot_state: int, page_size: int,
               decodes) -> float:
    """HBM bytes the decode calls ``decodes`` (``(t, contexts)``, one
    context per live slot) must move, in all."""
    tables = 1 if cfg.tie_embeddings else 2      # embedding, output head
    weights = (body + tables * cfg.padded_vocab * cfg.d_model) * jnp.dtype(
        cfg.param_dtype).itemsize
    live = sum(len(ctx) for _, ctx in decodes)
    _, kv = serve_common.paged_kernel_cost(cfg, page_size, decodes)
    return len(decodes) * weights + 2 * live * slot_state + kv


def read(cell):
    d = cell.data
    if cell.summary is None or cell.trace_span is None or "recorder" not in d:
        return None
    evs = [e for e in trace.events_matching(cell.summary, "modules", [MODULE])
           if e[0].split("(")[0] == MODULE]
    _, decodes = d["recorder"].in_span(*cell.trace_span)
    if not evs or not decodes:
        return None
    state = slot_state_bytes(d["cfg"])
    if state is None:
        return None
    per_step = step_bytes(d["cfg"], d["body"], state, d["page_size"],
                          decodes) / len(decodes)
    t = trace.total_s(evs) / len(evs)
    return per_step / cell.peaks["hbm_bytes_per_s"] / t * 100.0
