"""Share of its roofline reached by the paged flash-decode Pallas kernel in
the traced window: the larger of (FLOPs / bf16 peak) and (bytes / HBM
bandwidth), with FLOPs and bytes counted from the pages that hold each
live slot's cached keys and values (``serve_common.paged_kernel_cost``),
over the kernel's device time.  The kernel is the decode program's
``tpu_custom_call`` whose first operand is the page table
(``s32[slots, max_pages]``): the trace names it ``%closed_call.N``."""
from bench.drivers import serve_common
from bench.lib import trace


def read(cell):
    d = cell.data
    if cell.summary is None or cell.trace_span is None or "recorder" not in d:
        return None
    table = f"custom-call(s32[{d['slots']},{d['max_pages']}]"
    evs = [e for e in trace.events_matching(cell.summary, "ops", [table])
           if 'custom_call_target="tpu_custom_call"' in e[0]]
    if not evs:
        return None
    _, decodes = d["recorder"].in_span(*cell.trace_span)
    fl, by = serve_common.paged_kernel_cost(d["cfg"], d["page_size"], decodes)
    least = max(fl / cell.peaks["bf16_flops_per_s"],
                by / cell.peaks["hbm_bytes_per_s"])
    return least / trace.total_s(evs) * 100.0
