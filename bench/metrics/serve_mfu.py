"""Model FLOP utilisation of the window: model FLOPs of every token the
engine processed inside it (prompt chunks and decoded tokens, counted by
``serve_common.flops`` from the harness's record of engine calls) over the
window's seconds over the chip's bf16 peak."""
from bench.drivers import serve_common


def read(cell):
    d = cell.data
    if "recorder" not in d:
        return None
    chunks, decodes = d["recorder"].in_span(d["open"], d["close"])
    fl = serve_common.flops(d["cfg"], d["body"], chunks, decodes)
    return fl / (d["close"] - d["open"]) / cell.peaks["bf16_flops_per_s"] * 100
