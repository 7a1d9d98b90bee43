"""Share of its roofline reached by the Mamba-2 SSD intra-chunk Pallas
kernel over the chunk prefills of the traced window: the larger of
(FLOPs / bf16 peak) and (bytes / HBM bandwidth), both counted from the
kernel's operand shapes (:func:`ssd_cost`), over the kernel's device time.

The kernel is a ``tpu_custom_call`` that runs inside the chunk module
``jit__chunk`` and takes five operands: x ``(BC, H, Q, P)``, dt and the
cumulative decay ``(BC, H, Q, 1)``, B and C ``(BC, Q, N)``.  Its matrix
products run in float32 at the highest precision, which the chip does at
a fraction of its bf16 rate: the bf16 peak makes the share a lower bound."""
import math
import re

from bench.lib import trace

MODULE = "jit__chunk"
KERNEL = 'custom_call_target="tpu_custom_call"'
_OPERAND = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4}


def operands(op: str) -> list[tuple[str, tuple[int, ...]]]:
    """(element type, shape) of each operand of an HLO op's text."""
    if "custom-call(" not in op:
        return []
    # operands run to the call's attributes; layouts may hold parentheses
    args = op.split("custom-call(", 1)[1].split("custom_call_target", 1)[0]
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _OPERAND.findall(args)]


def ssd_cost(ops) -> tuple[float, float] | None:
    """(FLOPs, HBM bytes) of one kernel call from its operands, or None
    where they are not the SSD kernel's.  FLOPs: C Bᵀ once per chunk
    (2 BC Q² N), the intra-chunk output (2 BC H Q² P) and the chunk's
    state (2 BC H Q N P).  Bytes: every operand read once, y
    ``(BC, H, Q, P)`` and the state ``(BC, H, N, P)`` written in f32."""
    if len(ops) != 5 or [len(s) for _, s in ops] != [4, 4, 4, 3, 3]:
        return None
    (_, (BC, H, Q, P)), (_, dt), _, (_, b), _ = ops
    N = b[-1]
    if dt != (BC, H, Q, 1) or b != (BC, Q, N):
        return None
    fl = 2.0 * BC * Q * Q * N + 2.0 * BC * H * Q * Q * P \
        + 2.0 * BC * H * Q * N * P
    read = sum(_BYTES.get(t, 4) * math.prod(s) for t, s in ops)
    return fl, read + 4.0 * BC * H * P * (Q + N)


def kernel_events(summary) -> list:
    """The SSD kernel's op events inside a chunk module's run, each with
    its cost: ``[(event, (FLOPs, bytes)), ...]``."""
    lo, hi = summary["window"]
    out = []
    for dev in summary["devices"].values():
        runs = [(s, s + n) for name, s, n in dev["modules"]
                if name.split("(")[0] == MODULE]
        for e in dev["ops"]:
            if KERNEL not in e[0] or not lo <= e[1] < hi:
                continue
            if not any(a <= e[1] and e[1] + e[2] <= b for a, b in runs):
                continue
            cost = ssd_cost(operands(e[0]))
            if cost is not None:
                out.append((e, cost))
    return out


def read(cell):
    if cell.summary is None:
        return None
    got = kernel_events(cell.summary)
    if not got:
        return None
    least = sum(max(fl / cell.peaks["bf16_flops_per_s"],
                    by / cell.peaks["hbm_bytes_per_s"]) for _, (fl, by) in got)
    return least / trace.total_s([e for e, _ in got]) * 100.0
