"""Post-optimisation HLO text analyzer.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body **once**, so for
scan-over-layers models the reported FLOPs/bytes are ~1/L of the truth.
This module parses ``compiled.as_text()`` into computations, attributes
FLOPs (dot / matmul custom-calls), an HBM-traffic estimate and collective
bytes per computation, then walks the call graph multiplying ``while``
bodies by their trip counts (parsed from the loop-bound constant in the
condition computation, overridable).

Parsing details handled: operands are name references (shapes resolved via
a per-computation symbol table, HLO is SSA); tuple-typed ops (while);
CPU-backend oneDNN/dot custom-calls counted as matmuls.

Validated against ``cost_analysis()`` on unrolled models
(tests/test_analysis.py): dot FLOPs match exactly; the traffic estimate is
an upper-bound model (every materialising op reads operands / writes
output to HBM) that is *consistent* across perf iterations, which is what
hillclimbing needs.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

__all__ = ["HloAnalysis", "analyze_hlo", "CollectiveStats", "xla_cost_analysis"]


def xla_cost_analysis(compiled) -> dict:
    """``jax.stages.Compiled.cost_analysis()`` as a dict (``{}`` when the
    backend reports none)."""
    return dict(compiled.cost_analysis() or {})


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_OPERAND_NAME_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SKIP_TRAFFIC_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "bitcast-convert", "after-all", "partition-id", "replica-id", "iota",
    "copy-start", "copy-done",
}

_MATMUL_CC = ("matmul", "dot", "gemm", "cublas", "onednn")


def _shape_bytes(type_str: str) -> int:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        b = _DTYPE_BYTES.get(dtype)
        if b is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += b * n
    return int(total)


def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclasses.dataclass
class OpInfo:
    name: str
    opcode: str
    out_type: str
    operand_names: list[str]
    attrs: str

    out_bytes: int = 0
    in_bytes: int = 0
    flops: float = 0.0
    calls: list[str] = dataclasses.field(default_factory=list)
    body: str | None = None
    cond: str | None = None


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[OpInfo] = dataclasses.field(default_factory=list)
    types: dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]
    bytes_by_kind: dict[str, float]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


@dataclasses.dataclass
class HloAnalysis:
    flops: float
    traffic_bytes: float
    collectives: CollectiveStats
    while_trips: dict[str, int]
    by_computation: dict[str, dict]
    matmul_flops: float = 0.0

    def summary(self) -> str:
        c = self.collectives
        return (f"flops={self.flops:.3e} traffic={self.traffic_bytes:.3e}B "
                f"collective={c.total_bytes:.3e}B "
                + " ".join(f"{k}:{v}" for k, v in c.counts.items() if v))


def _split_type_rest(rhs: str) -> tuple[str, str]:
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[:i + 1], rhs[i + 1:].strip()
    sp = rhs.find(" ")
    if sp < 0:
        return rhs, ""
    return rhs[:sp], rhs[sp + 1:].strip()


def _parse_op(line: str) -> OpInfo | None:
    line = line.strip().rstrip(",")
    m = re.match(r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$", line)
    if not m:
        return None
    name, rhs = m.groups()
    out_type, rest = _split_type_rest(rhs)
    om = re.match(r"^([\w\-]+)\((.*)$", rest)
    if not om:
        return None
    opcode, tail = om.groups()
    depth, i = 1, 0
    while i < len(tail) and depth:
        if tail[i] == "(":
            depth += 1
        elif tail[i] == ")":
            depth -= 1
        i += 1
    operands, attrs = tail[:i - 1], tail[i:]
    info = OpInfo(name=name, opcode=opcode, out_type=out_type,
                  operand_names=_OPERAND_NAME_RE.findall(operands),
                  attrs=attrs)
    info.out_bytes = _shape_bytes(out_type)
    cm = _CALLS_RE.search(attrs)
    if cm:
        info.calls.append(cm.group(1))
    bm = _BODY_RE.search(attrs)
    if bm:
        info.body = bm.group(1)
    cm2 = _COND_RE.search(attrs)
    if cm2:
        info.cond = cm2.group(1)
    if opcode == "constant":
        info.attrs = "constant(" + operands + ")" + attrs
    return info


def _dot_flops(op: OpInfo, types: dict[str, str]) -> float:
    out_n = math.prod(_shape_dims(op.out_type)) if _shape_dims(op.out_type) else 1
    m = _CONTRACT_RE.search(op.attrs)
    contract = 1
    lhs_type = types.get(op.operand_names[0], "") if op.operand_names else ""
    lhs_dims = _shape_dims(lhs_type)
    if m:
        idxs = [int(i) for i in m.group(1).split(",")] if m.group(1) else []
        for i in idxs:
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    elif lhs_dims:
        contract = lhs_dims[-1]
    return 2.0 * out_n * contract


def _cc_matmul_flops(op: OpInfo, types: dict[str, str]) -> float:
    """Matmul-ish custom-call (oneDNN on CPU, cublas on GPU): out (.., M, N),
    lhs (.., M, K) => 2·prod(out)·K."""
    out_dims = _shape_dims(op.out_type)
    if not op.operand_names:
        return 0.0
    lhs_dims = _shape_dims(types.get(op.operand_names[0], ""))
    if not out_dims or not lhs_dims:
        return 0.0
    return 2.0 * math.prod(out_dims) * lhs_dims[-1]


def _parse_computations(text: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry = ""
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            if stripped.endswith("{") and ("->" in stripped or
                                           stripped.startswith("ENTRY")):
                m = _COMP_HEAD_RE.match(stripped)
                if m:
                    cur = Computation(m.group(1))
                    if stripped.startswith("ENTRY"):
                        entry = m.group(1)
            continue
        if stripped.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        op = _parse_op(stripped)
        if op is None:
            continue
        cur.ops.append(op)
        cur.types[op.name] = op.out_type
    if cur is not None:
        comps[cur.name] = cur
    if not entry and comps:
        entry = list(comps)[-1]
    return comps, entry


def _finalize_ops(comp: Computation) -> None:
    for op in comp.ops:
        op.in_bytes = sum(_shape_bytes(comp.types.get(n, ""))
                          for n in op.operand_names)
        if op.opcode == "dot":
            op.flops = _dot_flops(op, comp.types)
        elif op.opcode == "custom-call" and any(
                t in op.attrs.lower() for t in _MATMUL_CC):
            op.flops = _cc_matmul_flops(op, comp.types)
        elif op.opcode == "convolution":
            # flops = 2 * out_elems * (in_channels/feature_group * prod(kernel_spatial))
            out_n = math.prod(_shape_dims(op.out_type) or [0])
            rhs_dims = _shape_dims(comp.types.get(op.operand_names[1], "")) \
                if len(op.operand_names) > 1 else []
            k = math.prod(rhs_dims[:-1]) if rhs_dims else 0
            op.flops = 2.0 * out_n * k


def _trip_count(cond_comp: Computation | None, default: int) -> int:
    """Loop bound: the largest integer constant in the condition
    computation.  Exact for lax.scan-lowered loops."""
    if cond_comp is None:
        return default
    consts = []
    for op in cond_comp.ops:
        consts += [int(x) for x in _CONST_RE.findall(op.attrs)]
    consts = [c for c in consts if c > 0]
    return max(consts) if consts else default


def analyze_hlo(text: str, *, default_trips: int = 1,
                trip_overrides: dict[str, int] | None = None) -> HloAnalysis:
    comps, entry = _parse_computations(text)
    for comp in comps.values():
        _finalize_ops(comp)
    trip_overrides = trip_overrides or {}

    # multipliers: walk the call graph from ENTRY.  ``hbm`` marks whether a
    # computation's ops materialise buffers (while bodies: yes; fusion /
    # reduce-apply bodies: no — their traffic is charged at the call site).
    mult: dict[str, float] = {}
    hbm_mult: dict[str, float] = {}
    while_trips: dict[str, int] = {}
    visiting: set[str] = set()

    def visit(name: str, m: float, hbm: bool):
        if name not in comps or name in visiting:
            return
        visiting.add(name)
        mult[name] = mult.get(name, 0.0) + m
        if hbm:
            hbm_mult[name] = hbm_mult.get(name, 0.0) + m
        for op in comps[name].ops:
            if op.opcode == "while":
                trips = trip_overrides.get(op.body or "",
                                           trip_overrides.get(op.name, None))
                if trips is None:
                    trips = _trip_count(comps.get(op.cond or ""), default_trips)
                if op.body:
                    while_trips[op.body] = trips
                    visit(op.body, m * trips, hbm)
                if op.cond:
                    visit(op.cond, m * (trips + 1), False)
            elif op.opcode == "conditional":
                for callee in op.calls:
                    visit(callee, m, hbm)
            else:
                for callee in op.calls:
                    visit(callee, m, False)
        visiting.discard(name)

    visit(entry, 1.0, True)

    flops = 0.0
    matmul_flops = 0.0
    traffic = 0.0
    coll_counts = {k: 0 for k in COLLECTIVES}
    coll_bytes = {k: 0.0 for k in COLLECTIVES}
    by_comp: dict[str, dict] = {}
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        hm = hbm_mult.get(name, 0.0)
        if m == 0.0:
            continue
        cflops = 0.0
        cmm = 0.0
        ctraffic = 0.0
        for op in comp.ops:
            cflops += op.flops
            if op.opcode in ("dot", "convolution") or (
                    op.opcode == "custom-call" and op.flops):
                cmm += op.flops
            if op.opcode in _SKIP_TRAFFIC_OPS or op.opcode == "while":
                continue
            if op.opcode == "dynamic-slice":
                # reads only the sliced region (+ writes it)
                ctraffic += 2 * op.out_bytes
            elif op.opcode == "dynamic-update-slice":
                # in-place read-modify-write of the touched region only
                # (XLA aliases the buffer inside while loops); the update
                # operand is the second operand
                upd = (_shape_bytes(comp.types.get(op.operand_names[1], ""))
                       if len(op.operand_names) > 1 else op.out_bytes)
                ctraffic += 2 * upd
            elif op.opcode in ("gather", "scatter"):
                small = min(op.out_bytes, op.in_bytes)
                ctraffic += 2 * small
            else:
                ctraffic += op.in_bytes + op.out_bytes
            if op.opcode in COLLECTIVES:
                coll_counts[op.opcode] += max(int(m), 1 if m > 0 else 0)
                if op.opcode == "all-reduce":
                    b = 2.0 * op.in_bytes
                elif op.opcode == "all-gather":
                    b = float(op.out_bytes)
                else:
                    b = float(op.in_bytes)
                coll_bytes[op.opcode] += m * b
        flops += m * cflops
        matmul_flops += m * cmm
        traffic += hm * ctraffic
        by_comp[name] = {"mult": m, "hbm_mult": hm, "flops": cflops,
                         "traffic": ctraffic}

    return HloAnalysis(flops=flops, traffic_bytes=traffic,
                       collectives=CollectiveStats(coll_counts, coll_bytes),
                       while_trips=while_trips, by_computation=by_comp,
                       matmul_flops=matmul_flops)
