"""Loop-aware HLO analysis: FLOPs, HBM bytes and collective traffic.

``compiled.cost_analysis()`` counts a ``while`` body ONCE regardless of trip
count (verified empirically), which would understate a scanned-layer model by
~num_layers x.  This module parses ``compiled.as_text()`` instead:

  * builds a per-computation symbol table (instruction -> shape),
  * recovers while-loop trip counts from the loop-condition constant,
  * propagates multiplicative trip multipliers through nested loops,
  * sums dot/convolution FLOPs, per-instruction HBM bytes (fusion
    boundaries only, mirroring XLA's bytes-accessed convention), and
  * sizes every collective (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute) both as operand bytes (assignment
    formula) and as ring-model wire bytes per chip.

All shapes in a GSPMD-partitioned module are per-device, so every number
this module returns is per-chip.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\(")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute", "collective-broadcast",
                  "all-gather-start", "all-reduce-start")


def _parse_shapes(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    """'bf16[8,128]{1,0}' or '(f32[2], s32[])' -> [(dtype, dims), ...]."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype == "token" or dtype not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append((dtype, shape))
    return out


def _nbytes(type_str: str) -> int:
    return sum(_DTYPE_BYTES[dt] * int(math.prod(sh) or 1)
               for dt, sh in _parse_shapes(type_str))


def _nelems(type_str: str) -> int:
    return sum(int(math.prod(sh) or 1) for _, sh in _parse_shapes(type_str))


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    line: str
    operands: list[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    shapes: dict[str, str] = field(default_factory=dict)


@dataclass
class CollectiveStat:
    op: str
    count: float = 0.0
    operand_bytes: float = 0.0   # assignment formula: sum of operand sizes
    wire_bytes: float = 0.0      # ring model: per-chip bytes on the wire


def parse_module(hlo_text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if not line or line.startswith("HloModule"):
            continue
        hdr = _COMP_HDR_RE.match(line)
        if hdr and line.endswith("{") and "->" in line:
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.group(1), m.group(2), m.group(3)
        paren = line[m.end() - 1:]
        depth = 0
        args = ""
        for ch in paren:
            if ch == "(":
                depth += 1
                if depth == 1:
                    continue
            if ch == ")":
                depth -= 1
                if depth == 0:
                    break
            if depth >= 1:
                args += ch
        operands = _OPERAND_RE.findall(args)
        ins = Instr(name, type_str, op, line, operands)
        cur.instrs.append(ins)
        cur.shapes[name] = type_str
    if entry and entry != "main":
        comps.setdefault("__entry__", comps[entry])
    return comps


def _attr_comp(line: str, key: str) -> str | None:
    m = re.search(key + r"=%?([\w.\-]+)", line)
    return m.group(1) if m else None


def _trip_count(cond: Computation) -> int:
    """Largest s32 scalar constant in the loop condition (scan bound)."""
    best = 1
    for ins in cond.instrs:
        if ins.op == "constant" and ins.type_str.startswith("s32"):
            m = re.search(r"constant\((-?\d+)\)", ins.line)
            if m:
                best = max(best, int(m.group(1)))
    return best


def compute_multipliers(comps: dict[str, Computation], entry: str
                        ) -> dict[str, float]:
    """Execution-count multiplier per computation (nested loops compose)."""
    mult: dict[str, float] = {name: 0.0 for name in comps}
    mult[entry] = 1.0
    # topological-ish fixed point (call graph is a DAG)
    for _ in range(64):
        changed = False
        new = {name: 0.0 for name in comps}
        new[entry] = 1.0
        for cname, comp in comps.items():
            m = mult.get(cname, 0.0)
            if m == 0.0:
                continue
            for ins in comp.instrs:
                if ins.op == "while":
                    body = _attr_comp(ins.line, "body")
                    cond = _attr_comp(ins.line, "condition")
                    if body in comps and cond in comps:
                        trips = _trip_count(comps[cond])
                        new[body] = new.get(body, 0.0) + m * trips
                        new[cond] = new.get(cond, 0.0) + m * (trips + 1)
                elif ins.op in ("fusion", "call", "custom-call"):
                    callee = _attr_comp(ins.line, "calls")
                    if callee in comps:
                        new[callee] = new.get(callee, 0.0) + m
                elif ins.op == "conditional":
                    for callee in re.findall(
                            r"(?:branch_computations=\{([^}]*)\}|"
                            r"(?:true|false)_computation=%?([\w.\-]+))",
                            ins.line):
                        for c in callee:
                            for cc in re.findall(r"[\w.\-]+", c or ""):
                                if cc in comps:
                                    new[cc] = new.get(cc, 0.0) + m
        new_t = {k: v for k, v in new.items()}
        if new_t == mult:
            break
        mult = new_t
        changed = True
    return mult


def _fusion_callees(comps: dict[str, Computation]) -> set[str]:
    out = set()
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op == "fusion":
                callee = _attr_comp(ins.line, "calls")
                if callee:
                    out.add(callee)
    return out


def _dot_flops(ins: Instr, comp: Computation) -> float:
    out_elems = _nelems(ins.type_str)
    if not ins.operands:
        return 0.0
    lhs = comp.shapes.get(ins.operands[0])
    if lhs is None:
        return 0.0
    lhs_shapes = _parse_shapes(lhs)
    if not lhs_shapes:
        return 0.0
    lhs_dims = lhs_shapes[0][1]
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    contracted = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            if int(d) < len(lhs_dims):
                contracted *= lhs_dims[int(d)]
    return 2.0 * out_elems * contracted


def _conv_flops(ins: Instr, comp: Computation) -> float:
    out_elems = _nelems(ins.type_str)
    if len(ins.operands) < 2:
        return 0.0
    rhs = comp.shapes.get(ins.operands[1])
    if rhs is None:
        return 0.0
    rhs_shapes = _parse_shapes(rhs)
    if not rhs_shapes:
        return 0.0
    rhs_dims = rhs_shapes[0][1]
    # kernel contributes (prod of all dims except output-feature dim)
    m = re.search(r"dim_labels=\S*_(\w+)->", ins.line)
    per_out = int(math.prod(rhs_dims))
    if m:
        lbl = m.group(1)  # e.g. 01io or io01
        o_pos = lbl.index("o")
        per_out = per_out // rhs_dims[o_pos]
    return 2.0 * out_elems * per_out


_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "after-all", "partition-id", "replica-id",
    "iota", "fusion", "call",
}


@dataclass
class HLOAnalysis:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collectives: dict = field(default_factory=dict)
    trip_counts: dict = field(default_factory=dict)

    @property
    def collective_operand_bytes(self) -> float:
        return sum(c.operand_bytes for c in self.collectives.values())

    @property
    def collective_wire_bytes(self) -> float:
        return sum(c.wire_bytes for c in self.collectives.values())


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m and m.group(1):
        first = m.group(1).split("}")[0].strip("{ ")
        return max(1, len([x for x in first.split(",") if x.strip()]))
    return 1


def analyze(hlo_text: str) -> HLOAnalysis:
    comps = parse_module(hlo_text)
    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line[len("ENTRY "):].strip())
            if m:
                entry = m.group(1)
            break
    if entry is None:  # fall back: computation named main-ish
        entry = next((n for n in comps if "main" in n), next(iter(comps)))
    mult = compute_multipliers(comps, entry)
    fused = _fusion_callees(comps)

    res = HLOAnalysis()
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = cname in fused
        for ins in comp.instrs:
            if ins.op == "dot":
                res.flops += m * _dot_flops(ins, comp)
            elif ins.op == "convolution":
                res.flops += m * _conv_flops(ins, comp)
            if in_fusion:
                continue  # bytes count at the fusion boundary only
            if ins.op in _SKIP_BYTES_OPS and ins.op != "fusion":
                continue
            out_b = _nbytes(ins.type_str)
            opnd_b = sum(_nbytes(comp.shapes[o]) for o in ins.operands
                         if o in comp.shapes)
            res.hbm_bytes += m * (out_b + opnd_b)

            base_op = ins.op.replace("-start", "")
            if base_op in ("all-gather", "all-reduce", "reduce-scatter",
                           "all-to-all", "collective-permute",
                           "collective-broadcast"):
                g = _group_size(ins.line)
                out_b_c = _nbytes(ins.type_str)
                stat = res.collectives.setdefault(base_op,
                                                  CollectiveStat(base_op))
                stat.count += m
                if base_op == "all-gather":
                    operand = out_b_c / max(g, 1)
                    wire = out_b_c * (g - 1) / max(g, 1)
                elif base_op == "all-reduce":
                    operand = out_b_c
                    wire = 2.0 * out_b_c * (g - 1) / max(g, 1)
                elif base_op == "reduce-scatter":
                    operand = out_b_c * g
                    wire = out_b_c * (g - 1)
                elif base_op == "all-to-all":
                    operand = out_b_c
                    wire = out_b_c * (g - 1) / max(g, 1)
                else:  # permute / broadcast
                    operand = out_b_c
                    wire = out_b_c
                stat.operand_bytes += m * operand
                stat.wire_bytes += m * wire

    for cname, comp in comps.items():
        for ins in comp.instrs:
            if ins.op == "while":
                cond = _attr_comp(ins.line, "condition")
                if cond in comps:
                    res.trip_counts[cname + "/" + ins.name] = _trip_count(
                        comps[cond])
    return res


# ------------------------------------------------------------ roofline ----

#: ``device_kind`` JAX reports for a TPU v5e chip
V5E_KIND = "TPU v5 lite"

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (four links of 50 GB/s).
PEAKS = {
    V5E_KIND: {
        "flops_bf16": 197e12,   # per chip
        "hbm_gbps": 819e9,      # per chip
        "ici_gbps": 50e9,       # per link
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_terms(a: HLOAnalysis, device_kind: str) -> dict[str, float]:
    """Per-chip time (s) if each resource were the only bottleneck, on the
    chip ``device_kind`` names (a key of :data:`PEAKS`)."""
    hw = peaks(device_kind)
    return {
        "compute_s": a.flops / hw["flops_bf16"],
        "memory_s": a.hbm_bytes / hw["hbm_gbps"],
        "collective_s": a.collective_wire_bytes / hw["ici_gbps"],
    }
