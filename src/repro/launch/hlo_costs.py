"""Trip-count-aware cost analysis of post-optimization HLO text.

Why this exists: `compiled.cost_analysis()` (HloCostAnalysis) visits a
`while` body ONCE, so any lax.scan-structured model (layer stacks, KV-chunk
attention, SSD chunk scans — i.e. everything here) under-reports FLOPs,
bytes and collectives by the trip count.  Unrolling for the dry-run is not
an option at 62 layers x 32k tokens on a 1-core compile host.  This module
re-derives the three roofline numerators from the HLO text with loop
multipliers:

  flops       — 2 * prod(result) * prod(contracting dims) per dot
                (+1 flop/element for elementwise ops, prod(operand) per
                reduce), times the product of enclosing while trip counts
  hbm bytes   — per *materialized* op: operand sizes + result size
                (fusions count only their operands/result — internal
                values never touch HBM), times trip counts
  collectives — wire-bytes per device under ring algorithms (see
                hlo_stats), times trip counts

Trip counts are parsed from each while's condition computation (the
`compare(%iv, %constant(N)), direction=LT` pattern jax scan/fori emit).

Validated against `cost_analysis()` on fully-unrolled small models in
tests/test_hlo_costs.py (dots dominate; agreement within a few %).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


def xla_cost_analysis(compiled) -> dict[str, float]:
    """XLA's own HloCostAnalysis as a flat dict (``{}`` where the backend
    offers none)."""
    return dict(compiled.cost_analysis() or {})

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "rsqrt",
    "sqrt", "tanh", "negate", "abs", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "select", "compare", "and", "or", "xor", "not",
    "sign", "cosine", "sine", "logistic", "atan2", "remainder",
    "shift-left", "shift-right-arithmetic", "shift-right-logical",
    "clamp", "cbrt", "erf", "is-finite",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_TOKEN = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"            # name
    r"((?:\([^)]*\))|(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))\s+"  # type
    r"([\w\-]+)\(")                                     # opcode
_OPERANDS = re.compile(r"%([\w\.\-]+)")
_GROUPS_EXPLICIT = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w\.\-]+)")
_COND = re.compile(r"condition=%?([\w\.\-]+)")
_BODY = re.compile(r"body=%?([\w\.\-]+)")
_CONST_INT = re.compile(r"constant\((\d+)\)")


def _shape_elems_bytes(type_str: str) -> tuple[int, int]:
    """(elements, bytes) of a (possibly tuple) HLO type string."""
    elems = tot = 0
    for dtype, dims in _SHAPE_TOKEN.findall(type_str):
        b = _DTYPE_BYTES.get(dtype, 4)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        tot += n * b
    return elems, tot


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    line: str
    operands: list = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    by_name: dict = field(default_factory=dict)


_HEADER_NAME = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)")


def parse_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        # computation headers sit at column 0 and end with "{"
        if (line and not line[0].isspace() and line.endswith("{")
                and "->" in line and not line.startswith("HloModule")):
            m = _HEADER_NAME.match(line)
            if m:
                cur = Computation(name=m.group(1))
                comps[cur.name] = cur
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            # parameters: "%p = f32[..] parameter(0)" matches _INSTR; skip rest
            continue
        name, type_str, opcode = m.groups()
        rest = line[m.end():]
        ops = _OPERANDS.findall(rest.split("),")[0] + ")")
        inst = Instr(name=name, type_str=type_str, opcode=opcode, line=line,
                     operands=ops)
        cur.instrs.append(inst)
        cur.by_name[name] = inst
    return comps


def _trip_count(cond: Computation) -> int:
    """Largest integer constant in the loop condition ≈ trip count for
    jax-emitted scans/fori (compare(iv, const), direction=LT)."""
    best = 1
    for inst in cond.instrs:
        if inst.opcode == "constant":
            m = _CONST_INT.search(inst.line)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _wire_bytes(op: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if op == "all-gather":
        return result_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return float(result_bytes) * (g - 1)
    if op == "all-to-all":
        return result_bytes * (g - 1) / g
    return float(result_bytes)   # collective-permute


@dataclass
class Costs:
    flops: float = 0.0
    bytes: float = 0.0
    coll_wire: float = 0.0
    coll_result: float = 0.0
    coll_count: float = 0.0
    coll_by_type: dict = field(default_factory=dict)

    def add(self, other: "Costs", mult: float = 1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.coll_wire += mult * other.coll_wire
        self.coll_result += mult * other.coll_result
        self.coll_count += mult * other.coll_count
        for k, v in other.coll_by_type.items():
            slot = self.coll_by_type.setdefault(
                k, {"count": 0.0, "wire_bytes": 0.0})
            slot["count"] += mult * v["count"]
            slot["wire_bytes"] += mult * v["wire_bytes"]


class HloCostModel:
    def __init__(self, hlo: str):
        self.comps = parse_computations(hlo)
        self._memo: dict[str, Costs] = {}
        entry = None
        for name in self.comps:
            if name.startswith("main") or ".main" in name:
                entry = name
        self.entry = entry or next(iter(self.comps))

    # -- shape helpers ----------------------------------------------------
    def _operand_type(self, comp: Computation, op_name: str) -> str | None:
        inst = comp.by_name.get(op_name)
        return inst.type_str if inst else None

    # -- per-instruction costs --------------------------------------------
    def _instr_costs(self, comp: Computation, inst: Instr,
                     materialized: bool) -> Costs:
        c = Costs()
        op = inst.opcode
        elems, rbytes = _shape_elems_bytes(inst.type_str)

        base = op[:-6] if op.endswith("-start") else op
        if base in _COLLECTIVES:
            g = _group_size(inst.line)
            wire = _wire_bytes(base, rbytes, g)
            c.coll_wire += wire
            c.coll_result += rbytes
            c.coll_count += 1
            slot = c.coll_by_type.setdefault(
                base, {"count": 0.0, "wire_bytes": 0.0})
            slot["count"] += 1
            slot["wire_bytes"] += wire
            if materialized:
                c.bytes += rbytes * 2        # read + write locally
            return c

        if op == "dot":
            contract = 1
            m = _CONTRACT.search(inst.line)
            lhs_t = self._operand_type(comp, inst.operands[0]) \
                if inst.operands else None
            if m and lhs_t:
                dims_str = m.group(1)
                shape = _SHAPE_TOKEN.search(lhs_t)
                if shape and dims_str:
                    dims = [int(d) for d in shape.group(2).split(",")] \
                        if shape.group(2) else []
                    for ci in dims_str.split(","):
                        i = int(ci)
                        if i < len(dims):
                            contract *= dims[i]
            c.flops += 2.0 * elems * contract
        elif op in _ELEMENTWISE:
            c.flops += float(elems)
        elif op == "reduce" or op == "reduce-window":
            in_t = self._operand_type(comp, inst.operands[0]) \
                if inst.operands else None
            in_elems, _ = _shape_elems_bytes(in_t) if in_t else (elems, 0)
            c.flops += float(in_elems)
        elif op == "convolution":
            # none of our models convolve post-stub; coarse: 2*out*k window
            c.flops += 2.0 * elems

        if materialized and op not in ("parameter", "constant", "tuple",
                                       "get-tuple-element", "bitcast",
                                       "while", "conditional"):
            if op == "dynamic-slice":
                # touches only the sliced region (read) + result (write);
                # counting the full operand would bill a whole KV cache
                # for every per-layer slice
                c.bytes += 2 * rbytes
            elif op == "dynamic-update-slice":
                # in-place semantics: update read + region write; the
                # target buffer is aliased, not streamed
                upd = 0
                if len(inst.operands) >= 2:
                    t = self._operand_type(comp, inst.operands[1])
                    if t:
                        upd = _shape_elems_bytes(t)[1]
                c.bytes += 2 * upd if upd else rbytes
            else:
                opbytes = 0
                for o in inst.operands:
                    t = self._operand_type(comp, o)
                    if t:
                        opbytes += _shape_elems_bytes(t)[1]
                c.bytes += rbytes + opbytes
        return c

    # -- computation costs (memoized, recursive) ---------------------------
    def comp_costs(self, name: str, materialized: bool = True) -> Costs:
        key = f"{name}|{materialized}"
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(name)
        total = Costs()
        self._memo[key] = total          # break cycles defensively
        if comp is None:
            return total
        for inst in comp.instrs:
            op = inst.opcode
            if op == "while":
                body = _BODY.search(inst.line)
                cond = _COND.search(inst.line)
                trips = 1
                if cond and cond.group(1) in self.comps:
                    trips = _trip_count(self.comps[cond.group(1)])
                if body:
                    total.add(self.comp_costs(body.group(1), materialized),
                              mult=float(trips))
            elif op == "fusion":
                m = _CALLS.search(inst.line)
                if m:
                    inner = self.comp_costs(m.group(1), materialized=False)
                    total.add(inner)
                total.add(self._instr_costs(comp, inst, materialized))
            elif op in ("call", "custom-call", "conditional", "map",
                        "reduce", "sort", "scatter", "select-and-scatter",
                        "reduce-window"):
                total.add(self._instr_costs(comp, inst, materialized))
                m = _CALLS.search(inst.line)
                if m and m.group(1) in self.comps:
                    total.add(self.comp_costs(m.group(1),
                                              materialized=False))
            else:
                total.add(self._instr_costs(comp, inst, materialized))
        self._memo[key] = total
        return total

    def entry_costs(self) -> Costs:
        return self.comp_costs(self.entry)


def analyze(hlo: str) -> dict:
    """Loop-aware per-device costs from post-optimization HLO text."""
    cm = HloCostModel(hlo)
    c = cm.entry_costs()
    return {
        "flops": c.flops,
        "bytes": c.bytes,
        "collectives": {
            "total": {"count": c.coll_count,
                      "result_bytes": c.coll_result,
                      "wire_bytes": c.coll_wire},
            **c.coll_by_type,
        },
    }
