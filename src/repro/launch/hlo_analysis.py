"""Trip-count-aware HLO cost extraction.

``compiled.cost_analysis()`` visits each while-loop body ONCE, so any scanned
program (scan-over-layers, flash chunk scans, chunkwise recurrences) is
undercounted by orders of magnitude.  The optimized HLO text, however, carries
``known_trip_count`` on every scan-derived while op.  This module parses the
module text into its computation graph and accumulates

  * matmul FLOPs (from ``dot`` ops: 2 x prod(output dims) x contracted size),
  * matmul memory traffic (lhs + rhs + out bytes per execution — an upper
    bound on HBM traffic that ignores fusion reuse; standard roofline proxy),
  * collective bytes by kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute),

multiplying through while-loop trip counts (nested loops compose) and taking
the max over conditional branches.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
    "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"(f64|f32|bf16|f16|f8e\w+|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64|c128)"
    r"\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_OPCODE_RE = re.compile(r"^((?:\([^)]*\)|[\w\[\]{},.\s])*?)\s([\w\-]+)\(")
_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"')
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        key = "f8" if dt.startswith("f8") else dt
        total += _shape_elems(dims) * _DTYPE_BYTES.get(key, 4)
    return total


def _type_elems(type_str: str) -> int:
    total = 0
    for _, dims in _SHAPE_RE.findall(type_str):
        total += _shape_elems(dims)
    return total


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    dot_bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVES})
    coll_count: float = 0.0

    def add(self, other: "Totals", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.dot_bytes += other.dot_bytes * mult
        for k in COLLECTIVES:
            self.coll[k] += other.coll[k] * mult
        self.coll_count += other.coll_count * mult


class _Comp:
    def __init__(self, name: str):
        self.name = name
        self.shapes: Dict[str, str] = {}       # instr name -> type string
        self.own = Totals()
        self.whiles: List[Tuple[str, int]] = []     # (body comp, trips)
        self.calls: List[str] = []
        self.branches: List[List[str]] = []
        self.dots: List[str] = []              # raw dot lines (2nd pass)
        self.coll_ops: List[Tuple[str, int, str]] = []  # (kind, bytes, meta)


def parse_module(text: str) -> Dict[str, _Comp]:
    comps: Dict[str, _Comp] = {}
    cur: Optional[_Comp] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR_RE.match(line)
        if hdr and ("=" not in line.split("(")[0]):
            cur = _Comp(hdr.group(1))
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        op_m = _OPCODE_RE.match(rhs)
        type_str = rhs.split("=", 1)[0]
        # type string is everything before the opcode
        if op_m:
            type_str, opcode = op_m.group(1), op_m.group(2)
        else:
            opcode = ""
        cur.shapes[name] = type_str
        if opcode == "dot":
            cur.dots.append(rhs)
        elif opcode in COLLECTIVES or opcode.rstrip("-start") in COLLECTIVES:
            base = opcode[:-6] if opcode.endswith("-start") else opcode
            if base in COLLECTIVES:
                nb = _type_bytes(type_str)
                cur.own.coll[base] += nb
                cur.own.coll_count += 1
                meta = ""
                mm = re.search(r'op_name="([^"]*)"', rhs)
                if mm:
                    meta = mm.group(1)[-120:]
                cur.coll_ops.append((base, nb, meta or type_str.strip()[:80]))
        elif opcode == "while":
            body = _BODY_RE.search(rhs)
            trip = _TRIP_RE.search(rhs)
            if body:
                cur.whiles.append(
                    (body.group(1), int(trip.group(1)) if trip else 1))
        elif opcode == "conditional":
            br = _BRANCH_RE.search(rhs)
            if br:
                names = [b.strip().lstrip("%") for b in br.group(1).split(",")]
                cur.branches.append(names)
        elif opcode in ("call", "fusion", "custom-call", "reduce",
                        "reduce-window", "sort", "scatter", "map", "select-and-scatter"):
            for cal in _CALLS_RE.findall(rhs):
                cur.calls.append(cal)
    # second pass: dot flops need operand shapes
    for comp in comps.values():
        for rhs in comp.dots:
            _account_dot(comp, rhs)
    return comps


def _account_dot(comp: _Comp, rhs: str) -> None:
    op_m = _OPCODE_RE.match(rhs)
    out_type = op_m.group(1)
    args_part = rhs.split("dot(", 1)[1].split(")")[0]
    operand_names = _OPERANDS_RE.findall(args_part)
    lhs_c = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
    contracted = 1
    lhs_type = comp.shapes.get(operand_names[0]) if operand_names else None
    if lhs_c and lhs_type:
        m = _SHAPE_RE.search(lhs_type)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            for ci in lhs_c.group(1).split(","):
                if ci and int(ci) < len(dims):
                    contracted *= dims[int(ci)]
    out_elems = _type_elems(out_type)
    comp.own.flops += 2.0 * out_elems * contracted
    comp.own.dot_bytes += _type_bytes(out_type)
    for nm in operand_names[:2]:
        t = comp.shapes.get(nm)
        if t:
            comp.own.dot_bytes += _type_bytes(t)


def analyze(text: str, entry: Optional[str] = None) -> Totals:
    comps = parse_module(text)
    # find entry computation: the one declared with ENTRY, else "main*"
    entry_name = entry
    if entry_name is None:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
        entry_name = m.group(1) if m else next(iter(comps))
    memo: Dict[str, Totals] = {}

    def total(name: str, stack=()) -> Totals:
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return Totals()
        c = comps[name]
        t = Totals()
        t.add(c.own)
        for body, trips in c.whiles:
            t.add(total(body, stack + (name,)), trips)
        for cal in c.calls:
            t.add(total(cal, stack + (name,)))
        for branch in c.branches:
            best = None
            for b in branch:
                bt = total(b, stack + (name,))
                if best is None or bt.flops > best.flops:
                    best = bt
            if best:
                t.add(best)
        memo[name] = t
        return t

    return total(entry_name)


def top_collectives(text: str, n: int = 20) -> List[Tuple[float, str, str]]:
    """Per-op collective contributions with trip multipliers applied:
    returns [(total_bytes, kind, op_name_metadata)] sorted descending."""
    comps = parse_module(text)
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    entry_name = m.group(1) if m else next(iter(comps))
    out: List[Tuple[float, str, str]] = []

    def walk(name: str, mult: float, stack=()):
        if name not in comps or name in stack:
            return
        c = comps[name]
        for kind, nb, meta in c.coll_ops:
            out.append((nb * mult, kind, meta))
        for body, trips in c.whiles:
            walk(body, mult * trips, stack + (name,))
        for cal in c.calls:
            walk(cal, mult, stack + (name,))
        for branch in c.branches:
            for b in branch:
                walk(b, mult, stack + (name,))

    walk(entry_name, 1.0)
    out.sort(reverse=True)
    return out[:n]
