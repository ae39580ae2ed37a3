"""HLO-text cost model with while-loop trip-count accounting.

``compiled.cost_analysis()`` counts each while-loop body **once**, but our
layers run under `lax.scan` (and attention/Mamba/xLSTM scan internally), so
raw numbers understate FLOPs/bytes/collective-bytes by the trip counts.
This walker parses the post-SPMD HLO, builds the computation call graph,
extracts each while's trip count from its condition (`compare(iv, const),
direction=LT` — the shape `lax.scan` lowers to), and accumulates:

  flops            — 2·numel(out)·K over every `dot` (batch dims included
                     via numel(out)); convolutions are absent from our
                     models (the causal conv lowers to multiplies).
  bytes            — Σ (operand + output bytes) of every op in non-fused
                     computations; fusion internals are skipped (the fusion
                     op's own operands/outputs are the HBM traffic).
  collective bytes — output bytes of all-gather / all-reduce /
                     reduce-scatter / all-to-all / collective-permute.

All numbers are per-device (the module is the per-device SPMD program).
Validated against cost_analysis on scan-free functions in tests.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_hlo", "HloCost"]


_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
                "pred": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
                "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_NAME_RE = re.compile(r"^%?([\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")


def _parse_op_line(line: str):
    """Tokenize `[ROOT] %name = TYPE opcode(args), attrs`.

    TYPE may be a tuple containing `/*index=N*/` comments (which contain
    '='), so a paren-balance walk is the only robust parse.
    """
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    m = _NAME_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[m.end():]
    if rest.startswith("("):
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        out_blob = rest[:end + 1]
        rest = rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        out_blob = rest[:sp]
        rest = rest[sp + 1:].lstrip()
    m2 = _OPCODE_RE.match(rest)
    if not m2:
        return None
    opcode = m2.group(1)
    depth = 0
    start = m2.end() - 1
    end = len(rest) - 1
    for j in range(start, len(rest)):
        ch = rest[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    argstr = rest[start + 1:end]
    attrs = rest[end + 1:]
    return name, out_blob, opcode, argstr, attrs


def _shape_bytes(blob: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(blob):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(blob: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(blob)
    if not m:
        return None
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Op:
    name: str
    out_blob: str
    opcode: str
    args: List[str]
    attrs: str


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    shapes: Dict[str, str] = field(default_factory=dict)  # symbol -> blob
    is_fused: bool = False


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    transcendentals: float = 0.0

    def add(self, other: "HloCost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.transcendentals += other.transcendentals * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) + v * mult

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def _parse_computations(hlo: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    current: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line or line.startswith("HloModule"):
            continue
        hdr = _COMP_HDR_RE.match(line)
        if hdr and line.rstrip().endswith("{"):
            name = hdr.group(1)
            current = Computation(name=name,
                                  is_fused=name.startswith("fused_"))
            comps[name] = current
            if line.lstrip().startswith("ENTRY"):
                entry = name
            # parameters declared in the header: "%p.1: f32[4,4]"
            for pname, pblob in re.findall(r"%?([\w.\-]+):\s*([^,)]+)",
                                           hdr.group(2)):
                current.shapes[pname] = pblob
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        parsed = _parse_op_line(line)
        if parsed is None:
            continue
        name, out_blob, opcode, argstr, attrs = parsed
        args = [a.strip().lstrip("%") for a in _split_args(argstr)]
        current.ops.append(Op(name, out_blob, opcode, args, attrs))
        current.shapes[name] = out_blob
    return comps, entry


def _split_args(argstr: str) -> List[str]:
    """Split top-level commas (shapes contain commas inside brackets)."""
    out, depth, cur = [], 0, []
    for ch in argstr:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    # each arg looks like "bf16[2,3]{1,0} %name" or "%name"
    names = []
    for a in out:
        a = a.strip()
        mm = re.search(r"%([\w.\-]+)\s*$", a)
        names.append(mm.group(1) if mm else a)
    return names


def _arg_shape_blob(comp: Computation, arg: str) -> str:
    return comp.shapes.get(arg, "")


def _dot_flops(comp: Computation, op: Op) -> float:
    out = _shape_dims(op.out_blob)
    if out is None:
        return 0.0
    _, out_dims = out
    numel_out = 1
    for d in out_dims:
        numel_out *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    k = 1
    if m and op.args:
        lhs_blob = _arg_shape_blob(comp, op.args[0])
        lhs = _shape_dims(lhs_blob)
        if lhs is not None:
            _, ldims = lhs
            for idx in m.group(1).split(","):
                if idx != "" and int(idx) < len(ldims):
                    k *= ldims[int(idx)]
    return 2.0 * numel_out * k


_TRIP_CONST_RE = re.compile(r"constant\((\d+)\)")


def _while_trips(comps: Dict[str, Computation], cond_name: str) -> float:
    """Fallback when backend_config lacks known_trip_count: find a
    comparison against a constant in the condition (descending into the
    wrapped fusion computations XLA emits)."""
    cond = comps.get(cond_name)
    if cond is None:
        return 1.0
    consts: Dict[str, int] = {}
    compare_ops: List[Op] = []

    def scan_comp(c: Computation):
        for op in c.ops:
            if op.opcode == "constant":
                mm = re.search(r"constant\((\d+)\)",
                               f"constant({op.args[0]})" if op.args
                               else (op.attrs or ""))
                if mm:
                    consts[op.name] = int(mm.group(1))
            elif op.opcode == "compare":
                compare_ops.append(op)
            elif op.opcode == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", op.attrs)
                if m and m.group(1) in comps:
                    # map fusion args through to see the constant operands
                    for a in op.args:
                        if a in consts:
                            consts[f"__arg_{m.group(1)}"] = consts[a]
                    scan_comp(comps[m.group(1)])

    scan_comp(cond)
    # prefer LT comparisons with a known constant anywhere in the cond
    candidates = [v for k, v in consts.items()]
    if candidates and compare_ops:
        return float(max(candidates))
    return 1.0


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   # control ops alias their carried buffers in place; the
                   # loop body accounts for the actual reads/writes
                   "while", "conditional", "call", "optimization-barrier"}


def _comp_cost(comps: Dict[str, Computation], name: str,
               memo: Dict[str, HloCost]) -> HloCost:
    if name in memo:
        return memo[name]
    comp = comps.get(name)
    cost = HloCost()
    memo[name] = cost
    if comp is None:
        return cost
    for op in comp.ops:
        if op.opcode == "dot":
            cost.flops += _dot_flops(comp, op)
        elif op.opcode == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", op.attrs)
            if m:
                inner = _fused_flops(comps, m.group(1), memo)
                cost.flops += inner
            if not comp.is_fused:
                cost.bytes += _op_bytes(comp, op)
        elif op.opcode == "while":
            mc = re.search(r"condition=%?([\w.\-]+)", op.attrs)
            mb = re.search(r"body=%?([\w.\-]+)", op.attrs)
            # XLA annotates the trip count it proved:
            mt = re.search(r'"known_trip_count":\{"n":"(\d+)"', op.attrs)
            if mt:
                trips = float(mt.group(1))
            elif mc:
                trips = _while_trips(comps, mc.group(1))
            else:
                trips = 1.0
            if mb:
                cost.add(_comp_cost(comps, mb.group(1), memo), trips)
            if mc:
                cost.add(_comp_cost(comps, mc.group(1), memo), trips)
        elif op.opcode in ("call", "async-start"):
            m = re.search(r"(?:to_apply|called_computation)=%?([\w.\-]+)",
                          op.attrs)
            if m:
                cost.add(_comp_cost(comps, m.group(1), memo), 1.0)
        elif op.opcode == "conditional":
            for m in re.finditer(r"(?:true_computation|false_computation|"
                                 r"branch_computations=\{)([\w.,\- %]+)",
                                 op.attrs):
                for b in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    cost.add(_comp_cost(comps, b, memo), 1.0)
        if op.opcode in _COLLECTIVES or \
                any(op.opcode == c + "-start" for c in _COLLECTIVES):
            key = op.opcode.replace("-start", "")
            nbytes = _shape_bytes(op.out_blob)
            cost.collective_bytes[key] = cost.collective_bytes.get(key, 0.0) \
                + nbytes
        if not comp.is_fused and op.opcode not in _SKIP_BYTES_OPS and \
                op.opcode != "fusion":
            cost.bytes += _op_bytes(comp, op)
    return cost


def _fused_flops(comps: Dict[str, Computation], name: str,
                 memo: Dict[str, HloCost]) -> float:
    comp = comps.get(name)
    if comp is None:
        return 0.0
    total = 0.0
    for op in comp.ops:
        if op.opcode == "dot":
            total += _dot_flops(comp, op)
        elif op.opcode == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", op.attrs)
            if m:
                total += _fused_flops(comps, m.group(1), memo)
    return total


def _op_bytes(comp: Computation, op: Op) -> float:
    total = float(_shape_bytes(op.out_blob))
    for a in op.args:
        total += _shape_bytes(_arg_shape_blob(comp, a))
    return total


def analyze_hlo(hlo_text: str) -> HloCost:
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        return HloCost()
    return _comp_cost(comps, entry, {})
