"""Loop-aware HLO analysis: flops / bytes / collective wire bytes.

Why not `compiled.cost_analysis()` alone: XLA's cost analysis counts each
`while` body ONCE, not x trip-count (verified experimentally — a 10-step
scan of a matmul reports the flops of one matmul).  The partitioned
query schedules scan over the index partitions, so their collectives live
inside whiles.  This module walks the computation call graph from ENTRY,
multiplying by loop trip counts (parsed from each while's condition), and
accumulates:

  * flops      — 2 * prod(result dims) * prod(contracting dims) per dot
                 (operand shapes resolved through the computation's SSA
                 table — optimized HLO does not print them inline);
  * bytes      — operand + result bytes at fusion/call boundaries (ops
                 inside a fusion body touch registers/VMEM, not HBM);
  * wire bytes — collective results weighted by ring-algorithm cost:
                 all-reduce 2x, all-gather / reduce-scatter / all-to-all /
                 collective-permute 1x.  Shapes in the partitioned module
                 are PER-DEVICE, so totals are per-device.

Structural estimates (no fabric model), but consistent across programs —
which is what comparing two collective schedules needs.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?(%[\w\.\-]+)\s*\(.*\)\s*->")
_OPLINE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*(.+?)\s+([a-z][a-z0-9\-]*)\(")
_NAME_RE = re.compile(r"%[\w\.\-]+")
_CALLED = re.compile(
    r"(?:calls=|body=|condition=|to_apply=|true_computation=|"
    r"false_computation=|branch_computations=\{)\s*([%\w\.\-, ]+)\}?")
_CONST_S32 = re.compile(r"constant\((\d+)\)")
_COMPARE = re.compile(
    r"compare\(([^)]*)\),?.*direction=(LT|LE|GT|GE)")

_NO_DATA = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "after-all", "partition-id", "replica-id", "iota"}


def _nelem(dims: str) -> int:
    if not dims:
        return 1
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


@dataclasses.dataclass
class OpInfo:
    name: str
    kind: str
    result_bytes: int
    result_elems: int
    dims: List[List[int]]        # dims of each shape in the result
    operands: List[str]
    called: List[str]
    line: str
    const_val: Optional[int] = None


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[OpInfo]
    table: Dict[str, OpInfo]


def _parse_result(result_part: str) -> Tuple[int, int, List[List[int]]]:
    nbytes, nelems, dims = 0, 0, []
    for dt, dd in _SHAPE_RE.findall(result_part):
        e = _nelem(dd)
        nbytes += e * _DTYPE_BYTES.get(dt, 4)
        nelems += e
        dims.append([int(x) for x in dd.split(",")] if dd else [])
    return nbytes, nelems, dims


def parse_module(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR.match(line)
        if hdr and ("{" in line):
            cur = Computation(name=hdr.group(1), ops=[], table={})
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        om = _OPLINE.match(line)
        if not om:
            continue
        name, result_part, kind = om.groups()
        nbytes, nelems, dims = _parse_result(result_part)
        # operand names: everything inside the first paren group
        after = line[om.end():]
        depth, i = 1, 0
        while i < len(after) and depth:
            if after[i] == "(":
                depth += 1
            elif after[i] == ")":
                depth -= 1
            i += 1
        args = after[:i - 1] if depth == 0 else after
        operands = _NAME_RE.findall(args)
        called = []
        for cg in _CALLED.finditer(line):
            for c in cg.group(1).split(","):
                c = c.strip()
                if c.startswith("%"):
                    called.append(c)
        operands = [o for o in operands if o not in called]
        const_val = None
        if kind == "constant":
            cv = _CONST_S32.search(line)
            if cv:
                const_val = int(cv.group(1))
        op = OpInfo(name=name, kind=kind, result_bytes=nbytes,
                    result_elems=nelems, dims=dims, operands=operands,
                    called=called, line=line, const_val=const_val)
        cur.ops.append(op)
        cur.table[name] = op
    return comps, entry


def _dot_flops(op: OpInfo, comp: Computation) -> float:
    cd = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    k = 1
    if cd and op.operands:
        lhs = comp.table.get(op.operands[0])
        if lhs and lhs.dims:
            ld = lhs.dims[0]
            for i in (cd.group(1).split(",") if cd.group(1) else []):
                idx = int(i)
                if idx < len(ld):
                    k *= ld[idx]
    return 2.0 * op.result_elems * k


def _conv_flops(op: OpInfo, comp: Computation) -> float:
    k = 1
    if len(op.operands) >= 2:
        ker = comp.table.get(op.operands[1])
        if ker and ker.dims:
            k = _nelem(",".join(map(str, ker.dims[0])))
    return 2.0 * op.result_elems * k


def _operand_bytes(op: OpInfo, comp: Computation) -> int:
    return sum(comp.table[o].result_bytes for o in op.operands
               if o in comp.table)


def _op_traffic(op: OpInfo, comp: Computation) -> float:
    """HBM traffic estimate for one op.

    Slicing ops read/write only the slice, not the whole operand buffer
    (charging full operands made scan-over-stacked-params look like it
    re-reads all layers' weights every layer).  Loop fusions are capped the
    same way: each operand contributes at most the fusion's result size,
    except kInput (reduction) fusions which legitimately read operands
    larger than their result.
    """
    k = op.kind
    if k == "dynamic-slice" or k == "gather" or k == "copy" or k == "slice":
        return 2.0 * op.result_bytes
    if k == "dynamic-update-slice":
        upd = (comp.table[op.operands[1]].result_bytes
               if len(op.operands) > 1 and op.operands[1] in comp.table
               else op.result_bytes)
        return 2.0 * upd
    if k == "scatter":
        upd = (comp.table[op.operands[2]].result_bytes
               if len(op.operands) > 2 and op.operands[2] in comp.table
               else op.result_bytes)
        return 2.0 * upd
    if k == "fusion":
        cap = "kind=kInput" not in op.line
        total = op.result_bytes
        for o in op.operands:
            ob = comp.table[o].result_bytes if o in comp.table else 0
            total += min(ob, op.result_bytes) if cap else ob
        return float(total)
    return float(op.result_bytes + _operand_bytes(op, comp))


def _trip_count(cond: Computation) -> Optional[int]:
    for op in cond.ops:
        m = _COMPARE.search(op.line)
        if m:
            names = _NAME_RE.findall(m.group(1))
            d = m.group(2)
            for n in names:
                src = cond.table.get(n)
                if src is not None and src.const_val is not None:
                    return src.const_val + (1 if d in ("LE", "GE") else 0)
            # inline constant in the compare args
            cv = _CONST_S32.search(m.group(1))
            if cv:
                return int(cv.group(1)) + (1 if d in ("LE", "GE") else 0)
    consts = [o.const_val for o in cond.ops if o.const_val is not None]
    return max(consts) if consts else None


def analyze(text: str) -> Dict[str, float]:
    comps, entry = parse_module(text)
    if entry is None:
        return dict(flops=0.0, bytes=0.0, total=0.0, parse_error=1.0)

    totals = dict(flops=0.0, bytes=0.0, unknown_trip=0.0)
    coll = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0.0 for k in _COLLECTIVES}

    def walk(comp_name: str, mult: float, in_fusion: bool, stack):
        if comp_name not in comps or comp_name in stack:
            return
        comp = comps[comp_name]
        stack = stack | {comp_name}
        for op in comp.ops:
            if op.kind == "while":
                bm = re.search(r"body=([%\w\.\-]+)", op.line)
                cm = re.search(r"condition=([%\w\.\-]+)", op.line)
                trip = None
                if cm and cm.group(1) in comps:
                    trip = _trip_count(comps[cm.group(1)])
                if trip is None:
                    trip = 1
                    totals["unknown_trip"] += 1
                if bm:
                    walk(bm.group(1), mult * trip, in_fusion, stack)
                continue
            base = op.kind.replace("-start", "")
            if base in _COLLECTIVES and not op.kind.endswith("-done"):
                coll[base] += mult * op.result_bytes * _WIRE_FACTOR[base]
                counts[base] += mult
                for c in op.called:          # all-reduce reducer (tiny)
                    walk(c, mult, True, stack)
                continue
            if op.kind == "fusion":
                if not in_fusion:
                    totals["bytes"] += mult * _op_traffic(op, comp)
                for c in op.called:
                    walk(c, mult, True, stack)
                continue
            if op.called:
                for c in op.called:
                    walk(c, mult, True, stack)
            if op.kind == "dot":
                totals["flops"] += mult * _dot_flops(op, comp)
            elif op.kind == "convolution":
                totals["flops"] += mult * _conv_flops(op, comp)
            if not in_fusion and op.kind not in _NO_DATA:
                totals["bytes"] += mult * _op_traffic(op, comp)
        return

    walk(entry, 1.0, False, frozenset())
    out = dict(flops=totals["flops"], bytes=totals["bytes"],
               unknown_trip=totals["unknown_trip"])
    out.update({f"bytes_{k}": v for k, v in coll.items()})
    out.update({f"count_{k}": v for k, v in counts.items()})
    out["total"] = sum(coll.values())
    return out
