"""Loop-aware HLO analysis.

``compiled.cost_analysis()`` counts a while-loop body exactly ONCE regardless
of trip count (verified empirically — a scan of 16 matmuls reports the flops
of one).  Scan-over-layers models would therefore under-report flops and
collective bytes by ~n_layers.  This module re-derives both from the compiled
HLO text, trip-count aware:

1. split the module into computations and build a per-computation symbol
   table (%name -> shape) from defining lines + header params;
2. per computation, collect dot ops (flops from output shape x contracted
   dims of the lhs, bytes from operand/output shapes) and collective ops
   (output bytes);
3. build the call graph (while bodies, fusions, calls, conditionals); while
   trip counts come from the printed ``known_trip_count`` backend config
   (fallback: the s32 constant in the condition computation);
4. propagate multipliers from ENTRY; total = sum(comp x multiplier).

Dot flops cover >95% of transformer compute; elementwise flops are ignored
(documented in EXPERIMENTS.md §Roofline method).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*([a-z0-9]+)\[([0-9,]*)\]")
_DOT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+dot\(%([\w\.\-]+),\s*%([\w\.\-]+)\),\s*"
    r"(?:.*?lhs_contracting_dims=\{([0-9,]*)\})?")
_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9]+)\[([0-9,]*)\][^ ]*)\s+"
    r"(" + "|".join(c + r"(?:-start)?" for c in _COLLECTIVES) + r")\(")
_WHILE_RE = re.compile(r"\swhile\(")
_WHILE_COND = re.compile(r"condition=%?([\w\.\-]+)")
_WHILE_BODY = re.compile(r"body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_STACK_RE = re.compile(r"stack_frame_id=(\d+)")


def _dims_prod(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _shape_bytes(dtype: str, dims: str) -> int:
    return _dims_prod(dims) * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    dot_flops: float = 0.0
    dot_bytes: float = 0.0
    coll_bytes: dict[str, float] = field(default_factory=lambda: defaultdict(float))
    coll_counts: dict[str, int] = field(default_factory=lambda: defaultdict(int))
    whiles: list[tuple[str, str, int | None]] = field(default_factory=list)
    calls: list[str] = field(default_factory=list)
    consts: list[int] = field(default_factory=list)


def _split_computations(text: str):
    comps = []
    cur_name, cur_lines, is_entry, header = None, [], False, ""
    for line in text.splitlines():
        if not line.startswith(" ") and "{" in line and ("->" in line or line.startswith("ENTRY")):
            m = re.match(r"(ENTRY\s+)?%?([\w\.\-]+)", line.strip())
            if m:
                cur_name = m.group(2)
                is_entry = bool(m.group(1))
                header = line
                cur_lines = []
                continue
        if line.startswith("}"):
            if cur_name is not None:
                comps.append((cur_name, is_entry, header, cur_lines))
            cur_name, is_entry = None, False
            continue
        if cur_name is not None:
            cur_lines.append(line)
    return comps


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    for name, is_entry, header, lines in _split_computations(text):
        c = Computation(name, is_entry)
        # symbol table: defining lines + header params
        sym: dict[str, tuple[str, str]] = {}
        for pname, dt, dims in _PARAM_RE.findall(header):
            sym[pname] = (dt, dims)
        for line in lines:
            dm = _DEF_RE.match(line)
            if dm:
                sym[dm.group(1)] = (dm.group(2), dm.group(3))
        for line in lines:
            m = _DOT_RE.search(line)
            if m:
                odt, odims, lhs_name, rhs_name, cdims = m.groups()
                out_elems = _dims_prod(odims)
                k = 1
                lhs = sym.get(lhs_name)
                if lhs is not None and cdims is not None:
                    ld = lhs[1].split(",") if lhs[1] else []
                    for ci in (cdims.split(",") if cdims else []):
                        i = int(ci)
                        if i < len(ld):
                            k *= int(ld[i])
                c.dot_flops += 2.0 * out_elems * k
                ob = _shape_bytes(odt, odims)
                for nm in (lhs_name, rhs_name):
                    s = sym.get(nm)
                    if s is not None:
                        ob += _shape_bytes(*s)
                c.dot_bytes += ob
            mc = _COLL_RE.search(line)
            if mc:
                tup, dt, dims, op = mc.groups()
                kind = op.replace("-start", "")
                size = (sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tup))
                        if tup is not None else _shape_bytes(dt, dims))
                c.coll_bytes[kind] += size
                c.coll_counts[kind] += 1
            if _WHILE_RE.search(line):
                cond = _WHILE_COND.search(line)
                body = _WHILE_BODY.search(line)
                trip = _TRIP_RE.search(line)
                if cond and body:
                    c.whiles.append((cond.group(1), body.group(1),
                                     int(trip.group(1)) if trip else None))
            for mcall in _CALL_RE.finditer(line):
                c.calls.append(mcall.group(1))
            mb = _BRANCH_RE.search(line)
            if mb:
                for nm in mb.group(1).split(","):
                    c.calls.append(nm.strip().lstrip("%"))
            for mk in _CONST_RE.finditer(line):
                c.consts.append(int(mk.group(1)))
        comps[name] = c
    return comps


def _trip_count(comps, cond_name: str, printed: int | None) -> int:
    if printed is not None:
        return printed
    cond = comps.get(cond_name)
    if cond is None or not cond.consts:
        return 1
    return max(cond.consts)


def multipliers(comps: dict[str, Computation]) -> dict[str, float]:
    mult: dict[str, float] = defaultdict(float)
    entries = [c for c in comps.values() if c.is_entry] or list(comps.values())[-1:]
    stack = [(entries[0].name, 1.0)]
    while stack:
        name, m = stack.pop()
        mult[name] += m
        c = comps.get(name)
        if c is None:
            continue
        for cond, body, printed in c.whiles:
            trips = _trip_count(comps, cond, printed)
            stack.append((body, m * trips))
            stack.append((cond, m * (trips + 1)))
        for callee in c.calls:
            if callee in comps:
                stack.append((callee, m))
    return dict(mult)


def analyze(text: str) -> dict:
    """Loop-aware totals from compiled (per-device SPMD) HLO text."""
    comps = parse_module(text)
    mult = multipliers(comps)
    flops = bytes_ = 0.0
    coll: dict[str, float] = defaultdict(float)
    counts: dict[str, float] = defaultdict(float)
    for name, c in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        flops += c.dot_flops * m
        bytes_ += c.dot_bytes * m
        for k, v in c.coll_bytes.items():
            coll[k] += v * m
        for k, v in c.coll_counts.items():
            counts[k] += v * m
    return {
        "dot_flops": flops,
        "dot_bytes": bytes_,
        "collective_bytes": dict(coll),
        "collective_total": sum(coll.values()),
        "collective_counts": dict(counts),
        "n_computations": len(comps),
    }


def count_source_collectives(text: str, op: str = "all-to-all") -> int:
    """Collectives of kind ``op`` in compiled HLO text, counted per source
    operation: the TPU compiler splits a complex collective into one per
    f32 plane, and the halves share the source op's ``stack_frame_id``."""
    frames: set[str] = set()
    n = 0
    for line in text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or m.group(4).removesuffix("-start") != op:
            continue
        frame = _STACK_RE.search(line)
        if frame is None:
            n += 1
        elif frame.group(1) not in frames:
            frames.add(frame.group(1))
            n += 1
    return n
