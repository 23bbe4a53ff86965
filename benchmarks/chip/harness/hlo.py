"""Bytes each device hands to collectives in one run of a compiled program.

Parses the compiled HLO text: every collective instruction's payload bytes,
times the trip counts of the ``while`` loops that hold it (XLA records
``known_trip_count`` on each loop it can bound).  The payload of a
collective is its operand: for ``collective-permute`` and ``all-reduce``
that is the result shape (for their ``-start`` forms, the first element of
the result tuple and the whole result respectively); for ``all-gather`` the
result over the group size; for ``reduce-scatter`` the result times it.

The arithmetic follows the program's own ``launch/roofline.py`` parser
(shapes x dtype bytes), extended by the loop multipliers that a static
count lacks.
"""
from __future__ import annotations

import json
import re
from typing import Dict, List, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")

_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                    r"([a-z][a-z0-9\-]*)\((.*)$")
_CALLEES = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_BODY = re.compile(r"body=%?([\w.\-]+)")
_CONST = re.compile(r"=\s*s32\[\]\S*\s+constant\((\d+)\)")
_GROUPS = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _top_elements(shape: str) -> List[str]:
    """Top-level elements of a tuple shape ``(a, (b, c), d)``."""
    s = shape.strip()
    if not s.startswith("("):
        return [s]
    s = s[1:-1] if s.endswith(")") else s[1:]
    out, depth, cur = [], 0, ""
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        out.append(cur)
    return out


def _group_size(rest: str) -> int:
    m = _GROUPS.search(rest)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    m = _GROUPS_IOTA.search(rest)
    if m:
        return int(m.group(2))
    return 1


def payload_bytes(kind: str, phase: str, shape: str, rest: str) -> int:
    if kind == "collective-permute" and phase == "-start":
        return shape_bytes(_top_elements(shape)[0])
    if kind == "all-gather":
        if phase == "-start":
            return shape_bytes(_top_elements(shape)[0])
        return shape_bytes(shape) // max(_group_size(rest), 1)
    if kind == "reduce-scatter":
        return shape_bytes(shape) * _group_size(rest)
    return shape_bytes(shape)


def parse(hlo_text: str):
    """(computations: name -> [(kind, bytes)], edges: name -> [(callee,
    multiplier)], entry name).

    A loop's trip count is its ``known_trip_count`` where XLA recorded it;
    otherwise (the TPU compiler drops it) that of a counted loop whose
    condition is ``counter < constant``: the loops of ``lax.scan`` and
    ``fori_loop`` count from 0 by 1."""
    comps: Dict[str, List[Tuple[str, int]]] = {}
    edges: Dict[str, List[Tuple[str, int]]] = {}
    bound: Dict[str, int] = {}          # condition -> its s32 constant
    lt: Dict[str, bool] = {}            # condition's root is a LT compare
    loops = []                          # (computation, body, condition, n)
    entry, cur = None, None
    for line in hlo_text.splitlines():
        head = _COMP_HEAD.match(line)
        if head and not line[:1].isspace():
            cur = head.group(2)
            comps.setdefault(cur, [])
            edges.setdefault(cur, [])
            if head.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        c = _CONST.search(line)
        if c:
            bound[cur] = int(c.group(1))
        if line.lstrip().startswith("ROOT") and " compare(" in line:
            lt[cur] = "direction=LT" in line
        m = _INSTR.match(line)
        if not m:
            continue
        _, shape, op, rest = m.groups()
        if op == "while":
            t = _TRIP.search(rest)
            loops.append((cur, _BODY.search(rest).group(1),
                          _COND.search(rest).group(1),
                          int(t.group(1)) if t else None))
            continue
        for callee in _CALLEES.findall(rest):
            edges[cur].append((callee, 1))
        for grp in _BRANCHES.findall(rest):
            for callee in grp.split(","):
                edges[cur].append((callee.strip().lstrip("%"), 1))
        for kind in KINDS:
            if op == kind or op == kind + "-start":
                phase = op[len(kind):]
                comps[cur].append((kind, payload_bytes(kind, phase, shape,
                                                       rest)))
    for where, body, cond, n in loops:
        if n is None:
            n = bound.get(cond, 1) if lt.get(cond) else 1
        edges[where].append((body, n))
        edges[where].append((cond, n))
    return comps, edges, entry


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Bytes per device per run of the program, by collective kind."""
    comps, edges, entry = parse(hlo_text)
    out = {k: 0 for k in KINDS}
    if entry is None:
        return out

    def visit(name: str, mult: int, depth: int = 0):
        if depth > 64 or name not in comps:
            return
        for kind, b in comps[name]:
            out[kind] += b * mult
        for callee, m in edges.get(name, []):
            visit(callee, mult * m, depth + 1)

    visit(entry, 1)
    return out


def total_bytes(hlo_text: str) -> int:
    return sum(collective_bytes(hlo_text).values())


if __name__ == "__main__":
    import sys
    print(json.dumps(collective_bytes(open(sys.argv[1]).read())))
