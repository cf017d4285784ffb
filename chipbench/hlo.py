"""What the compiled step says about itself: its memory plan, the bytes
its all-to-alls move and the op metadata of its instructions.

All of it is read from ``compiled.memory_analysis()`` and the text of the
compiled (per-device) HLO module.  An instruction inside a counted loop
(what ``lax.scan`` lowers to: ``counter < constant``) counts once per trip.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len,
                                               reverse=True))
                    + r")\[([0-9,]*)\]")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                    r"([a-z][\w\-]*)\((.*)$")
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def plan_bytes(ma) -> int:
    """Arguments + temporaries + outputs - aliased: the compiler's plan of
    one device's memory for the step, not a measured peak."""
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _instructions(text: str) -> Iterator[Tuple[str, str, str, str, str]]:
    """(computation, name, result type, opcode, rest of line) of every
    instruction."""
    comp = None
    for line in text.splitlines():
        if line[:1] not in ("", " ", "\t"):
            m = _COMP.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            yield comp, m.group(1), m.group(2), m.group(3), m.group(4)


def _trip_counts(text: str) -> Dict[str, int]:
    """Loop condition computation -> trip count, for the counted loops a
    ``scan`` lowers to: the counter from 0 in steps of 1 while it is less
    than an ``s32[]`` constant."""
    bound: Dict[str, int] = {}
    trips: Dict[str, int] = {}
    for comp, _, result, op, rest in _instructions(text):
        if op == "constant" and result.startswith("s32[]"):
            bound.setdefault(comp, int(rest.split(")")[0]))
        elif op == "compare" and "direction=LT" in rest and comp in bound:
            trips[comp] = bound[comp]
    return trips


def _multipliers(text: str) -> Dict[str, int]:
    """How many times each computation runs per run of the entry: the
    product of the trip counts of the loops around it."""
    trips = _trip_counts(text)
    calls: Dict[str, List[Tuple[str, int]]] = {}
    for comp, _, _, op, rest in _instructions(text):
        cond = _COND.search(rest)
        n = trips.get(cond.group(1), 1) if (op == "while" and cond) else 1
        for one, many in _CALLED.findall(rest):
            for callee in ([one] if one else re.split(r",\s*", many)):
                calls.setdefault(comp, []).append((callee.lstrip("%"), n))
        calls.setdefault(comp, [])
    callees = {c for v in calls.values() for c, _ in v}
    mult: Dict[str, int] = {}
    stack = [(c, 1) for c in calls if c not in callees]
    while stack:
        comp, n = stack.pop()
        if mult.get(comp, 0) >= n:
            continue
        mult[comp] = n
        stack += [(c, n * k) for c, k in calls.get(comp, [])]
    return mult


def all_to_all_bytes(text: str) -> int:
    """Bytes of every all-to-all's result, per device, per run."""
    mult = _multipliers(text)
    return sum(shape_bytes(result) * mult.get(comp, 1)
               for comp, _, result, op, _ in _instructions(text)
               if op in ("all-to-all", "all-to-all-start"))


def op_names(text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` of its metadata: the jit path,
    ``.../jit(<kernel>)/pallas_call`` for a Pallas kernel."""
    out = {}
    for _, name, _, _, rest in _instructions(text):
        meta = _OP_NAME.search(rest)
        if meta:
            out[name] = meta.group(1)
    return out
