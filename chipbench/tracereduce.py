"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the ops
that ran on each device (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane, each named by its HLO instruction) and the benchmark's own host
spans (``TraceAnnotation`` events named ``bench.<part>``), on one clock.
The compiled HLO's op metadata (``hlo.op_names``) tells which kernel or
layer an op belongs to.  ``Reduced`` then gives:

* busy time: the union of the intervals in which an op ran on a device;
* idle share: 1 - busy / window, averaged over the devices;
* device time of the ops whose name or op metadata contains a kernel's
  name, per device;
* exposed collective time: the part of a collective's interval during
  which no other op ran on that device;
* the idle gaps, each named by the host span it fell in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")

Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Op:
    start: int          # ns
    end: int            # ns
    name: str
    meta: str = ""      # the op's jit path, from the compiled HLO

    def matches(self, needle: str) -> bool:
        return needle in self.name or needle in self.meta


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]        # plane name -> ops, by start
    spans: List[Op]                     # host spans named bench.<part>


# an op event's name is its HLO instruction: "%name = <type> opcode(...)"
_INSTR = re.compile(r"^%?([\w.\-]+)(?:\s*=\s*.*?\s([a-z][\w\-]*)\()?")
# ops whose interval holds other ops of the same line
CONTAINERS = ("while", "conditional", "call")


def parse_op_name(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op event's name."""
    m = _INSTR.match(text)
    if not m:
        return text, ""
    return m.group(1), m.group(2) or ""


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``: the ``XLA Ops`` of
    every device plane, less the loops and calls that hold other ops,
    and the ``bench.*`` spans of the host planes."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, List[Op]] = {}
    spans: List[Op] = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if is_device:
                    name, opcode = parse_op_name(ev.name)
                    if opcode not in CONTAINERS:
                        devices.setdefault(plane.name, []).append(
                            Op(start, end, name))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(Op(start, end, ev.name[len(SPAN_PREFIX):]))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda o: o.start)
    return Trace(devices, spans)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the disjoint sorted ``a`` not in the disjoint sorted ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def is_collective(op: Op) -> bool:
    name = op.name.replace("_", "-")
    return any(c in name or c in op.meta for c in COLLECTIVES)


def label(op: Op) -> str:
    """A name for an op that survives recompilation: the tail of its jit
    path (``transpose(jvp())/.../jit(_grouped_matmul_impl)/pallas_call``)
    where its metadata has one, else its name without the number."""
    paths = [w for w in op.meta.split() if "/" in w]
    if not paths:
        return op.name.rsplit(".", 1)[0]
    parts = [p for p in paths[-1].split("/")
             if p not in ("jit(train_step)", "while", "body", "closed_call",
                          "shard_map")]
    return "/".join(parts[:1] + parts[-3:] if len(parts) > 4 else parts)


class Reduced:
    """The trace of one window ``[lo, hi)`` (ns), reduced."""

    def __init__(self, trace: Trace, lo: Optional[int] = None,
                 hi: Optional[int] = None,
                 names: Optional[Dict[str, str]] = None):
        """``names`` maps an op's name to more of its metadata (the op
        names of the compiled HLO), which kernel matching also reads."""
        if not trace.devices:
            raise ValueError("the trace holds no device ops")
        if lo is None or hi is None:
            if not trace.spans:
                raise ValueError("the trace holds no bench spans")
            lo = trace.spans[0].start
            hi = max(s.end for s in trace.spans)
        self.lo, self.hi = lo, hi
        self.spans = trace.spans
        names = names or {}
        self.ops = {d: [dataclasses.replace(o, meta=names.get(o.name, o.meta))
                        for o in ops if o.end > lo and o.start < hi]
                    for d, ops in trace.devices.items()}
        self.ops = {d: ops for d, ops in self.ops.items() if ops}
        self.busy = {d: union(clip(((o.start, o.end) for o in ops), lo, hi))
                     for d, ops in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran ops."""
        return sum(length(b) for b in self.busy.values()) / 1e9 / \
            len(self.busy)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, needles: Sequence[str]) -> float:
        """Device seconds of the ops matching any of ``needles``, summed
        over each device and averaged over the devices."""
        total = 0
        for ops in self.ops.values():
            total += length(union(clip(
                ((o.start, o.end) for o in ops
                 if any(o.matches(n) for n in needles)), self.lo, self.hi)))
        return total / 1e9 / len(self.ops)

    def exposed_collective_seconds(self, needles: Sequence[str] = COLLECTIVES
                                   ) -> float:
        """Seconds of the collectives matching ``needles`` during which no
        other op ran on the same device, averaged over the devices."""
        total = 0
        for ops in self.ops.values():
            coll = union(clip(((o.start, o.end) for o in ops
                               if any(o.matches(n) for n in needles)),
                              self.lo, self.hi))
            other = union(clip(((o.start, o.end) for o in ops
                                if not is_collective(o)), self.lo, self.hi))
            total += length(subtract(coll, other))
        return total / 1e9 / len(self.ops)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` ops that took the most device time (seconds per
        device), grouped by :func:`label`."""
        acc: Dict[str, int] = {}
        for ops in self.ops.values():
            for o in ops:
                a, b = max(o.start, self.lo), min(o.end, self.hi)
                if b > a:
                    acc[label(o)] = acc.get(label(o), 0) + (b - a)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / 1e9 / len(self.ops)) for k, v in top]

    def gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of the first device, each named by
        the host span that covers most of it (``none`` where no span
        does)."""
        dev = sorted(self.busy)[0]
        idle = subtract([(self.lo, self.hi)], self.busy[dev])
        named = []
        for a, b in idle:
            best, cover = "none", 0
            for s in self.spans:
                c = min(b, s.end) - max(a, s.start)
                if c > cover:
                    best, cover = s.name, c
            named.append((best, (b - a) / 1e9))
        return sorted(named, key=lambda kv: -kv[1])[:n]
