"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

A TPU device plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per executed HLO instruction, named by the instruction's text
(``%int8_decompress_reduce.14 = f32[...] custom-call(...)``). Control-flow
instructions (``while``, ``conditional``) span the instructions of their
bodies, so events nest. Host planes hold one line per thread; the
benchmark's own spans (``bench.*``) and the Python tracer's function events
are on the ``python`` lines, on the same clock as the device.

Everything below works on plain tuples ``(name, start_ns, end_ns)`` so that
it can be checked on a trace made up by hand.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` of one trace. A
    device plane's lines of one name are read as one (the profiler can
    split a line, such as ``XLA Ops``, over several); a host plane's
    threads of one name stay apart (``<name>#<index>``)."""
    from jax.profiler import ProfileData
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device")
        lines: Dict[str, List[Event]] = {}
        for i, line in enumerate(plane.lines):
            key = (line.name if device or line.name not in lines
                   else f"{line.name}#{i}")
            lines.setdefault(key, []).extend(
                (e.name, float(e.start_ns), float(e.end_ns))
                for e in line.events)
        out[plane.name] = lines
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event (a loop's body ops, not the
    loop)."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for k, (n, s, e) in enumerate(ev):
        nxt = ev[k + 1] if k + 1 < len(ev) else None
        if nxt is not None and nxt[1] < e and nxt[2] <= e:
            continue          # the next event starts inside this one
        out.append((n, s, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Per instruction name, its duration less that of the events nested
    in it, summed."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    total: Dict[str, float] = {}
    stack: List[List] = []            # [name, end, child_time]

    def close(item):
        n, e, child, s = item
        total[n] = total.get(n, 0.0) + (e - s) - child
        if stack:
            stack[-1][2] += e - s

    for n, s, e in ev:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        stack.append([op_name(n), e, 0.0, s])
    while stack:
        close(stack.pop())
    return total


def device_ops(planes) -> Dict[int, List[Event]]:
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and OPS_LINE in lines:
            out[int(m.group(1))] = lines[OPS_LINE]
    return out


def host_span(planes, name: str) -> Optional[Tuple[Interval, List[Event]]]:
    """The first host event called ``name`` and all events of its line."""
    for pname, lines in planes.items():
        if pname.startswith("/device"):
            continue
        for events in lines.values():
            for n, s, e in events:
                if n == name:
                    return (s, e), events
    return None


def busy(ops: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    return clip(union((s, e) for _, s, e in ops), lo, hi)


def kernel_time(ops: Sequence[Event], prefix: str, lo: float, hi: float
                ) -> Tuple[float, int]:
    """(summed ns, call count) of the events whose instruction name starts
    with ``prefix``."""
    t, n = 0.0, 0
    for name, s, e in ops:
        if op_name(name).startswith(prefix):
            c = clip([(s, e)], lo, hi)
            if c:
                t += length(c)
                n += 1
    return t, n


def exposed_collective(ops: Sequence[Event], lo: float, hi: float) -> float:
    """ns of collective instructions during which no other instruction runs
    on the device (innermost events only, so the loop that holds a
    collective does not hide it)."""
    inner = leaves(ops)
    coll = union((s, e) for n, s, e in inner
                 if any(c in op_name(n) for c in COLLECTIVES))
    other = union((s, e) for n, s, e in inner
                  if not any(c in op_name(n) for c in COLLECTIVES))
    coll = clip(coll, lo, hi)
    return length(coll) - length(intersect(coll, other))


def idle_gaps(busy_iv: Sequence[Interval], lo: float, hi: float
              ) -> List[Interval]:
    gaps, t = [], lo
    for s, e in busy_iv:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_gap(gap: Interval, host: Sequence[Event]) -> str:
    """The innermost host event on the main thread that covers the gap's
    midpoint: what the host was doing while the device waited."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for n, s, e in host:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "host: untraced"


def reduce(planes, window_span: str = "bench.window",
           kernels: Sequence[str] = ("int8_decompress_reduce",),
           top: int = 10) -> Optional[dict]:
    """The device numbers of one traced window, or None where the trace
    holds no device operation."""
    found = host_span(planes, window_span)
    devs = device_ops(planes)
    if found is None or not devs:
        return None
    (lo, hi), host = found
    per_dev = {}
    for d, ops in sorted(devs.items()):
        b = busy(ops, lo, hi)
        per_dev[d] = {
            "busy_ns": length(b),
            "kernels": {k: kernel_time(ops, k, lo, hi) for k in kernels},
            "exposed_collective_ns": exposed_collective(ops, lo, hi),
        }
    ops0 = [(n, max(s, lo), min(e, hi)) for n, s, e in devs[min(devs)]
            if min(e, hi) > max(s, lo)]
    selft = sorted(self_times(ops0).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(busy(devs[min(devs)], lo, hi), lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    if any(v["busy_ns"] <= 0 for v in per_dev.values()):
        return None
    return {
        "window_ns": hi - lo,
        "devices": per_dev,
        "device_ops": [[n, t / 1e9] for n, t in selft],
        "idle_gaps": [[label_gap(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }
