"""Reduce a profiler trace to the program's own layers: device time per
named scope, and device idle time per host span.

The program names its work (``repro.core.obs``): device scopes
(``jax.named_scope``) end up in the ``op_name`` metadata of the optimised
HLO, host spans (``jax.profiler.TraceAnnotation``) are events on the
trainer's thread. The device trace names instructions but not their scope,
and one instruction name (``fusion.12``) recurs in several programs, so an
op is keyed by ``(module, instruction)``: the module from the event's
``hlo_module`` stat, else from the enclosing event of the device's
``XLA Modules`` line. ``scope_map`` reads each instruction's scope from the
HLO text of the executables (``compiled.as_text()``).

The names are kept on the benchmark's side, not imported from the
program, so that the yardstick stays put when the program changes: the
readers under ``metrics/`` declare the scopes and spans they read, and the
harness reduces the trace with each reader's own scopes, so that a name
declared later changes no existing reading. ``SCOPES`` and ``SPANS`` are
the names of the round engine's layers, read by ``layer_metrics``.
Everything below, but ``load``, works on plain tuples so that it can be
checked on a trace made up by hand.
"""
from __future__ import annotations

import bisect
import itertools
import re
import statistics
from typing import AbstractSet, Dict, Iterable, List, NamedTuple, \
    Optional, Sequence, Tuple

from chipbench import trace

SCOPES = ("client.step", "uplink.encode", "uplink.reduce", "server.step")
SPANS = ("round.dispatch", "feed.wait", "slab.place", "slab.call",
         "finalize.call", "bucket.call", "round.absorb", "loss.sync")
OUTSIDE = "outside the program"     # idle time under no program span
UNSCOPED = "unscoped"               # device time under no scope

Interval = Tuple[float, float]


class Op(NamedTuple):
    """One executed instruction."""
    module: str
    op: str
    start: float
    end: float


class Loaded(NamedTuple):
    ops: Dict[str, List[Op]]               # per device (or CPU line)
    modules: Dict[str, List[trace.Event]]  # module executions per device
    host: Dict[str, List[trace.Event]]     # host events per thread line
    tf_ops: Dict[Tuple[str, str], str]     # op_name path carried by events


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def module_name(event_name: str) -> str:
    """``jit_slab(123)`` -> ``jit_slab``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def load(path: str) -> Loaded:
    """Every op event of one ``.xplane.pb`` with its module, the module
    executions of each device, and the host lines. A device plane's ops
    come from its ``XLA Ops`` lines and its modules from its ``XLA
    Modules`` lines, every line of the name (the profiler can split one
    over several); elsewhere (the CPU) an op is any event with ``hlo_op``
    and ``hlo_module`` stats."""
    from jax.profiler import ProfileData
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[trace.Event]] = {}
    host: Dict[str, List[trace.Event]] = {}
    tf_ops: Dict[Tuple[str, str], str] = {}
    for plane in ProfileData.from_file(path).planes:
        device = trace.DEVICE_PLANE.match(plane.name) is not None
        lines = list(plane.lines)
        mods = sorted(((module_name(e.name), float(e.start_ns),
                        float(e.end_ns))
                       for line in lines if device
                       and line.name == "XLA Modules"
                       for e in line.events), key=lambda m: m[1])
        if device:
            modules[plane.name] = mods
        starts = [m[1] for m in mods]
        reach = list(itertools.accumulate((m[2] for m in mods), max))
        for i, line in enumerate(lines):
            key = (f"{plane.name}/{line.name}" if device
                   else f"{plane.name}/{line.name}#{i}")
            events = []
            for e in line.events:
                st = dict(e.stats)
                s, t = float(e.start_ns), float(e.end_ns)
                if device:
                    if line.name != trace.OPS_LINE:
                        continue
                    op = str(st.get("hlo_op") or trace.op_name(e.name))
                    mod = st.get("hlo_module")
                    mod = (module_name(str(mod)) if mod
                           else _enclosing(mods, starts, reach, s))
                elif "hlo_op" in st and "hlo_module" in st:
                    op = str(st["hlo_op"])
                    mod = module_name(str(st["hlo_module"]))
                else:
                    events.append((e.name, s, t))
                    continue
                ops.setdefault(key, []).append(Op(mod, op, s, t))
                if st.get("tf_op"):
                    tf_ops[(mod, op)] = str(st["tf_op"])
            if events:
                host[key] = events
    return Loaded(ops, modules, host, tf_ops)


def _enclosing(mods: Sequence[trace.Event], starts: Sequence[float],
               reach: Sequence[float], t: float) -> str:
    """The module execution that holds time ``t``, the latest to start
    where several overlap (``mods`` sorted by their ``starts``; ``reach``
    the latest end of each prefix of them)."""
    k = bisect.bisect_right(starts, t) - 1
    while k >= 0 and reach[k] > t:
        if t < mods[k][2]:
            return mods[k][0]
        k -= 1
    return ""


def window_line(host: Dict[str, List[trace.Event]], name: str
                ) -> Optional[Tuple[Interval, List[trace.Event]]]:
    """The first host event called ``name`` and all events of its line."""
    for events in host.values():
        for n, s, e in events:
            if n == name:
                return (s, e), events
    return None


# ---------------------------------------------------------------------------
# HLO text -> scope of each instruction
# ---------------------------------------------------------------------------

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\b(?:body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TOKEN = re.compile(r"[A-Za-z_][\w.\-]*")
_PALLAS = 'custom_call_target="tpu_custom_call"'


def scope_of(path: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost scope named in an ``op_name`` path (``jit(slab)/
    client.step/while/body/...``); None where it names none."""
    found = None
    for tok in _TOKEN.findall(path or ""):
        if tok in scopes:
            found = tok
    return found


class _Instr(NamedTuple):
    name: str
    comp: str           # the computation that holds it
    path: str           # its op_name
    fused: str          # the computation a fusion calls, or ""
    called: List[str]   # loop and branch bodies it runs
    refs: List[str]     # operands (and called computations)
    pallas: bool        # a Pallas kernel's call (``tpu_custom_call``)


def _parse(text: str):
    """(module name, [_Instr], {computation: its root instruction})."""
    module, comp = "", None
    instrs: List[_Instr] = []
    roots: Dict[str, str] = {}
    for line in text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            m = _COMP.match(line)
            if m:
                comp = m.group(1)
            continue
        if comp is None:
            continue
        root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
        p = _OP_NAME.search(rhs)
        f = _FUSED.search(rhs)
        called = _CALLED.findall(rhs)
        for b in _BRANCHES.findall(rhs):
            called += [x.strip().lstrip("%") for x in b.split(",")
                       if x.strip()]
        instrs.append(_Instr(name, comp, p.group(1) if p else "",
                             f.group(1) if f else "", called,
                             _REF.findall(rhs.split(", metadata=", 1)[0]),
                             _PALLAS in rhs))
        if root:
            roots[comp] = name
    return module, instrs, roots


def scope_map(texts: Iterable[str], scopes: Sequence[str] = SCOPES
              ) -> Dict[Tuple[str, str], str]:
    """``{(module, instruction): scope}`` for every instruction of the
    given optimised HLO modules that can be placed in a scope.

    An instruction takes the innermost scope of its own ``op_name``; a
    fusion takes that of its fused computation's root, else its own.
    Instructions the compiler made without metadata (copies, the wrapped
    ops of a loop) take the scope of a user, else of an operand, else of
    the loop or branch that runs their computation."""
    out: Dict[Tuple[str, str], str] = {}
    for text in texts:
        module, instrs, roots = _parse(text)
        by_name = {i.name: i for i in instrs}
        caller = {c: i.name for i in instrs for c in i.called + [i.fused]
                  if c}
        users: Dict[str, List[str]] = {}
        for i in instrs:
            for r in i.refs:
                users.setdefault(r, []).append(i.name)
        scope: Dict[str, str] = {}
        for i in instrs:
            root = by_name.get(roots.get(i.fused, ""))
            s = (scope_of(root.path, scopes) if root else None) or \
                scope_of(i.path, scopes)
            if s:
                scope[i.name] = s
        while True:     # in rounds, so that the order of the text is moot
            new = {}
            for i in instrs:
                if i.name in scope:
                    continue
                s = next((scope[u] for u in users.get(i.name, ())
                          if u in scope), None) or \
                    next((scope[r] for r in i.refs if r in scope), None) or \
                    scope.get(caller.get(i.comp, ""))
                if s:
                    new[i.name] = s
            if not new:
                break
            scope.update(new)
        out.update(((module, n), s) for n, s in scope.items())
    return out


def pallas_calls(texts: Iterable[str]) -> AbstractSet[Tuple[str, str]]:
    """``{(module, instruction)}`` of the Pallas kernels' calls
    (``tpu_custom_call``) in the given optimised HLO modules, whatever
    the compiler named the instruction."""
    out = set()
    for text in texts:
        module, instrs, _ = _parse(text)
        out.update((module, i.name) for i in instrs if i.pallas)
    return frozenset(out)


def resolve(op: Op, smap: Dict[Tuple[str, str], str],
            tf_ops: Dict[Tuple[str, str], str],
            scopes: Sequence[str] = SCOPES) -> str:
    """The scope of one executed op: the path the trace carries on the
    event where it has one, else the HLO's; ``UNSCOPED`` where neither
    places it."""
    key = (op.module, op.op)
    s = scope_of(tf_ops.get(key, ""), scopes)
    return s or smap.get(key) or UNSCOPED


# ---------------------------------------------------------------------------
# device time per scope, idle time per span
# ---------------------------------------------------------------------------

def scope_times(ops: Sequence[Op], smap, tf_ops, lo: float, hi: float,
                scopes: Sequence[str] = SCOPES) -> Dict[str, float]:
    """ns of device time per scope inside [lo, hi]: each op's self time
    (``trace.self_times``: a loop's body ops, not the loop, hold their
    time) goes to the op's scope."""
    ev = [(f"{o.module}/{o.op}", max(o.start, lo), min(o.end, hi))
          for o in ops if min(o.end, hi) > max(o.start, lo)]
    out: Dict[str, float] = {}
    for key, ns in trace.self_times(ev).items():
        module, op = key.split("/", 1)
        s = resolve(Op(module, op, 0.0, 0.0), smap, tf_ops, scopes)
        out[s] = out.get(s, 0.0) + ns
    return out


def idle_by_span(idle: Sequence[Interval], host: Sequence[trace.Event],
                 spans: Sequence[str] = SPANS) -> Dict[str, float]:
    """ns of idle device time per innermost program span over it: of the
    spans covering a moment, the one that started last, else ended first,
    else comes later on the line (a span and the one it wraps can share
    both ends). Idle time under no program span goes to ``OUTSIDE``; the
    parts sum to the idle time."""
    prog = [(s, -e, k, n) for k, (n, s, e) in enumerate(host) if n in spans]
    out: Dict[str, float] = {}
    for g0, g1 in idle:
        over = [p for p in prog if p[0] < g1 and -p[1] > g0]
        cuts = sorted({g0, g1} | {t for s, me, _, _ in over
                                  for t in (s, -me) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inner = max((p for p in over if p[0] <= mid < -p[1]),
                        default=None)
            name = inner[3] if inner else OUTSIDE
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def span_intervals(host: Sequence[trace.Event], name: str,
                   lo: float, hi: float) -> List[Interval]:
    return trace.clip(trace.union((s, e) for n, s, e in host if n == name),
                      lo, hi)


def launch_leads(modules: Sequence[trace.Event], host: Sequence[trace.Event],
                 pairs: Dict[str, str], lo: float, hi: float
                 ) -> Dict[str, dict]:
    """Per module, the ns from the start of each host call span to the
    start of the execution it launched (``pairs``: module -> span name;
    the k-th span in [lo, hi] with the k-th execution). On one clock with
    the device none is negative; the lists are as long as the fewer of
    spans and executions, and ``counts`` gives both."""
    out = {}
    for mod, span in pairs.items():
        starts = sorted(s for n, s, e in host if n == span and lo <= s < hi)
        runs = sorted(s for n, s, e in modules if n == mod and lo <= s < hi)
        if starts or runs:
            out[mod] = {"counts": [len(starts), len(runs)],
                        "leads_ns": [r - s for s, r in zip(starts, runs)]}
    return out


def pallas_times(ops: Sequence[Op], smap, tf_ops, calls, lo: float,
                 hi: float, scopes: Sequence[str]) -> Dict[str, list]:
    """Per scope, [ns inside [lo, hi], count] of the events of the Pallas
    kernels' calls (``calls``, from ``pallas_calls``) that lie in it."""
    out = {s: [0.0, 0] for s in scopes}
    for o in ops:
        if (o.module, o.op) not in calls:
            continue
        s = resolve(o, smap, tf_ops, scopes)
        c = trace.clip([(o.start, o.end)], lo, hi)
        if s in out and c:
            out[s][0] += trace.length(c)
            out[s][1] += 1
    return out


def reduce(loaded: Loaded, smap: Dict[Tuple[str, str], str],
           window_span: str = "bench.window",
           scopes: Sequence[str] = SCOPES,
           spans: Sequence[str] = SPANS,
           calls: AbstractSet[Tuple[str, str]] = frozenset()
           ) -> Optional[dict]:
    """Per device plane, inside the window: busy ns; device ns per scope
    (``smap`` made with the same ``scopes``; each declared scope is there,
    0 where no op falls in it) and, of it, [ns, count] of the Pallas
    kernels' calls (``pallas_ns``; ``calls`` from ``pallas_calls``); idle
    ns under each span (``span_idle_ns``: all of the idle time the span
    covers, whatever lies inside it) and idle ns per innermost span
    (``idle_by_span_ns``). None where the trace holds no device op or no
    window."""
    found = window_line(loaded.host, window_span)
    devs = {k: v for k, v in loaded.ops.items() if k.startswith("/device")}
    if found is None or not devs:
        return None
    (lo, hi), host = found
    out = {"window_ns": hi - lo, "devices": {}}
    under = {s: span_intervals(host, s, lo, hi) for s in spans}
    for key, ops in sorted(devs.items()):
        busy = trace.busy([(o.op, o.start, o.end) for o in ops], lo, hi)
        idle = trace.idle_gaps(busy, lo, hi)
        plane = key.split("/XLA Ops", 1)[0]
        scoped = dict.fromkeys(scopes, 0.0)
        if scopes:
            scoped.update(scope_times(ops, smap, loaded.tf_ops, lo, hi,
                                      scopes))
        out["devices"][plane] = {
            "busy_ns": trace.length(busy),
            "scopes_ns": scoped,
            "pallas_ns": pallas_times(ops, smap, loaded.tf_ops, calls, lo,
                                      hi, scopes),
            "idle_by_span_ns": idle_by_span(idle, host, spans),
            "span_idle_ns": {s: trace.length(trace.intersect(idle, iv))
                             for s, iv in under.items()},
            "launch_leads_ns": launch_leads(
                loaded.modules.get(plane, []), host,
                {"jit_slab": "slab.call", "jit_slabfin": "finalize.call",
                 "jit_bucket": "bucket.call"}, lo, hi),
        }
    return out


# ---------------------------------------------------------------------------
# the round engine's layers, per round
# ---------------------------------------------------------------------------

def layer_metrics(devs: Optional[Sequence[dict]], rounds_traced: int
                  ) -> dict:
    """The device's layer numbers, ms per traced round: device time under
    each of ``SCOPES`` (``<scope>_ms``) and device idle time under the
    ``round.dispatch`` span (``dispatch.idle_ms``), each the mean over
    ``devs`` (the planes of one ``reduce``; None without a trace). A
    number whose scope or span the reduction lacks is left out."""
    out = {}
    if devs:
        per = 1e6 * rounds_traced
        for s in SCOPES:
            if all(s in d["scopes_ns"] for d in devs):
                out[f"{s}_ms"] = statistics.fmean(
                    d["scopes_ns"][s] for d in devs) / per
        if all("round.dispatch" in d["span_idle_ns"] for d in devs):
            out["dispatch.idle_ms"] = statistics.fmean(
                d["span_idle_ns"]["round.dispatch"] for d in devs) / per
    return out


def layer_reading(ctx: dict, name: str) -> Optional[float]:
    """One number of ``layer_metrics`` from a metric reader's context (its
    reduction, ``ctx["layers"]``)."""
    red = ctx.get("layers")
    devs = None if red is None else list(red["devices"].values())
    return layer_metrics(devs, ctx["counters"].get("traced_rounds", 0)
                         ).get(name)


def counter_ms(counters: dict, name: str) -> Optional[float]:
    """The trainer's counter ``name`` over the window (its delta in
    ``counters["program"]``, in seconds) in ms per window round; None
    where the program has no such counter."""
    v = (counters.get("program") or {}).get(name)
    return None if v is None else 1e3 * v / counters["rounds"]
