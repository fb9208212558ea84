"""Per-layer arithmetic over the program's own named scopes and host spans.

The programs name their layers: ``snn/stream.run_stream`` runs its stages
under ``jax.named_scope``s (``chip``, ``plasticity``, ``egress``,
``fabric`` with ``leaf``, ``level<i>`` and ``merge`` inside,
``ingress``) and the
engine's window program adds ``slots``; the compiled program keeps the
name stack as each op's ``tf_op`` stat.  The engine's host loop records
``engine.*`` ``TraceAnnotation`` spans with their counters as arguments.

* ``scope_times`` assigns each device op's own time (nested ops subtracted,
  as ``trace.own_times`` does) to the top-level scope of its ``tf_op``,
  per HLO module, so the extraction programs the engine runs between
  windows stay apart from the window program.  An op the compiler made
  with no name of its own (the trace then gives it the name of the loop
  around it) computes for the ops that read its result, so it takes the
  name stack of the nearest of them that has a scope, in the module's HLO
  that the trace keeps (``read_by``);
* ``program_spans`` lists the ``engine.*`` host spans with their stats;
* the readers below turn them into per-layer metrics of a run's context,
  from ``scope_ns``, ``level_ns`` and ``program_spans`` on the trace
  summary; where the summary has none of them a reader returns None.
"""

from __future__ import annotations

import bisect
import re
from typing import NamedTuple

from bench.harness import trace as tracelib
from bench.harness import xspace

SCOPES = ("chip", "plasticity", "egress", "fabric", "ingress", "slots")
UNSCOPED = "unscoped"
FABRIC_PART = re.compile(r"^(leaf|level\d+|merge)$")
FABRIC_OTHER = "other"
SPAN_PREFIX = "engine."
STEP_SPAN = "engine.step"
NO_SPAN = "host.other"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


class _Op(NamedTuple):
    name: str              # the op's tf_op path
    start_ns: float
    duration_ns: float


def components(tf_op: str) -> list[str]:
    """The name stack's components with transform wrappers removed:
    ``jit(f)/vmap(vmap(fabric))/level0/sort`` -> ``[f, fabric, level0,
    sort]``."""
    out = []
    for c in tf_op.split("/"):
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        out.append(c)
    return out


def scope_of(tf_op: str) -> tuple[str, str | None]:
    """(top-level scope, part of ``fabric``) of an op: the first component
    that is one of ``SCOPES``, else ``unscoped``; inside ``fabric`` the
    first ``leaf``, ``level<i>`` or ``merge`` below it, else ``other``."""
    parts = components(tf_op)
    for k, c in enumerate(parts):
        if c in SCOPES:
            if c != "fabric":
                return c, None
            sub = next((p for p in parts[k + 1:] if FABRIC_PART.match(p)),
                       FABRIC_OTHER)
            return c, sub
    return UNSCOPED, None


def read_by(instructions: dict, users: dict, name: str,
            depth: int = 8) -> str:
    """The name stack of the nearest instruction with a scope that reads
    the result of instruction ``name``, breadth first over its readers
    (``users``: instruction name -> the names of its readers), ``depth``
    steps at most; "" where there is none."""
    seen, frontier = {name}, [name]
    for _ in range(depth):
        nxt = []
        for n in frontier:
            for m in users.get(n, ()):
                if m in seen:
                    continue
                seen.add(m)
                op_name = instructions[m].op_name
                if scope_of(op_name)[0] != UNSCOPED:
                    return op_name
                nxt.append(m)
        frontier = nxt
    return ""


def _namer(planes):
    """``name(module, op event)``: the op's name stack, from its ``tf_op``
    or, where the compiler gave the op none, from ``read_by``."""
    protos = {k: v.get("Hlo Proto") for p in planes
              if p.name == "/host:metadata" for k, v in p.metadata.items()}
    modules, memo = {}, {}

    def name(module, event):
        tf_op = str(event.stats.get("tf_op", ""))
        if scope_of(tf_op)[0] != UNSCOPED or not protos.get(module):
            return tf_op
        if module not in modules:
            instructions = xspace.hlo_instructions(protos[module])
            users = {}
            for n, ins in instructions.items():
                for o in ins.operands:
                    users.setdefault(o, []).append(n)
            modules[module] = instructions, users
        instructions, users = modules[module]
        instr = event.name.partition(" = ")[0].lstrip("%")
        if instr not in instructions or instructions[instr].op_name:
            return tf_op
        key = (module, instr)
        if key not in memo:
            memo[key] = read_by(instructions, users, instr) or tf_op
        return memo[key]

    return name


def _device_planes(planes):
    return sorted((p for p in planes if tracelib.DEVICE_PLANE.match(p.name)),
                  key=lambda p: p.name)


def _line(plane, name):
    return next((ln for ln in plane.lines if ln.name == name), None)


def scope_times(planes, window) -> tuple[dict, dict]:
    """Own device ns inside ``window`` of every op that runs inside an
    ``XLA Modules`` event, mean per device: ``({module: {scope: ns}},
    {module: {fabric part: ns}})``."""
    lo, hi = window
    devices = _device_planes(planes)
    name = _namer(planes)
    scope_ns, level_ns = {}, {}
    for plane in devices:
        mods, ops = _line(plane, tracelib.MODULES_LINE), _line(
            plane, tracelib.OPS_LINE)
        if mods is None or ops is None:
            continue
        spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in mods.events)
        starts = [s for s, _, _ in spans]
        by_module = {}
        for e in ops.events:
            if e.start_ns + e.duration_ns <= lo or e.start_ns >= hi:
                continue
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k < 0 or e.start_ns >= spans[k][1]:
                continue
            module = spans[k][2]
            by_module.setdefault(module, []).append(
                _Op(name(module, e), e.start_ns, e.duration_ns))
        for module, evs in by_module.items():
            sc, lv = scope_ns.setdefault(module, {}), None
            for _, ns, tf_op in tracelib.own_times(evs, lo, hi):
                scope, part = scope_of(tf_op)
                sc[scope] = sc.get(scope, 0.0) + ns / len(devices)
                if part is not None:
                    lv = level_ns.setdefault(module, {})
                    lv[part] = lv.get(part, 0.0) + ns / len(devices)
    return scope_ns, level_ns


def program_spans(planes, window) -> list:
    """``engine.*`` host spans that overlap ``window``: ``[(name, start,
    end, stats)]`` in start order."""
    lo, hi = window
    out = []
    for plane in planes:
        if tracelib.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name.startswith(SPAN_PREFIX) and end > lo and \
                        e.start_ns < hi:
                    out.append((e.name, e.start_ns, end, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def self_ns(spans) -> dict:
    """Self time of the spans per name: each span's length less that of
    the spans nested directly in it (the spans of one thread nest)."""
    out, stack = {}, []
    for name, s, e, _ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - (e - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, s, e))
    return out


def innermost(spans) -> list:
    """The spans as consecutive pieces ``[(start, end, name)]``, each piece
    named by the innermost span that covers it."""
    pieces, stack, t = [], [], None
    for name, s, e, _ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            if top[1] > t:
                pieces.append((t, top[1], top[0]))
                t = top[1]
        if stack and s > t:
            pieces.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    while stack:
        top = stack.pop()
        if top[1] > t:
            pieces.append((t, top[1], top[0]))
            t = top[1]
    return pieces


def idle_by_span(summary) -> dict:
    """Device idle time of chip 0 inside the window per innermost
    ``engine.*`` span covering it (``host.other`` where none does), ns."""
    lo, hi = summary.window
    edges = [lo] + [x for iv in summary.busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    pieces = innermost(getattr(summary, "program_spans", None) or [])
    out, k = {}, 0
    for s, e in gaps:
        t = s
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < e:
            a, b, name = pieces[j]
            if a > t:
                out[NO_SPAN] = out.get(NO_SPAN, 0.0) + min(a, e) - t
            ov = min(b, e) - max(a, t)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            t = max(t, min(b, e))
            j += 1
        if e > t:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + e - t
    return out


# -- readers -----------------------------------------------------------------


def _program(ctx, field: str):
    s = ctx.get("summary")
    per_module = getattr(s, field, None) if s is not None else None
    if not per_module:
        return None
    out = {}
    for module, parts in per_module.items():
        if ctx["program"] in module:
            for k, v in parts.items():
                out[k] = out.get(k, 0.0) + v
    return out or None


def scope_ms_per_step(ctx: dict, scope: str):
    """Own device time of the program's ops in ``scope`` per emulated
    step, ms.  For ``fabric`` it notes the split by level."""
    ns = _program(ctx, "scope_ns")
    steps = ctx["stats"].get("emulated_steps", 0)
    if ns is None or scope not in ns or not steps:
        return None
    if scope == "fabric":
        parts = _program(ctx, "level_ns") or {}
        total = sum(parts.values())
        if total > 0:
            ctx.setdefault("notes", []).append(
                "fabric split, ms per step: " + ", ".join(
                    f"{k} {v * 1e-6 / steps:.4f} ({100 * v / total:.1f}%)"
                    for k, v in sorted(parts.items())))
    return ns[scope] * 1e-6 / steps


def unscoped_share(ctx: dict):
    """The program's own device time outside every scope, % of its own
    device time."""
    ns = _program(ctx, "scope_ns")
    total = sum(ns.values()) if ns else 0.0
    return 100.0 * ns.get(UNSCOPED, 0.0) / total if total else None


def _spans(ctx):
    s = ctx.get("summary")
    spans = getattr(s, "program_spans", None) if s is not None else None
    if not spans:
        return None, 0
    return spans, sum(1 for sp in spans if sp[0] == STEP_SPAN)


def span_ms_per_step(ctx: dict, names) -> float | None:
    """Self time of the ``engine.*`` spans named ``names`` per
    ``engine.step`` span, ms."""
    spans, n = _spans(ctx)
    if not n:
        return None
    own = self_ns(spans)
    return sum(own.get(k, 0.0) for k in names) * 1e-6 / n


def readback_mb(ctx: dict):
    """Bytes copied to the host (``engine.readback`` ``bytes``) per
    ``engine.step`` span, MB."""
    spans, n = _spans(ctx)
    if not n:
        return None
    total = sum(sp[3].get("bytes", 0) for sp in spans
                if sp[0] == "engine.readback")
    return total * 1e-6 / n


def slot_occupancy(ctx: dict):
    """Slot-steps that advanced a session, % of the slot-steps run
    (``live`` over ``slot_steps`` of the ``engine.step`` spans)."""
    spans, n = _spans(ctx)
    if not n:
        return None
    live = sum(sp[3].get("live", 0) for sp in spans if sp[0] == STEP_SPAN)
    run = sum(sp[3].get("slot_steps", 0) for sp in spans
              if sp[0] == STEP_SPAN)
    return 100.0 * live / run if run else None


def note_idle_by_span(ctx: dict) -> None:
    """Notes the device's idle time split by the innermost ``engine.*``
    span over it, as shares of the idle time."""
    s = ctx.get("summary")
    if s is None or not getattr(s, "program_spans", None):
        return
    idle = idle_by_span(s)
    total = sum(idle.values())
    if total > 0:
        ctx.setdefault("notes", []).append(
            "idle by engine span: " + ", ".join(
                f"{k} {100 * v / total:.1f}%" for k, v in
                sorted(idle.items(), key=lambda x: -x[1])))
