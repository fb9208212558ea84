"""Per-layer arithmetic over the program's own named scopes and host spans,
read by name: nothing here lists the program's layers.

The programs name their layers with ``jax.named_scope`` (the stream's
stages, the fabric's parts inside its stage, the engine's slot handling);
the compiled program keeps the name stack as each op's ``tf_op`` stat.
The host loops record ``TraceAnnotation`` spans named ``<layer>.<what>``
with their counters as arguments.

* ``scope_path`` reduces a name stack to the named scopes in it, outermost
  first;
* ``scope_times`` keeps each device op's own time (nested ops subtracted,
  as ``trace.own_times`` does) under its scope path, per HLO module, so
  the extraction programs the engine runs between windows stay apart from
  the window program.  An op the compiler made with no name of its own
  (the trace then gives it the name of the loop around it) computes for
  the ops that read its result, so it takes the name stack of the nearest
  of them that has a scope, in the module's HLO that the trace keeps
  (``read_by``);
* ``program_spans`` lists the program's host spans with their stats;
* ``attach`` fills both into a trace summary, and the readers below turn
  them into per-layer metrics of a run's context.  A scope is read by its
  path (``fabric``, ``fabric/leaf``, ``level0``), a span by its name, a
  counter by its span and stat; where the summary has nothing to read a
  reader returns None.
"""

from __future__ import annotations

import bisect
import re
import time
from typing import NamedTuple

from bench.harness import trace as tracelib
from bench.harness import xspace

UNSCOPED = "unscoped"
OTHER = "other"
NO_SPAN = "host.other"
STEP_SPAN = "engine.step"
# Name-stack components that JAX makes for control flow, not for a scope.
STRUCTURAL = frozenset({"", "while", "body", "cond", "closed_call",
                        "checkpoint", "remat", "shard_map"})
_BRANCH = re.compile(r"^branch_\d+_fun$")
# A scope's name; an ``einsum``'s spec (``br,brn->bn``) is none.
_SCOPE_NAME = re.compile(r"^[\w.\-]+$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# A program span: dotted lower-case words, such as ``engine.readback``.
# The benchmark's own ``bench.*`` spans, the runtime's events and the
# compiler's op names (``copy.46``) do not match or are left out.
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
BENCH_PREFIX = "bench."


class _Op(NamedTuple):
    name: str              # the op's tf_op path
    start_ns: float
    duration_ns: float


def scope_path(tf_op: str) -> tuple[str, ...]:
    """The named scopes of an op's name stack, outermost first: the
    components between the program's function and the primitive, with
    transform wrappers removed (``vmap(fabric)`` is ``fabric``) and JAX's
    control-flow components and ``einsum`` specs left out.  A nested ``jit(...)`` function and
    what lies inside it belong to the scope that calls it:
    ``jit(f)/while/body/vmap(fabric)/merge/jit(pack)/pallas_call`` ->
    ``("fabric", "merge")``."""
    out = []
    for c in tf_op.split(";", 1)[0].split("/")[1:-1]:
        m = _WRAPPED.match(c)
        while m and m.group(1) not in ("jit", "pjit"):
            c = m.group(2)
            m = _WRAPPED.match(c)
        if m:
            break
        if c not in STRUCTURAL and not _BRANCH.match(c) and \
                _SCOPE_NAME.match(c):
            out.append(c)
    return tuple(out)


def read_by(instructions: dict, users: dict, name: str,
            depth: int = 8) -> str:
    """The name stack of the nearest instruction with a scope (a
    non-empty ``scope_path``) that reads the result of instruction
    ``name``, breadth first over its readers
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
                if scope_path(op_name):
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
        if scope_path(tf_op) or not protos.get(module):
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


def scope_times(planes, window) -> dict:
    """Own device ns inside ``window`` of every op that runs inside an
    ``XLA Modules`` event, mean per device, by module and scope path:
    ``{module: {"fabric/leaf": ns, ..., "": ns outside every scope}}``."""
    lo, hi = window
    devices = _device_planes(planes)
    name = _namer(planes)
    out, memo = {}, {}
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
            paths = out.setdefault(module, {})
            for _, ns, tf_op in tracelib.own_times(evs, lo, hi):
                if tf_op not in memo:
                    memo[tf_op] = "/".join(scope_path(tf_op))
                key = memo[tf_op]
                paths[key] = paths.get(key, 0.0) + ns / len(devices)
    return out


def program_spans(planes, window) -> list:
    """The program's host spans (``SPAN_NAME``, outside ``bench.*``) that
    overlap ``window``: ``[(name, start, end, stats)]`` in start order."""
    lo, hi = window
    out = []
    for plane in planes:
        if tracelib.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if end > lo and e.start_ns < hi and SPAN_NAME.match(
                        e.name) and not e.name.startswith(BENCH_PREFIX):
                    out.append((e.name, e.start_ns, end, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def attach(summary, xplane_path: str) -> float:
    """Fill ``summary.scope_ns`` and ``summary.program_spans`` from the
    trace at ``xplane_path``; returns the seconds it took."""
    t0 = time.perf_counter()
    planes = xspace.read(xplane_path)
    summary.scope_ns = scope_times(planes, summary.window)
    summary.program_spans = program_spans(planes, summary.window)
    return time.perf_counter() - t0


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
    program span covering it (``host.other`` where none does), ns."""
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


def _path(key: str) -> tuple:
    return tuple(key.split("/")) if key else ()


def _runs(path: tuple, scope: tuple) -> list:
    """Where ``scope`` occurs in ``path`` as a run of whole components."""
    n = len(scope)
    return [k for k in range(len(path) - n + 1) if path[k:k + n] == scope]


def time_under(paths: dict, scope: str) -> float | None:
    """Own ns of the ops under ``scope`` in a ``{scope path: ns}`` map:
    ``scope`` is a path (``a/b``) that may sit at any depth, or
    ``unscoped`` for the ops outside every scope.  None where no op is
    under it."""
    if scope == UNSCOPED:
        hits = [ns for key, ns in paths.items() if not key]
    else:
        want = _path(scope)
        hits = [ns for key, ns in paths.items() if _runs(_path(key), want)]
    return sum(hits) if hits else None


def split(paths: dict, scope: str = "") -> dict:
    """Own ns of the ops under ``scope`` by the scope right inside it
    (``other`` for the ops directly in it); with ``scope`` "" by the
    outermost scope (``unscoped`` for the ops outside every scope)."""
    want = _path(scope)
    out = {}
    for key, ns in paths.items():
        path = _path(key)
        if not want:
            k = path[0] if path else UNSCOPED
        else:
            runs = _runs(path, want)
            if not runs:
                continue
            inner = path[runs[0] + len(want):]
            k = inner[0] if inner else OTHER
        out[k] = out.get(k, 0.0) + ns
    return out


def program_paths(ctx) -> dict | None:
    """The ``{scope path: own ns}`` of the run's program (the modules that
    ``ctx["program"]`` names), or None where the summary has none."""
    s = ctx.get("summary")
    per_module = getattr(s, "scope_ns", None) if s is not None else None
    if not per_module:
        return None
    out = {}
    for module, paths in per_module.items():
        if ctx["program"] in module:
            for k, v in paths.items():
                out[k] = out.get(k, 0.0) + v
    return out or None


def scope_ms_per_step(ctx: dict, scope: str):
    """Own device time of the program's ops under ``scope`` per emulated
    step, ms.  Where named scopes lie inside it, it notes the split."""
    paths = program_paths(ctx)
    steps = ctx["stats"].get("emulated_steps", 0)
    ns = time_under(paths, scope) if paths else None
    if ns is None or not steps:
        return None
    parts = split(paths, scope) if scope != UNSCOPED else {}
    if set(parts) - {OTHER}:
        ctx.setdefault("notes", []).append(
            f"{scope} split, ms per step: " + ", ".join(
                f"{k} {v * 1e-6 / steps:.4f} ({100 * v / ns:.1f}%)"
                for k, v in sorted(parts.items())))
    return ns * 1e-6 / steps


def unscoped_share(ctx: dict):
    """The program's own device time outside every scope, % of its own
    device time."""
    paths = program_paths(ctx)
    total = sum(paths.values()) if paths else 0.0
    return 100.0 * paths.get("", 0.0) / total if total else None


def _spans(ctx, per: str):
    s = ctx.get("summary")
    spans = getattr(s, "program_spans", None) if s is not None else None
    if not spans:
        return None, 0
    return spans, sum(1 for sp in spans if sp[0] == per)


def span_ms_per_step(ctx: dict, names, per: str = STEP_SPAN):
    """Self time of the program spans named ``names`` per ``per`` span,
    ms."""
    spans, n = _spans(ctx, per)
    if not n:
        return None
    own = self_ns(spans)
    return sum(own.get(k, 0.0) for k in names) * 1e-6 / n


def stat_per_step(ctx: dict, name: str, stat: str, per: str = STEP_SPAN):
    """Sum of counter ``stat`` over the program spans named ``name`` per
    ``per`` span."""
    spans, n = _spans(ctx, per)
    if not n:
        return None
    return sum(sp[3].get(stat, 0) for sp in spans if sp[0] == name) / n


def readback_mb(ctx: dict):
    """Bytes copied to the host (``engine.readback`` ``bytes``) per
    ``engine.step`` span, MB."""
    b = stat_per_step(ctx, "engine.readback", "bytes")
    return None if b is None else b * 1e-6


def slot_occupancy(ctx: dict):
    """Slot-steps that advanced a session, % of the slot-steps run
    (``live`` over ``slot_steps`` of the ``engine.step`` spans)."""
    live = stat_per_step(ctx, STEP_SPAN, "live")
    run = stat_per_step(ctx, STEP_SPAN, "slot_steps")
    return 100.0 * live / run if run else None


def note_idle_by_span(ctx: dict) -> None:
    """Notes the device's idle time split by the innermost program span
    over it, as shares of the idle time."""
    s = ctx.get("summary")
    if s is None or not getattr(s, "program_spans", None):
        return
    idle = idle_by_span(s)
    total = sum(idle.values())
    if total > 0:
        ctx.setdefault("notes", []).append(
            "idle by program span: " + ", ".join(
                f"{k} {100 * v / total:.1f}%" for k, v in
                sorted(idle.items(), key=lambda x: -x[1])))
