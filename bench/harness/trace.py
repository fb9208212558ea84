"""Reduction of a profiler trace to device busy time, per-program and
per-kernel device time, and idle gaps attributed to the benchmark's spans.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are ``/device:TPU:<i>``.  Their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's text
(``%fusion.218 = s32[147456]{...} fusion(...), kind=kCustom, ...``); a
``while`` loop's event encloses the events of its body, so an op's own time
is its duration less that of the events nested in it.  A Pallas kernel is
an instruction with ``custom_call_target="tpu_custom_call"``.  The ``XLA
Modules`` line holds one event per executed program, named after its HLO
module (``jit_<function>(<fingerprint>)``).  The host plane holds the
``bench.*`` annotations of the driving paths on the Python thread;
``bench.window`` spans the measured window, and every share is taken inside
it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]        # ns, on the trace's clock
    n_devices: int
    busy_ns: float                     # union of op intervals, mean per chip
    busy: list                         # merged [start, end] of chip 0
    op_ns: dict                        # short op name -> own device ns
    op_count: dict
    kernel_ns: dict                    # Pallas kernel op -> device ns
    module_ns: dict                    # HLO module name -> total device ns
    module_count: dict
    module_events: list                # (name, start, end) on chip 0
    spans: list                        # (name, start, end) bench host spans
    # Filled by ``layers.attach``: {module: {scope path: own device ns}},
    # and the program's host spans [(name, start, end, stats)].
    scope_ns: dict | None = None
    program_spans: list | None = None

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def modules_matching(self, needle: str) -> tuple[float, int]:
        ns = sum(v for k, v in self.module_ns.items() if needle in k)
        n = sum(v for k, v in self.module_count.items() if needle in k)
        return ns, n

    def kernels_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.kernel_ns.items() if rx.search(k))


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def merge_intervals(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def short_name(text: str) -> str:
    """``%fusion.218 = s32[147456]{0:T(1024)} fusion(...)`` ->
    ``fusion.218 s32[147456]``."""
    name, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()


def own_times(events, lo, hi):
    """(short name, own ns inside [lo, hi], full text) of every op event,
    nested events subtracted from their enclosing one."""
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events), key=lambda x: (x[0], -x[1]))
    own, stack = [], []
    for s, e, text in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1][2]][1] -= max(0.0, min(e, hi) - max(s, lo))
        own.append([short_name(text), max(0.0, min(e, hi) - max(s, lo)),
                    text])
        stack.append((s, e, len(own) - 1))
    return own


def load(path: str) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    wins = [s for s in spans if s[0] == WINDOW_SPAN]
    if not wins or not devices:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN} span or no "
                         "TPU device plane")
    lo, hi = wins[0][1], wins[0][2]
    op_ns, op_count, module_ns, module_count, kernel_ns = {}, {}, {}, {}, {}
    busy_total, busy0, modules0 = 0.0, None, []
    for k, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                events = [ev for ev in line.events
                          if ev.start_ns + ev.duration_ns > lo
                          and ev.start_ns < hi]
                intervals += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                              for ev in events]
                for name, ns, text in own_times(events, lo, hi):
                    op_ns[name] = op_ns.get(name, 0.0) + ns
                    op_count[name] = op_count.get(name, 0) + 1
                    if 'custom_call_target="tpu_custom_call"' in text:
                        kernel_ns[name] = kernel_ns.get(name, 0.0) + ns
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e <= lo or s >= hi:
                        continue
                    module_ns[ev.name] = (module_ns.get(ev.name, 0.0)
                                          + min(e, hi) - max(s, lo))
                    module_count[ev.name] = module_count.get(ev.name, 0) + 1
                    if k == 0:
                        modules0.append((ev.name, s, e))
        merged = clip(merge_intervals(intervals), lo, hi)
        busy_total += sum(e - s for s, e in merged)
        if k == 0:
            busy0 = merged
    n = len(devices)
    return Summary(window=(lo, hi), n_devices=n, busy_ns=busy_total / n,
                   busy=busy0, op_ns={k: v / n for k, v in op_ns.items()},
                   op_count=op_count,
                   kernel_ns={k: v / n for k, v in kernel_ns.items()},
                   module_ns={k: v / n for k, v in module_ns.items()},
                   module_count=module_count, module_events=modules0,
                   spans=[s for s in spans if s[0] != WINDOW_SPAN
                          and s[2] > lo and s[1] < hi])


def idle_gaps(summary: Summary):
    """Gaps between device busy intervals inside the window, each named by
    the ``bench.*`` span that overlaps it most (``host.other`` when none
    does): [(name, ns)], longest first."""
    lo, hi = summary.window
    edges = [lo] + [x for iv in summary.busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in gaps:
        best, name = 0.0, "host.other"
        for sp, a, b in summary.spans:
            ov = min(e, b) - max(s, a)
            if ov > best:
                best, name = ov, sp
        out.append((name, e - s))
    return sorted(out, key=lambda x: -x[1])


def device_time_inside(summary: Summary, span_name: str, needle: str):
    """Per ``span_name`` host span: (span ns, device ns of modules matching
    ``needle`` that overlap it)."""
    out = []
    for sp, a, b in summary.spans:
        if sp != span_name:
            continue
        dev = sum(max(0.0, min(e, b) - max(s, a))
                  for name, s, e in summary.module_events if needle in name)
        out.append((b - a, dev))
    return out


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.op_ns.items(), key=lambda x: -x[1])[:top]
    gaps = idle_gaps(summary)[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}
