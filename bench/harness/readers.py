"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the run's context: the configuration and traffic files, the
window's counts (``stats``), the set-up time, and in a traced run the trace
``summary`` with the chip's ``peak`` entry and the ``program`` needle that
names the program's HLO module.  A reader that finds nothing to read
returns None and the metric is left out of the result line.
"""

from __future__ import annotations

import numpy as np

from bench.harness import trace as tracelib
from bench.harness import work as worklib


def rate(ctx: dict, key: str):
    st = ctx["stats"]
    if key not in st or st["window_s"] <= 0:
        return None
    return st[key] / st["window_s"]


def percentile_ms(ctx: dict, key: str, q: float):
    vals = ctx["stats"].get(key)
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals), q)) * 1e3


def idle_share(ctx: dict):
    s = ctx.get("summary")
    if s is None or s.window_ns <= 0:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)


def program_device_ns(ctx: dict):
    s = ctx.get("summary")
    if s is None:
        return 0.0, 0
    return s.modules_matching(ctx["program"])


def program_ms_per_call(ctx: dict):
    ns, n = program_device_ns(ctx)
    return ns * 1e-6 / n if n else None


def program_ms_per_step(ctx: dict):
    ns, _ = program_device_ns(ctx)
    steps = ctx["stats"].get("emulated_steps", 0)
    return ns * 1e-6 / steps if ns and steps else None


def host_ms_per_span(ctx: dict, span_name: str):
    """Mean of (host span - program device time inside it), ms."""
    s = ctx.get("summary")
    if s is None:
        return None
    pairs = tracelib.device_time_inside(s, span_name, ctx["program"])
    if not pairs:
        return None
    return float(np.mean([a - b for a, b in pairs])) * 1e-6


def step_roofline(ctx: dict):
    """Share (%) of the program's device time that the semantic work of its
    emulated steps needs at the chip's peaks."""
    ns, _ = program_device_ns(ctx)
    st, tr = ctx["stats"], ctx["traffic"]
    steps = st.get("emulated_steps", 0)
    if not ns or not steps:
        return None
    w = worklib.step_work(ctx["cfg"], live_rows=st["live_row_steps"] / steps,
                          plastic=tr["plastic"], timed=tr["timed"])
    share, bound = worklib.roofline(w["bytes"] * steps, w["int8_ops"] * steps,
                                    ns * 1e-9, ctx["peak"])
    ctx.setdefault("notes", []).append(f"step roofline bound by {bound}")
    return share


def kernel_share(ctx: dict, pattern: str):
    """Device time of the Pallas kernels whose op name matches ``pattern``
    as a share (%) of the device's busy time."""
    s = ctx.get("summary")
    if s is None or s.busy_ns <= 0:
        return None
    k = s.kernels_matching(pattern)
    return 100.0 * k / s.busy_ns if k else None


def kernel_roofline(ctx: dict, pattern: str, work_bytes: float, ops: float):
    """Share (%) of the device time of the Pallas kernels whose op name
    matches ``pattern`` that ``work_bytes`` of HBM traffic and ``ops`` int8
    operations, the kernels' work over the window, need at the chip's
    peaks; None where no such kernel ran."""
    s = ctx.get("summary")
    ns = s.kernels_matching(pattern) if s is not None else 0.0
    if not ns:
        return None
    share, bound = worklib.roofline(work_bytes, ops, ns * 1e-9, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"{pattern} kernel roofline bound by {bound}")
    return share
