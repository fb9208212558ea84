"""Program lint: weight-class regressions in the jitted exchange programs.

Walks the jaxpr of the entry points the fabric actually ships —
``fabric_route_step`` (stacked executor), ``fabric_exchange`` (the
shard_map'd per-leaf round) and ``snn.stream.run_stream`` (the scanned
emulation) — and fails on regressions no example-based test reliably
catches:

  * ``program.f64``              — double-precision values anywhere (the
    wire is int16/int32; an f64 leak doubles every buffer it touches);
  * ``program.gather-widening``  — an ``all_gather`` moving anything wider
    than the int16 wire words (a pre-gather upcast silently doubles wire
    bytes);
  * ``program.gather-count``     — more than one ``all_gather`` per fabric
    level (per mesh axis);
  * ``program.collective-budget``— gathered bytes per round exceeding the
    plan-derived link budget (``sum_i fan_in_i * len_i * 2``);
  * ``program.scan-const``       — large constants closed over or
    rematerialized (literal ``iota``/``broadcast_in_dim``) inside a
    ``lax.scan`` body instead of riding the carry/closure.

Routed-mode programs (``exchange_mode="routed"``) get their own pass,
``check_routed``: *zero* all_gathers (every wire byte moves along hop-graph
edges via ``ppermute``), the per-edge byte budget
(``sum_i (fan_in_i - 1) * len_i * 2``), and the int16 wire dtype on every
permuted plane.

``fabric_exchange`` needs one device per leaf, so the linter traces a
structure-preserving *shrunk twin* of each plan (every fan-in clamped to
2, capacities re-clamped, one dead edge kept per degraded level): the
checked properties — one gather per level, wire dtype, the budget
formula — are shape-generic, and the twin fits the 8 virtual CPU devices
the CLI forces.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator

import numpy as np

from repro.analysis.diagnostics import Diagnostic, WARNING
from repro.analysis.planlint import stream_lengths
from repro.core.fabric import FabricPlan, compile_fabric

LARGE_CONST_ELEMS = 1 << 15     # arrays beyond this don't belong in a body
WIRE_WORD_BYTES = 2             # events.pack_wire16 — the int16 wire format
WIRE_DTYPES = ("int16", "uint16")


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every eqn, descending into sub-jaxprs (pjit,
    shard_map, scan, while, cond, custom_jvp, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from iter_eqns(sub)


def _sub_jaxprs(val) -> Iterator:
    from jax.extend import core as jex

    if isinstance(val, jex.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _sub_jaxprs(v)


def _aval_bytes(aval) -> int:
    return int(math.prod(aval.shape)) * aval.dtype.itemsize


def check_f64(closed, path: str) -> list[Diagnostic]:
    """No double precision anywhere in the program."""
    diags = []
    for eqn in iter_eqns(closed.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if aval.dtype in (np.float64, np.complex128):
                diags.append(Diagnostic(
                    "program.f64", f"{path}/{eqn.primitive.name}",
                    f"{aval.dtype} value of shape {aval.shape} — the "
                    f"datapath is f32/int16/int32"))
                break
    return diags[:8]


def check_gathers(closed, path: str, *, plan: FabricPlan | None = None,
                  cap_in: int | None = None,
                  wire_dtypes: tuple[str, ...] = WIRE_DTYPES,
                  timed: bool = False) -> list[Diagnostic]:
    """One int16 all-gather per fabric level, within the link budget."""
    diags = []
    per_axis: dict[str, int] = {}
    total_bytes = 0
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "all_gather":
            continue
        axes = eqn.params.get("axis_name")
        axes = axes if isinstance(axes, tuple) else (axes,)
        for ax in axes:
            per_axis[str(ax)] = per_axis.get(str(ax), 0) + 1
        aval = eqn.invars[0].aval
        out_bytes = _aval_bytes(eqn.outvars[0].aval)
        total_bytes += out_bytes
        allowed = wire_dtypes + (("int32",) if timed else ())
        if str(aval.dtype) not in allowed:
            diags.append(Diagnostic(
                "program.gather-widening", f"{path}/axis[{axes}]",
                f"all_gather moves {aval.dtype} (shape {aval.shape}) — the "
                f"wire format is int16 words; a pre-gather widening "
                f"multiplies wire bytes"))
    for ax, count in per_axis.items():
        if count > (2 if timed else 1):
            diags.append(Diagnostic(
                "program.gather-count", f"{path}/axis[{ax}]",
                f"{count} all_gathers on one fabric level — each level is "
                f"one gather of the packed wire stream"))
    if plan is not None and cap_in is not None:
        budget = gather_budget_bytes(plan, cap_in, timed=timed)
        if total_bytes > budget:
            diags.append(Diagnostic(
                "program.collective-budget", path,
                f"program gathers {total_bytes} bytes/round but the plan's "
                f"link capacities budget {budget} "
                f"(fan_in x link_capacity x {WIRE_WORD_BYTES}B per level)"))
    return diags


def gather_budget_bytes(plan: FabricPlan, cap_in: int, *,
                        timed: bool = False) -> int:
    """Plan-derived wire budget of one exchange round, per leaf: each level
    gathers ``fan_in`` child streams of the packed length, as int16 wire
    words (plus the int32 timestamp plane when timed)."""
    lens = stream_lengths(plan, cap_in)
    word = WIRE_WORD_BYTES + (4 if timed else 0)
    return sum(lvl.fan_in * ln * word
               for lvl, ln in zip(plan.levels, lens))


def check_routed(closed, path: str, *, plan: FabricPlan | None = None,
                 cap_in: int | None = None,
                 wire_dtypes: tuple[str, ...] = WIRE_DTYPES,
                 timed: bool = False) -> list[Diagnostic]:
    """Routed-mode program invariants: zero all_gathers (every wire byte
    moves edge-to-edge via ``ppermute``), the per-edge byte budget, and the
    int16 wire dtype on every permuted plane."""
    diags = []
    n_gathers = 0
    total_bytes = 0
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name == "all_gather":
            n_gathers += 1
            continue
        if eqn.primitive.name != "ppermute":
            continue
        aval = eqn.invars[0].aval
        total_bytes += _aval_bytes(eqn.outvars[0].aval)
        allowed = wire_dtypes + (("int32",) if timed else ())
        if str(aval.dtype) not in allowed:
            diags.append(Diagnostic(
                "program.gather-widening", f"{path}/ppermute",
                f"ppermute moves {aval.dtype} (shape {aval.shape}) — the "
                f"routed wire format is int16 words; a pre-exchange "
                f"widening multiplies per-edge bytes"))
    if n_gathers:
        diags.append(Diagnostic(
            "program.gather-count", path,
            f"{n_gathers} all_gather(s) in a routed program — routed mode "
            f"exchanges only along hop-graph edges (ppermute); a gather "
            f"reintroduces O(n_chips) broadcast bandwidth"))
    if plan is not None and cap_in is not None:
        budget = routed_budget_bytes(plan, cap_in, timed=timed)
        if total_bytes > budget:
            diags.append(Diagnostic(
                "program.collective-budget", path,
                f"routed program permutes {total_bytes} bytes/round but the "
                f"plan's edge schedule budgets {budget} "
                f"((fan_in - 1) x stream_len x {WIRE_WORD_BYTES}B per "
                f"level)"))
    return diags


def routed_budget_bytes(plan: FabricPlan, cap_in: int, *,
                        timed: bool = False) -> int:
    """Per-edge wire budget of one *routed* exchange round, per leaf: each
    level runs ``fan_in - 1`` ring rotations, each shipping this child's
    packed stream to one sibling (the own slot never travels), as int16
    wire words (plus the int32 timestamp plane when timed).  The routed /
    gather byte ratio is therefore ``(fan_in - 1) / fan_in`` per level in
    the worst case — and lower when route-enable pruning drops edges at
    the top level."""
    lens = stream_lengths(plan, cap_in)
    word = WIRE_WORD_BYTES + (4 if timed else 0)
    return sum((lvl.fan_in - 1) * ln * word
               for lvl, ln in zip(plan.levels, lens))


def check_scan_consts(closed, path: str,
                      limit: int = LARGE_CONST_ELEMS) -> list[Diagnostic]:
    """Large arrays must ride the scan carry/xs, not the body.

    Scan hoists Python-closure constants of the body into its leading
    ``num_consts`` operands; when such an operand is one of the program's
    *constvars* (baked-in data, not a traced argument), the array is
    embedded in the staged computation itself."""
    from jax.extend import core as jex

    diags = []
    constvars = {id(v) for v in closed.jaxpr.constvars}
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params.get("jaxpr")
        if not isinstance(body, jex.ClosedJaxpr):
            continue
        n_consts = int(eqn.params.get("num_consts", 0))
        for v in eqn.invars[:n_consts]:
            aval = getattr(v, "aval", None)
            if aval is None or id(v) not in constvars:
                continue
            size = int(math.prod(aval.shape))
            if size > limit:
                diags.append(Diagnostic(
                    "program.scan-const", f"{path}/scan",
                    f"{size}-element constant closed into the scan body "
                    f"(baked into the program; hoist it or thread it as an "
                    f"xs/carry input)"))
        for sub in iter_eqns(body.jaxpr):
            if sub.primitive.name not in ("iota", "broadcast_in_dim"):
                continue
            if any(not isinstance(v, jex.Literal) for v in sub.invars):
                continue
            out = sub.outvars[0].aval
            if int(math.prod(out.shape)) > limit:
                diags.append(Diagnostic(
                    "program.scan-const", f"{path}/scan/"
                    f"{sub.primitive.name}",
                    f"{int(math.prod(out.shape))}-element "
                    f"{sub.primitive.name} materialized inside the scan "
                    f"body every step — hoist the constant"))
    return diags[:8]


# ---------------------------------------------------------------------------
# Entry-point drivers
# ---------------------------------------------------------------------------


def shrink_plan(plan: FabricPlan, cap_in: int,
                max_fan: int = 2) -> tuple[FabricPlan, int]:
    """Structure-preserving twin small enough for the virtual-CPU mesh:
    fan-ins clamped to ``max_fan``, capacities re-clamped to the shrunk
    streams, one dead edge kept per level that had any (so degraded plans
    lint their degraded program).  Returns ``(twin, twin_cap_in)``."""
    cap_small = min(cap_in, 4)
    fans = [min(sl.fan_in, max_fan) for sl in plan.spec.levels]
    levels, lens = [], []
    for i, (sl, pl) in enumerate(zip(plan.spec.levels, plan.levels)):
        feed = cap_small if i == 0 else fans[i - 1] * lens[i - 1]
        cap = pl.link_capacity
        cap = None if cap is None else min(cap, feed)
        lens.append(feed if cap is None else cap)
        levels.append(dataclasses.replace(
            sl, fan_in=fans[i], enables=None, link_capacity=cap, link=None,
            uplink_health=None, downlink_health=None))
    n_nodes = math.prod(fans)
    gsize = 1
    for i, pl in enumerate(plan.levels):
        n_edges = n_nodes // gsize
        dead = [False] * n_edges
        dead[0] = True
        if pl.uplink_ok is not None:
            levels[i] = dataclasses.replace(
                levels[i], uplink_health=tuple(not d for d in dead))
        if pl.downlink_ok is not None:
            levels[i] = dataclasses.replace(
                levels[i], downlink_health=tuple(not d for d in dead))
        gsize *= fans[i]
    total = sum(f * ln for f, ln in zip(fans, lens))
    spec = dataclasses.replace(
        plan.spec, levels=tuple(levels),
        capacity=min(plan.capacity, total))
    return compile_fabric(spec), cap_small


def lint_route_step(plan: FabricPlan, cap_in: int,
                    path: str = "fabric_route_step") -> list[Diagnostic]:
    """Trace the stacked executor on this plan and run the jaxpr checks
    (no collectives here — the stacked round is single-device)."""
    import jax
    import jax.numpy as jnp

    from repro.core import identity_router
    from repro.core.events import EventFrame
    from repro.core.fabric import fabric_route_step

    state = identity_router(plan.n_nodes)
    frames = EventFrame(
        labels=jnp.zeros((plan.n_nodes, cap_in), jnp.int32),
        times=jnp.zeros((plan.n_nodes, cap_in), jnp.int32),
        valid=jnp.zeros((plan.n_nodes, cap_in), jnp.bool_))
    closed = jax.make_jaxpr(
        lambda f: fabric_route_step(state, f, plan))(frames)
    return check_f64(closed, path) + check_scan_consts(closed, path)


def lint_fabric_exchange(plan: FabricPlan, cap_in: int,
                         path: str = "fabric_exchange") -> list[Diagnostic]:
    """Trace the shard_map'd per-leaf round on the plan's shrunk twin and
    run every jaxpr check, including the gather-per-level and wire-budget
    invariants.  Needs ``twin.n_nodes`` devices (the CLI forces 8 virtual
    CPU devices); emits a warning and skips when the host has fewer."""
    import jax

    twin, cap_small = shrink_plan(plan, cap_in)
    if len(jax.devices()) < twin.n_nodes:
        return [Diagnostic(
            "program.devices", path,
            f"skipped: {twin.n_nodes} devices needed, "
            f"{len(jax.devices())} available (run via "
            f"`python -m repro.analysis.lint`, which forces "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            WARNING)]
    closed, _ = trace_fabric_exchange(twin, cap_small)
    return (check_f64(closed, path)
            + check_gathers(closed, path, plan=twin, cap_in=cap_small)
            + check_scan_consts(closed, path))


def lint_fabric_exchange_routed(plan: FabricPlan, cap_in: int,
                                path: str = "fabric_exchange[routed]"
                                ) -> list[Diagnostic]:
    """Trace the shard_map'd round of the plan's shrunk twin in
    ``exchange_mode="routed"`` and pin the routed invariants: zero
    all_gathers, ppermute-only wire traffic within the per-edge byte
    budget, int16 wire words on every permuted plane.  Device-count
    handling as in ``lint_fabric_exchange``."""
    import jax

    from repro.core.fabric import with_exchange_mode

    twin, cap_small = shrink_plan(plan, cap_in)
    twin = with_exchange_mode(twin, "routed")
    if len(jax.devices()) < twin.n_nodes:
        return [Diagnostic(
            "program.devices", path,
            f"skipped: {twin.n_nodes} devices needed, "
            f"{len(jax.devices())} available (run via "
            f"`python -m repro.analysis.lint`, which forces "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            WARNING)]
    closed, _ = trace_fabric_exchange(twin, cap_small)
    return (check_f64(closed, path)
            + check_routed(closed, path, plan=twin, cap_in=cap_small)
            + check_scan_consts(closed, path))


def trace_fabric_exchange(plan: FabricPlan, cap_in: int):
    """(jaxpr, jitted fn + example args) of the shard_map'd exchange round."""
    import jax
    import jax.numpy as jnp

    from repro.core.events import EventFrame
    from repro.core.fabric import FabricInterconnect
    from repro.parallel.sharding import fabric_mesh

    mesh = fabric_mesh(plan)
    fn = FabricInterconnect(mesh=mesh, plan=plan).exchange_fn()
    n = plan.n_nodes
    frame = EventFrame(
        labels=jnp.zeros((n, cap_in), jnp.int32),
        times=jnp.zeros((n, cap_in), jnp.int32),
        valid=jnp.zeros((n, cap_in), jnp.bool_))
    fwd, rev = plan.identity_tables()
    closed = jax.make_jaxpr(fn)(frame, fwd, rev)
    return closed, (fn, (frame, fwd, rev))


def lint_run_stream(path: str = "run_stream") -> list[Diagnostic]:
    """Trace the scanned emulation pipeline on a small star network and run
    the f64 + scan-const checks (the scan body is where a hoisting
    regression would land)."""
    import jax
    import jax.numpy as jnp

    from repro.snn import network as netlib
    from repro.snn import stream as stlib

    cfg = netlib.NetworkConfig(n_chips=2, capacity=64)
    params = netlib.init_feedforward(jax.random.key(0), cfg)
    state = netlib.init_state(cfg, 1)
    drives = jnp.zeros((3, cfg.n_chips, 1, cfg.chip.n_rows), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, s, d: stlib.run_stream(p, s, d, cfg, mode="event"))(
            params, state, drives)
    return check_f64(closed, path) + check_scan_consts(closed, path)
