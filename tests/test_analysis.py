"""Fabric verifier battery (ISSUE 7): known-good plans lint clean,
deliberately corrupted plans/programs/kernels each produce their specific
path-qualified diagnostic, and the HLO collective parser survives the two
shapes that made it undercount to zero (layout annotations, async
``-start``/``-done`` pairs).
"""

import dataclasses
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.analysis import hlo as hlolib
from repro.analysis import jaxprlint, kernelcheck, planlint, roofline
from repro.analysis.diagnostics import (Diagnostic, Suppression, WARNING,
                                        apply_suppressions)
from repro.analysis.scenarios import CASES, benchmark_plans, level_caps, \
    plan_for

SCENARIOS = {sc.name: sc for sc in benchmark_plans()}


def checks(diags):
    return {d.check for d in diags}


def errors(diags):
    return [d for d in diags if d.severity != WARNING]


# ---------------------------------------------------------------------------
# hlo.py regex regression: layout annotations + async collective pairs
# ---------------------------------------------------------------------------


def test_collective_bytes_layout_annotated():
    # Optimized CPU HLO suffixes shapes with layouts; the original pattern
    # required `dtype[dims] op` adjacency and counted these as zero.
    text = "%all-gather.1 = s16[2,4]{1,0} all-gather(%param.0), dims={0}"
    per = hlolib.collective_bytes(text)
    assert per["all-gather"] == 2 * 4 * 2
    assert per["_counts"]["all-gather"] == 1


def test_collective_bytes_async_pair_counted_once():
    text = textwrap.dedent("""
        %ags = (s16[1,4]{1,0}, s16[2,4]{1,0}) all-gather-start(%p), dims={0}
        %agd = s16[2,4]{1,0} all-gather-done(%ags)
    """)
    per = hlolib.collective_bytes(text)
    # one transfer: the -start tuple's destination buffer, the -done skipped
    assert per["all-gather"] == 2 * 4 * 2
    assert per["_counts"]["all-gather"] == 1
    assert hlolib.total_collective_bytes(text) == 16


def test_collective_bytes_plain_shapes_still_counted():
    text = ("%ar = f32[8] all-reduce(%x), to_apply=%add\n"
            "%cp = bf16[4,4] collective-permute(%y)\n")
    per = hlolib.collective_bytes(text)
    assert per["all-reduce"] == 32
    assert per["collective-permute"] == 32
    sched = hlolib.collective_schedule(text)
    assert sched[0].startswith("all-reduce:")


def test_collective_bytes_ignores_non_collectives():
    # `all-gather-done` alone (no -start) and lookalike identifiers must
    # not double- or mis-count.
    text = "%x = s16[2,4]{1,0} all-gather-done(%ags)\n"
    assert hlolib.total_collective_bytes(text) == 0


# ---------------------------------------------------------------------------
# roofline revival: unit math + compiled 2-level exchange vs the wire model
# ---------------------------------------------------------------------------


def test_roofline_terms_and_dominant():
    r = roofline.Roofline(
        arch="test", shape="s", mesh="2", chips=2,
        hlo_flops=roofline.PEAK_FLOPS,         # 1 s compute
        hlo_bytes=roofline.HBM_BW / 2,         # 0.5 s memory
        coll_bytes=roofline.ICI_BW / 4,        # 0.25 s collective
        coll_detail={}, model_flops=roofline.PEAK_FLOPS,
        compute_s=1.0, memory_s=0.5, collective_s=0.25,
        bytes_per_device={})
    assert r.dominant == "compute"
    assert r.bound_s == 1.0
    assert r.useful_ratio == pytest.approx(0.5)    # model / (flops x chips)
    assert r.roofline_fraction == pytest.approx(0.5)
    d = r.to_dict()
    assert d["dominant"] == "compute" and d["bound_s"] == 1.0


@pytest.mark.slow
def test_compiled_exchange_gather_bytes_match_wire_model():
    """Compile a 2-level fabric exchange (8 virtual devices, subprocess) and
    assert the optimized HLO's all-gather bytes match the plan-derived
    ``fan_in x link_capacity x 2 B`` wire-word model within layout slack."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        from repro.analysis import hlo as hlolib
        from repro.analysis import jaxprlint
        from repro.analysis.scenarios import benchmark_plans
        sc = next(s for s in benchmark_plans()
                  if s.name == "PROJECTED_120CHIP")
        twin, cap = jaxprlint.shrink_plan(sc.plan, sc.cap_in)
        assert twin.n_levels == 2
        _, (fn, args) = jaxprlint.trace_fabric_exchange(twin, cap)
        text = fn.lower(*args).compile().as_text()
        per = hlolib.collective_bytes(text)
        print(json.dumps({
            "measured": per.get("all-gather", 0),
            "budget": jaxprlint.gather_budget_bytes(twin, cap),
            "gathers": per.get("_counts", {}).get("all-gather", 0),
        }))
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, f"stderr:\n{res.stderr[-2000:]}"
    got = json.loads(res.stdout.strip().splitlines()[-1])
    # per-partition program: one gather per level, bytes within [model, 2x]
    assert got["gathers"] == 2
    assert got["budget"] <= got["measured"] <= 2 * got["budget"], got


# ---------------------------------------------------------------------------
# planlint: every benchmark scenario clean; corruptions caught by name
# ---------------------------------------------------------------------------


def test_benchmark_plans_lint_clean():
    for sc in SCENARIOS.values():
        diags = planlint.lint_plan(sc.plan, sc.cap_in, sc.name)
        assert diags == [], [d.format() for d in diags]


def _replace_level(plan, i, **kw):
    levels = list(plan.levels)
    levels[i] = dataclasses.replace(levels[i], **kw)
    return dataclasses.replace(plan, levels=tuple(levels))


def test_overlapping_merge_segments_flagged():
    sc = SCENARIOS["EXT_4CASE_96CHIP"]
    layout = [list(segs) for segs in sc.plan.merge_layout(sc.cap_in)]
    layout[0][0] += 4                       # one segment spills into the next
    diags = planlint.check_merge_segments(
        sc.plan, sc.cap_in, "bad", layout=tuple(tuple(s) for s in layout))
    assert checks(diags) == {"plan.merge-segments"}
    assert diags[0].path == "bad/level[0]"
    assert "overlapping" in diags[0].message


def test_underfilled_and_misaligned_merge_segments_flagged():
    sc = SCENARIOS["FULL_BACKPLANE"]
    layout = [list(segs) for segs in sc.plan.merge_layout(sc.cap_in)]
    layout[0][0] -= 4
    under = planlint.check_merge_segments(
        sc.plan, sc.cap_in, "bad", layout=tuple(tuple(s) for s in layout))
    assert any("dropped silently" in d.message for d in under)
    layout[0][0] += 8                       # re-covers, but misaligned
    layout[0][1] -= 4
    mis = planlint.check_merge_segments(
        sc.plan, sc.cap_in, "bad", layout=tuple(tuple(s) for s in layout))
    assert any("misaligned" in d.message for d in mis)


def test_capacity_widening_flagged():
    # A level-1 uplink wider than the stream aggregated below it.
    name, fan_ins, cap_in, cap = CASES[1]
    caps = list(level_caps(fan_ins, cap_in, 0.05))
    caps[1] = 10_000
    plan = plan_for(fan_ins, cap, tuple(caps))
    diags = planlint.check_capacity_monotone(plan, cap_in, "bad")
    assert [d.check for d in diags] == ["plan.capacity-monotone"]
    assert diags[0].path == "bad/level[1]"
    assert "never widen" in diags[0].message


def test_leaf_uplink_wider_than_frame_flagged():
    plan = plan_for((12, 10), 128, (99, 40))     # cap_in is 32
    diags = planlint.check_capacity_monotone(plan, 32, "bad")
    assert any(d.path == "bad/level[0]" for d in diags)


def test_over_budget_detours_flagged():
    """Five dead edges forced onto one host exceed the Aggregator's four
    spare extension lanes."""
    from repro.core import fabric as fablib
    from repro.core.fabric import FabricSpec, LevelSpec, compile_fabric

    spec = FabricSpec(levels=(LevelSpec(fan_in=4), LevelSpec(fan_in=6)),
                      capacity=16)
    plan = compile_fabric(fablib.degrade_spec(
        compile_fabric(spec).spec, tuple((1, e) for e in range(5))))
    detour = np.asarray(plan.levels[1].detour).copy()
    detour[:5] = 5                          # all five lean on host 5
    bad = _replace_level(plan, 1, detour=detour)
    diags = planlint.check_detours(bad, "bad")
    budget = [d for d in diags if "spare extension lanes" in d.message]
    assert budget and budget[0].check == "plan.detours"
    assert budget[0].path == "bad/level[1]/edge[5]"


def test_detour_through_dead_host_flagged():
    sc = SCENARIOS["EXT_4CASE_96CHIP/exhausted"]     # edges 0 and 1 dead
    detour = np.asarray(sc.plan.levels[1].detour).copy()
    detour[0] = 1                           # reroute onto the other corpse
    bad = _replace_level(sc.plan, 1, detour=detour)
    assert any("itself dead" in d.message
               for d in planlint.check_detours(bad, "bad"))
    assert any(d.check == "plan.conservation"
               and "crosses dead host" in d.message
               for d in planlint.check_conservation(bad, "bad"))


def test_detours_without_dead_uplinks_flagged():
    sc = SCENARIOS["FULL_BACKPLANE"]
    bad = _replace_level(sc.plan, 0,
                         detour=np.full(sc.plan.n_nodes, -1, np.int32))
    diags = planlint.check_detours(bad, "bad")
    assert checks(diags) == {"plan.detours"}
    assert "no dead uplinks" in diags[0].message


def test_health_vector_length_mismatch_flagged():
    sc = SCENARIOS["EXT_4CASE_96CHIP/1dead_uplink"]
    bad = _replace_level(sc.plan, 1,
                         uplink_ok=np.ones(3, bool))  # level crosses 8 edges
    diags = planlint.check_shape(bad, "bad")
    assert checks(diags) == {"plan.shape"}
    assert "uplink_ok" in diags[0].message


def test_conservation_classes_partition_and_track_degradation():
    healthy = SCENARIOS["EXT_4CASE_96CHIP"]
    onedead = SCENARIOS["EXT_4CASE_96CHIP/1dead_uplink"]
    exhausted = SCENARIOS["EXT_4CASE_96CHIP/exhausted"]
    n = healthy.plan.n_nodes

    def counts(plan):
        c = planlint.classify_pairs(plan)
        cover = (c["ungated"].astype(int) + c["delivered"]
                 + c["unroutable"])
        assert (cover == 1).all()           # exactly one class per pair
        return {k: int(v.sum()) for k, v in c.items()}

    h, d1, ex = counts(healthy.plan), counts(onedead.plan), \
        counts(exhausted.plan)
    assert h["unroutable"] == 0 and h["rerouted"] == 0
    # a detoured dead uplink loses no traffic — it only marks it rerouted
    assert d1["delivered"] == h["delivered"] and d1["rerouted"] > 0
    # reroute exhaustion turns the lost pairs unroutable, nothing vanishes
    assert ex["unroutable"] > 0
    assert ex["delivered"] + ex["unroutable"] == h["delivered"]
    assert h["ungated"] == d1["ungated"] == ex["ungated"]
    assert h["delivered"] + h["ungated"] == n * n


# ---------------------------------------------------------------------------
# jaxprlint: program weight-class corruptions caught on hand-built jaxprs
# ---------------------------------------------------------------------------


def test_scan_const_closed_into_body_flagged():
    import jax
    import jax.numpy as jnp

    big = jnp.arange(jaxprlint.LARGE_CONST_ELEMS + 1)

    def f(xs):
        def body(c, x):
            return c + (x * big).sum(), x
        return jax.lax.scan(body, jnp.int32(0), xs)

    closed = jax.make_jaxpr(f)(
        jnp.zeros((3, jaxprlint.LARGE_CONST_ELEMS + 1), jnp.int32))
    diags = jaxprlint.check_scan_consts(closed, "prog")
    assert "program.scan-const" in checks(diags)
    assert any("closed into the scan body" in d.message for d in diags)


def test_iota_materialized_in_scan_body_flagged():
    import jax
    import jax.numpy as jnp

    def f(xs):
        def body(c, x):
            ramp = jnp.arange(jaxprlint.LARGE_CONST_ELEMS + 1,
                              dtype=jnp.int32)
            return c + ramp.sum() + x, x
        return jax.lax.scan(body, jnp.int32(0), xs)

    closed = jax.make_jaxpr(f)(jnp.zeros((3,), jnp.int32))
    diags = jaxprlint.check_scan_consts(closed, "prog")
    assert any("materialized inside the scan body" in d.message
               for d in diags)


def test_f64_leak_flagged():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.zeros(3, jnp.float32))
    diags = jaxprlint.check_f64(closed, "prog")
    assert checks(diags) == {"program.f64"}


def _pmap_gather_jaxpr(payload):
    """An axis-bound all_gather without needing >1 device."""
    import jax

    return jax.make_jaxpr(jax.pmap(
        lambda x: jax.lax.all_gather(x, "fab0"), axis_name="fab0"))(payload)


def test_gather_widening_flagged():
    import jax.numpy as jnp

    closed = _pmap_gather_jaxpr(jnp.zeros((1, 4), jnp.int32))
    diags = jaxprlint.check_gathers(closed, "prog")
    assert "program.gather-widening" in checks(diags)
    # the int32 timestamp plane is legal on the timed lane only
    assert jaxprlint.check_gathers(closed, "prog", timed=True) == []


def test_gather_count_flagged():
    import jax

    def two(x):
        return (jax.lax.all_gather(x, "fab0"),
                jax.lax.all_gather(x + 1, "fab0"))

    import jax.numpy as jnp
    closed = jax.make_jaxpr(jax.pmap(two, axis_name="fab0"))(
        jnp.zeros((1, 4), jnp.int16))
    diags = jaxprlint.check_gathers(closed, "prog")
    assert checks(diags) == {"program.gather-count"}


def test_collective_budget_flagged():
    import jax.numpy as jnp

    sc = SCENARIOS["PROJECTED_120CHIP"]
    twin, cap = jaxprlint.shrink_plan(sc.plan, sc.cap_in)
    budget = jaxprlint.gather_budget_bytes(twin, cap)
    closed = _pmap_gather_jaxpr(jnp.zeros((1, budget), jnp.int16))
    diags = jaxprlint.check_gathers(closed, "prog", plan=twin, cap_in=cap)
    assert "program.collective-budget" in checks(diags)


def _pmap_ppermute_jaxpr(payload):
    """An axis-bound ppermute without needing >1 device."""
    import jax

    return jax.make_jaxpr(jax.pmap(
        lambda x: jax.lax.ppermute(x, "fab0", [(0, 0)]),
        axis_name="fab0"))(payload)


def test_routed_gather_count_flagged():
    # Any all_gather in a routed program is an error — the whole point of
    # the mode is that every wire byte moves edge-to-edge via ppermute.
    import jax.numpy as jnp

    closed = _pmap_gather_jaxpr(jnp.zeros((1, 4), jnp.int16))
    diags = jaxprlint.check_routed(closed, "prog")
    assert checks(diags) == {"program.gather-count"}
    assert jaxprlint.check_routed(
        _pmap_ppermute_jaxpr(jnp.zeros((1, 4), jnp.int16)), "prog") == []


def test_routed_widening_flagged():
    import jax.numpy as jnp

    closed = _pmap_ppermute_jaxpr(jnp.zeros((1, 4), jnp.int32))
    diags = jaxprlint.check_routed(closed, "prog")
    assert checks(diags) == {"program.gather-widening"}
    # the int32 timestamp plane is legal on the timed lane only
    assert jaxprlint.check_routed(closed, "prog", timed=True) == []


def test_routed_budget_flagged():
    import jax.numpy as jnp

    sc = SCENARIOS["PROJECTED_120CHIP"]
    twin, cap = jaxprlint.shrink_plan(sc.plan, sc.cap_in)
    budget = jaxprlint.routed_budget_bytes(twin, cap)
    assert 0 < budget < jaxprlint.gather_budget_bytes(twin, cap)
    closed = _pmap_ppermute_jaxpr(jnp.zeros((1, budget), jnp.int16))
    diags = jaxprlint.check_routed(closed, "prog", plan=twin, cap_in=cap)
    assert "program.collective-budget" in checks(diags)


def test_routed_exchange_lint_clean():
    # The real routed program of every headline scenario passes its own
    # invariants: zero all_gathers, edge traffic within budget, int16 wire.
    sc = SCENARIOS["EXT_4CASE_96CHIP"]
    diags = jaxprlint.lint_fabric_exchange_routed(sc.plan, sc.cap_in)
    assert errors(diags) == []


def test_shrink_plan_preserves_structure():
    sc = SCENARIOS["EXT_4CASE_96CHIP/1dead_uplink"]
    twin, cap = jaxprlint.shrink_plan(sc.plan, sc.cap_in)
    assert twin.n_levels == sc.plan.n_levels
    assert twin.n_nodes == 8 and cap == 4
    # the degraded level keeps a dead edge, so the twin's program carries
    # the same reroute datapath the full plan would
    assert twin.levels[1].uplink_ok is not None
    assert not twin.levels[1].uplink_ok.all()
    assert errors(planlint.lint_plan(twin, cap, "twin")) == []
    assert jaxprlint.gather_budget_bytes(twin, cap) > 0


def test_route_step_and_run_stream_lint_clean():
    sc = SCENARIOS["FULL_BACKPLANE"]
    assert jaxprlint.lint_route_step(sc.plan, sc.cap_in) == []
    assert jaxprlint.lint_run_stream() == []


# ---------------------------------------------------------------------------
# kernelcheck: pack units + Pallas grids
# ---------------------------------------------------------------------------


def test_pack_units_clean():
    assert kernelcheck.check_pack_units([5, 8]) == []


def test_segmented_pack_without_base_offsets_overlaps():
    """The exact bug class the checker exists for: per-segment ranks
    scattered without their destination base offsets."""
    import jax.numpy as jnp

    def broken(ok, capacity):
        pos = jnp.cumsum(ok, axis=-1) - ok      # rank within segment only
        keep = (ok == 1) & (pos < capacity)
        return (jnp.where(keep, pos, capacity).reshape(-1),
                keep.reshape(-1))

    diags = kernelcheck.check_pack_writeset(broken, (2, 4), 5, "broken")
    assert [d.check for d in diags] == ["kernel.scatter-overlap"]
    assert "neighbour" in diags[0].message


def test_reversed_ranks_break_stream_order():
    import jax.numpy as jnp

    def reversed_ranks(ok, capacity):
        pos = jnp.cumsum(ok) - ok
        keep = (ok == 1) & (pos < capacity)
        k = jnp.minimum(ok.sum(), capacity)
        return jnp.where(keep, k - 1 - pos, capacity), keep

    diags = kernelcheck.check_pack_writeset(reversed_ranks, (6,), 4, "rev")
    assert [d.check for d in diags] == ["kernel.scatter-order"]


def test_off_by_one_rank_hits_overflow_slot():
    import jax.numpy as jnp

    def off_by_one(ok, capacity):
        pos = jnp.cumsum(ok) - ok
        keep = (ok == 1) & (pos <= capacity)  # admits rank `capacity` itself
        return jnp.where(keep, pos, capacity), keep

    diags = kernelcheck.check_pack_writeset(off_by_one, (6,), 4, "off")
    assert diags and diags[0].check == "kernel.scatter-bounds"
    assert "overflow slot" in diags[0].message


def test_router_kernel_grids_clean():
    assert kernelcheck.check_router_kernels() == []


def test_overlapping_grid_tiling_flagged():
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    def bad(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((1, 4), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 4), lambda i: (0, 0)),  # every cell
            out_shape=jax.ShapeDtypeStruct((2, 4), jnp.float32))(x)

    diags = kernelcheck.check_pallas_calls(
        bad, (jnp.zeros((2, 4), jnp.float32),), "bad")
    assert "kernel.grid-overlap" in checks(diags)


@pytest.mark.parametrize("semantics, clean", [
    (("parallel", "arbitrary"), True),     # revisit along a reduction axis
    (("arbitrary", "parallel"), False),    # revisit along a parallel axis
])
def test_accumulator_block_needs_a_sequential_axis(semantics, clean):
    """An output block that consecutive steps of a declared-sequential grid
    axis revisit is a resident accumulator; the same revisit along a
    parallel axis is an overwrite."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    def row_sums(x):
        def kernel(x_ref, o_ref):
            @pl.when(pl.program_id(1) == 0)
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)
            o_ref[...] += x_ref[...]
        return pl.pallas_call(
            kernel, grid=(2, 3),
            in_specs=[pl.BlockSpec((1, 4), lambda i, k: (i, k))],
            out_specs=pl.BlockSpec((1, 4), lambda i, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 4), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics),
            interpret=True)(x)

    diags = kernelcheck.check_pallas_calls(
        row_sums, (jnp.zeros((2, 12), jnp.float32),), "acc")
    assert (diags == []) is clean
    if not clean:
        assert "kernel.grid-overlap" in checks(diags)


def test_out_of_bounds_grid_tiling_flagged():
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    def bad(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            kernel, grid=(3,),                        # one block too far
            in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((1, 4), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 4), jnp.float32))(x)

    diags = kernelcheck.check_pallas_calls(
        bad, (jnp.zeros((2, 4), jnp.float32),), "bad")
    assert "kernel.grid-bounds" in checks(diags)


# ---------------------------------------------------------------------------
# suppressions: waivers are themselves linted
# ---------------------------------------------------------------------------


def test_suppression_waives_matching_finding():
    d = Diagnostic("plan.detours", "X/level[1]/edge[0]", "msg")
    active, suppressed = apply_suppressions(
        [d], [Suppression("plan.detours", "X/", reason="known-flaky rig")])
    assert suppressed == [d] and active == []


def test_stale_suppression_fails_the_run():
    active, suppressed = apply_suppressions(
        [], [Suppression("plan.detours", reason="long gone")])
    assert suppressed == []
    assert [d.check for d in active] == ["suppression.stale"]
    assert active[0].severity != WARNING


def test_undocumented_suppression_fails_the_run():
    d = Diagnostic("plan.detours", "X", "msg")
    active, _ = apply_suppressions([d], [Suppression("plan.detours")])
    assert "suppression.undocumented" in {a.check for a in active}


# ---------------------------------------------------------------------------
# CLI: exit status and the full default pass
# ---------------------------------------------------------------------------


def test_cli_exit_codes(monkeypatch, capsys):
    from repro.analysis import lint

    monkeypatch.setattr(lint, "run_lint", lambda **kw: [])
    assert lint.main(["-q"]) == 0
    bad = Diagnostic("plan.merge-segments", "EXT/level[0]", "segments clash")
    monkeypatch.setattr(lint, "run_lint", lambda **kw: [bad])
    assert lint.main(["-q"]) == 1
    out = capsys.readouterr().out
    assert "plan.merge-segments @ EXT/level[0]" in out    # path-qualified
    warn = Diagnostic("plan.detours", "EXT", "odd but legal", WARNING)
    monkeypatch.setattr(lint, "run_lint", lambda **kw: [warn])
    assert lint.main(["-q"]) == 0                         # warnings don't fail


@pytest.mark.slow
def test_run_lint_default_passes():
    """The acceptance gate: every default pass over every benchmark scenario
    is error-free in-process (device-bound exchange lints degrade to
    warnings under pytest's single-device view; the CI stage runs the CLI
    with 8 virtual devices and catches those too)."""
    from repro.analysis import lint

    findings = lint.run_lint()
    assert errors(findings) == [], [d.format() for d in errors(findings)]
