"""Multi-device distribution tests (8 virtual CPU devices via subprocess —
the main pytest process keeps its single-device view)."""

import subprocess
import sys
import textwrap

import pytest

# Every test spawns an 8-device subprocess — slow by construction.
pytestmark = pytest.mark.slow


def _run(body: str) -> str:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.parallel.sharding import auto_mesh
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, f"stderr:\n{res.stderr[-2000:]}"
    return res.stdout


def test_hierarchical_psum_equals_flat():
    out = _run("""
        from repro.parallel.collectives import hierarchical_psum
        mesh = auto_mesh((2, 4), ("pod", "data"))
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

        def flat(v):
            return jax.lax.psum(jax.lax.psum(v, "data"), "pod")

        def hier(v):
            return hierarchical_psum(v, "data", "pod")

        spec = P(("pod", "data"))
        f = jax.jit(jax.shard_map(flat, mesh=mesh, in_specs=spec,
                                  out_specs=spec))
        h = jax.jit(jax.shard_map(hier, mesh=mesh, in_specs=spec,
                                  out_specs=spec))
        print("MATCH", bool(jnp.allclose(f(x), h(x))))
    """)
    assert "MATCH True" in out


def test_star_exchange_on_8_chips():
    out = _run("""
        from repro.core import StarInterconnect, identity_router, make_frame
        mesh = auto_mesh((8,), ("chip",))
        ic = StarInterconnect(mesh, "chip", capacity=64)
        fn = ic.exchange_fn()
        st = identity_router(8)
        labels = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (8, 1))
        frames, _ = make_frame(labels, jnp.zeros_like(labels),
                               jnp.ones((8, 8), bool), 8)
        out, dropped = fn(frames, st.fwd_tables, st.rev_tables,
                          st.route_enables)
        # all-to-all minus self: each chip receives 7 × 8 events
        print("COUNTS", out.count().tolist(), int(dropped.congestion.sum()),
              int(dropped.uplink.sum()))
    """)
    assert "COUNTS [56, 56, 56, 56, 56, 56, 56, 56] 0 0" in out


def test_stream_fn_matches_per_step_exchange_on_8_chips():
    """The scanned shard_map stream equals T per-step exchange dispatches."""
    out = _run("""
        from repro.core import StarInterconnect, identity_router, make_frame
        mesh = auto_mesh((8,), ("chip",))
        ic = StarInterconnect(mesh, "chip", capacity=32)
        st = identity_router(8)
        key = jax.random.key(0)
        T = 5
        labels = jax.random.randint(key, (T, 8, 16), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1),
                                   (T, 8, 16)) < 0.6
        frames, _ = make_frame(labels, None, valid, 16)
        outs, drops = ic.stream_fn()(frames, st.fwd_tables, st.rev_tables,
                                     st.route_enables)
        ex = ic.exchange_fn()
        ok = True
        for t in range(T):
            o, d = ex(jax.tree.map(lambda x: x[t], frames), st.fwd_tables,
                      st.rev_tables, st.route_enables)
            ok &= bool(jnp.array_equal(outs.labels[t], o.labels))
            ok &= bool(jnp.array_equal(outs.valid[t], o.valid))
            ok &= bool(jnp.array_equal(drops.congestion[t], d.congestion))
            ok &= bool(jnp.array_equal(drops.uplink[t], d.uplink))
        print("STREAM_MATCH", ok)
    """)
    assert "STREAM_MATCH True" in out


def test_hierarchical_stacked_matches_shard_map():
    """route_step_hierarchical (one device, stacked) is bit-exact with the
    shard_map'd hierarchical_exchange on a 2x4 pod/chip mesh, and the
    scanned hierarchical stream_fn agrees with both."""
    out = _run("""
        from repro.core import (StarInterconnect, identity_router, make_frame,
                                route_step_hierarchical, full_route_enables)
        n_pods, per = 2, 4
        N = n_pods * per
        st = identity_router(N)
        intra = full_route_enables(per)
        inter = full_route_enables(n_pods)
        key = jax.random.key(3)
        T = 3
        labels = jax.random.randint(key, (T, N, 16), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 2),
                                   (T, N, 16)) < 0.7
        frames, _ = make_frame(labels, None, valid, 16)
        mesh = auto_mesh((n_pods, per), ("pod", "chip"))
        ok = True
        for caps in (dict(), dict(link_capacity=12, pod_capacity=24)):
            ic = StarInterconnect(mesh, "chip", pod_axis="pod", capacity=24,
                                  **caps)
            outs, drops = ic.stream_fn()(frames, st.fwd_tables,
                                         st.rev_tables, intra, inter)
            for t in range(T):
                ref, d_ref = route_step_hierarchical(
                    st, jax.tree.map(lambda x: x[t], frames), 24,
                    n_pods=n_pods, intra_enables=intra, inter_enables=inter,
                    **caps)
                ok &= bool(jnp.array_equal(outs.labels[t], ref.labels))
                ok &= bool(jnp.array_equal(outs.valid[t], ref.valid))
                ok &= bool(jnp.array_equal(drops.congestion[t],
                                           d_ref.congestion))
                ok &= bool(jnp.array_equal(drops.uplink[t], d_ref.uplink))
        print("HIER_MATCH", ok)
    """)
    assert "HIER_MATCH True" in out


def test_timed_exchange_stacked_matches_shard_map():
    """The timed datapath (ISSUE 4) distributed: star_exchange and
    hierarchical_exchange with ``timing=`` on real meshes are bit-exact —
    timestamps included — with the single-device stacked mirrors, and the
    timed stream_fn agrees with the per-round exchange."""
    out = _run("""
        from repro.core import (StarInterconnect, RouterState, identity_router,
                                make_frame, route_step,
                                route_step_hierarchical, full_route_enables,
                                timed_wire)
        w = timed_wire()
        N = 8
        st = identity_router(N)
        key = jax.random.key(7)
        labels = jax.random.randint(key, (N, 24), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1), (N, 24)) < 0.5
        frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, 24)
        ok = True

        # Star on 8 chips vs the stacked timed round (full enables incl.
        # self-loops so both sides see identical routes).
        en = jnp.ones((N, N), bool)
        mesh = auto_mesh((N,), ("chip",))
        ic = StarInterconnect(mesh, "chip", capacity=32, timing=w)
        out_s, d_s = ic.exchange_fn()(frames, st.fwd_tables, st.rev_tables,
                                      en)
        ref_s, dr_s = route_step(
            RouterState(st.fwd_tables, st.rev_tables, en), frames, 32,
            timing=w)
        ok &= bool(jnp.array_equal(out_s.times, ref_s.times))
        ok &= bool(jnp.array_equal(out_s.labels, ref_s.labels))
        ok &= bool(jnp.array_equal(d_s.congestion, dr_s))
        # Timed stream_fn: T scanned rounds == the per-round exchange.
        frames_T = jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                           (3, *x.shape)),
                                frames)
        outs_T, _ = ic.stream_fn()(frames_T, st.fwd_tables, st.rev_tables,
                                   en)
        ok &= bool(jnp.array_equal(outs_T.times[1], out_s.times))

        # Hierarchical on a 2x4 mesh vs the stacked timed round, with the
        # compact-before-gather uplink stages on.
        n_pods, per = 2, 4
        intra, inter = full_route_enables(per), full_route_enables(n_pods)
        mesh2 = auto_mesh((n_pods, per), ("pod", "chip"))
        for caps in (dict(), dict(link_capacity=12, pod_capacity=40)):
            ic2 = StarInterconnect(mesh2, "chip", pod_axis="pod",
                                   capacity=32, timing=w, **caps)
            out_h, d_h = ic2.exchange_fn()(frames, st.fwd_tables,
                                           st.rev_tables, intra, inter)
            ref_h, dr_h = route_step_hierarchical(
                st, frames, 32, n_pods=n_pods, intra_enables=intra,
                inter_enables=inter, timing=w, **caps)
            ok &= bool(jnp.array_equal(out_h.times, ref_h.times))
            ok &= bool(jnp.array_equal(out_h.labels, ref_h.labels))
            ok &= bool(jnp.array_equal(d_h.congestion, dr_h.congestion))
            ok &= bool(jnp.array_equal(d_h.uplink, dr_h.uplink))
        print("TIMED_MATCH", ok)
    """)
    assert "TIMED_MATCH True" in out


def test_three_level_fabric_stacked_matches_shard_map():
    """The N-level fabric distributed (ISSUE 5): a 3-level plan on a nested
    (case, pod, chip) mesh — derived from the plan by
    ``parallel.sharding.fabric_mesh`` — is bit-exact with the stacked
    ``fabric_route_step``, cascaded uplink capacities and the timed lane
    included, and the scanned ``stream_fn`` agrees with the per-round
    exchange."""
    out = _run("""
        from repro.core import (FabricInterconnect, FabricSpec, LevelSpec,
                                compile_fabric, fabric_route_step,
                                identity_router, make_frame, timed_wire)
        from repro.parallel.sharding import fabric_mesh
        w = timed_wire()
        N = 8
        st = identity_router(N)
        key = jax.random.key(13)
        labels = jax.random.randint(key, (N, 16), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1), (N, 16)) < 0.6
        frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, 16)
        ok = True
        for caps, timing in (((None, None, None), None),
                             ((8, 12, 6), None), ((8, 12, 6), w)):
            plan = compile_fabric(FabricSpec(
                levels=(LevelSpec(2, link_capacity=caps[0]),
                        LevelSpec(2, link_capacity=caps[1]),
                        LevelSpec(2, link_capacity=caps[2], extension=True)),
                capacity=24))
            mesh = fabric_mesh(plan)
            ic = FabricInterconnect(mesh=mesh, plan=plan, timing=timing)
            out_f, d_f = ic.exchange_fn()(frames, st.fwd_tables,
                                          st.rev_tables)
            ref, d_r = fabric_route_step(st, frames, plan, timing=timing)
            ok &= bool(jnp.array_equal(out_f.labels, ref.labels))
            ok &= bool(jnp.array_equal(out_f.valid, ref.valid))
            ok &= bool(jnp.array_equal(out_f.times, ref.times))
            ok &= bool(jnp.array_equal(d_f.congestion, d_r.congestion))
            ok &= bool(jnp.array_equal(d_f.uplink, d_r.uplink))
        # Scanned stream == per-round exchange (last config's plan).
        frames_T = jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                           (3, *x.shape)),
                                frames)
        outs_T, drops_T = ic.stream_fn()(frames_T, st.fwd_tables,
                                         st.rev_tables)
        ok &= bool(jnp.array_equal(outs_T.times[1], out_f.times))
        ok &= bool(jnp.array_equal(outs_T.labels[2], out_f.labels))
        ok &= bool(jnp.array_equal(drops_T.uplink[0], d_f.uplink))
        print("FABRIC3_MATCH", ok)
    """)
    assert "FABRIC3_MATCH True" in out


def test_degraded_fabric_shard_map_matches_stacked():
    """Degraded-mesh parity (ISSUE 6): the shard_map'd exchange on a plan
    with a dead (detoured) uplink, a reroute-exhausted group, a dead
    downlink, and a dynamic health overlay is bit-exact with the stacked
    executor on every observable — labels, valid, timestamps, and all four
    drop fields (unroutable/rerouted attribution included)."""
    out = _run("""
        from repro.core import (FabricHealth, FabricInterconnect, FabricSpec,
                                LevelSpec, compile_fabric, degrade_spec,
                                fabric_route_step, identity_router,
                                make_frame, timed_wire)
        from repro.parallel.sharding import fabric_mesh
        w = timed_wire()
        spec = FabricSpec(levels=(LevelSpec(2), LevelSpec(2),
                                  LevelSpec(2, extension=True)), capacity=24)
        st = identity_router(8)
        key = jax.random.key(17)
        labels = jax.random.randint(key, (8, 12), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1), (8, 12)) < 0.6
        frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, 12)
        up = [None] * 3
        up[1] = jnp.array([True, False, True, True])
        overlay = FabricHealth(uplink=tuple(up), downlink=(None,) * 3)
        cases = [
            (compile_fabric(degrade_spec(spec, [(1, 0)])), None),   # detour
            (compile_fabric(degrade_spec(spec, [(1, 0), (1, 1)])),  # exhausted
             None),
            (compile_fabric(degrade_spec(spec, [(1, 2),             # mixed
                                                (0, 3, "downlink")])), None),
            (compile_fabric(spec), overlay),                        # dynamic
        ]
        ok = True
        for plan, health in cases:
            mesh = fabric_mesh(plan)
            ic = FabricInterconnect(mesh=mesh, plan=plan, timing=w,
                                    health=health)
            out_f, d_f = ic.exchange_fn()(frames, st.fwd_tables,
                                          st.rev_tables)
            ref, d_r = fabric_route_step(st, frames, plan, timing=w,
                                         health=health)
            ok &= bool(jnp.array_equal(out_f.labels, ref.labels))
            ok &= bool(jnp.array_equal(out_f.valid, ref.valid))
            ok &= bool(jnp.array_equal(out_f.times, ref.times))
            for fld in ("congestion", "uplink", "unroutable", "rerouted"):
                ok &= bool(jnp.array_equal(getattr(d_f, fld),
                                           getattr(d_r, fld)))
        print("DEGRADED_MATCH", ok)
    """)
    assert "DEGRADED_MATCH True" in out


def test_routed_fabric_shard_map_matches_stacked_and_gather():
    """Routed exchange mode (ISSUE 9): the ppermute edge schedule on the
    nested 2x2x2 mesh is bit-exact with both the stacked routed executor
    and the gather-mode shard_map round — cascaded caps, extension level
    and the timed lane included — and the scanned stream_fn agrees."""
    out = _run("""
        from repro.core import (FabricInterconnect, FabricSpec, LevelSpec,
                                compile_fabric, fabric_route_step,
                                identity_router, make_frame, timed_wire,
                                with_exchange_mode)
        from repro.parallel.sharding import fabric_mesh
        w = timed_wire()
        N = 8
        st = identity_router(N)
        key = jax.random.key(13)
        labels = jax.random.randint(key, (N, 16), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1), (N, 16)) < 0.6
        frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, 16)
        ok = True
        for caps, timing in (((None, None, None), None),
                             ((8, 12, 6), None), ((8, 12, 6), w)):
            plan = compile_fabric(FabricSpec(
                levels=(LevelSpec(2, link_capacity=caps[0]),
                        LevelSpec(2, link_capacity=caps[1]),
                        LevelSpec(2, link_capacity=caps[2], extension=True)),
                capacity=24, exchange_mode="routed"))
            mesh = fabric_mesh(plan)
            ic = FabricInterconnect(mesh=mesh, plan=plan, timing=timing)
            out_f, d_f = ic.exchange_fn()(frames, st.fwd_tables,
                                          st.rev_tables)
            ref, d_r = fabric_route_step(st, frames, plan, timing=timing)
            icg = FabricInterconnect(
                mesh=mesh, plan=with_exchange_mode(plan, "gather"),
                timing=timing)
            out_g, _ = icg.exchange_fn()(frames, st.fwd_tables,
                                         st.rev_tables)
            ok &= bool(jnp.array_equal(out_f.labels, ref.labels))
            ok &= bool(jnp.array_equal(out_f.valid, ref.valid))
            ok &= bool(jnp.array_equal(out_f.times, ref.times))
            ok &= bool(jnp.array_equal(out_f.labels, out_g.labels))
            ok &= bool(jnp.array_equal(out_f.valid, out_g.valid))
            for fld in ("congestion", "uplink", "unroutable", "rerouted"):
                ok &= bool(jnp.array_equal(getattr(d_f, fld),
                                           getattr(d_r, fld)))
        frames_T = jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                           (3, *x.shape)),
                                frames)
        outs_T, _ = ic.stream_fn()(frames_T, st.fwd_tables, st.rev_tables)
        ok &= bool(jnp.array_equal(outs_T.labels[1], out_f.labels))
        print("ROUTED_MATCH", ok)
    """)
    assert "ROUTED_MATCH True" in out


def test_routed_degraded_parity_and_zero_gathers_in_jaxpr():
    """Degraded detours under routed mode (dead uplink, exhausted group,
    mixed, dynamic health overlay) match the stacked executor on every
    observable; and the routed program's jaxpr carries ZERO all_gathers —
    every wire byte moves by ppermute."""
    out = _run("""
        from repro.core import (FabricHealth, FabricInterconnect, FabricSpec,
                                LevelSpec, compile_fabric, degrade_spec,
                                fabric_route_step, identity_router,
                                make_frame, timed_wire, with_exchange_mode)
        from repro.parallel.sharding import fabric_mesh
        from repro.analysis import jaxprlint
        w = timed_wire()
        spec = FabricSpec(levels=(LevelSpec(2), LevelSpec(2),
                                  LevelSpec(2, extension=True)), capacity=24)
        st = identity_router(8)
        key = jax.random.key(17)
        labels = jax.random.randint(key, (8, 12), 0, 2**15)
        valid = jax.random.uniform(jax.random.fold_in(key, 1), (8, 12)) < 0.6
        frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, 12)
        up = [None] * 3
        up[1] = jnp.array([True, False, True, True])
        overlay = FabricHealth(uplink=tuple(up), downlink=(None,) * 3)
        cases = [
            (compile_fabric(degrade_spec(spec, [(1, 0)])), None),
            (compile_fabric(degrade_spec(spec, [(1, 0), (1, 1)])), None),
            (compile_fabric(degrade_spec(spec, [(1, 2),
                                                (0, 3, "downlink")])), None),
            (compile_fabric(spec), overlay),
        ]
        ok = True
        for plan, health in cases:
            plan = with_exchange_mode(plan, "routed")
            mesh = fabric_mesh(plan)
            ic = FabricInterconnect(mesh=mesh, plan=plan, timing=w,
                                    health=health)
            out_f, d_f = ic.exchange_fn()(frames, st.fwd_tables,
                                          st.rev_tables)
            ref, d_r = fabric_route_step(st, frames, plan, timing=w,
                                         health=health)
            ok &= bool(jnp.array_equal(out_f.labels, ref.labels))
            ok &= bool(jnp.array_equal(out_f.valid, ref.valid))
            ok &= bool(jnp.array_equal(out_f.times, ref.times))
            for fld in ("congestion", "uplink", "unroutable", "rerouted"):
                ok &= bool(jnp.array_equal(getattr(d_f, fld),
                                           getattr(d_r, fld)))
        closed, _ = jaxprlint.trace_fabric_exchange(
            with_exchange_mode(compile_fabric(spec), "routed"), 12)
        names = [e.primitive.name
                 for e in jaxprlint.iter_eqns(closed.jaxpr)]
        print("GATHERS", names.count("all_gather"),
              "PPERMUTES", names.count("ppermute") > 0)
        print("ROUTED_DEGRADED_MATCH", ok)
    """)
    assert "ROUTED_DEGRADED_MATCH True" in out
    assert "GATHERS 0 PPERMUTES True" in out


def test_engine_batched_step_shards_over_slot_axis():
    """The emulation engine's window program distributed (ISSUE 10): tenant
    sessions are batch rows, and the exchange is vmapped over batch, so a
    shard_map of the masked, per-slot-plastic ``run_stream`` over the slot
    axis on 8 devices (1 session per device) is bit-exact with the
    single-device batched step — spikes, drops, final delay-line state and
    the per-slot evolved weights."""
    out = _run("""
        import numpy as np
        from repro.core.aggregator import identity_router
        from repro.snn import chip as chiplib
        from repro.snn import network as netlib
        from repro.snn import stream as stlib
        from repro.snn.plasticity import STDPConfig

        chip = chiplib.ChipConfig(n_neurons=16, n_rows=8)
        cfg = netlib.NetworkConfig(n_chips=3, capacity=12, chip=chip)
        params = netlib.init_feedforward(jax.random.PRNGKey(0), cfg)._replace(
            router=identity_router(cfg.n_chips))
        pcfg = STDPConfig()
        S, T = 8, 6
        state = netlib.init_state(cfg, S)
        plast = netlib.init_slot_plasticity(params, S)
        key = jax.random.key(1)
        drives = (jax.random.uniform(key, (T, cfg.n_chips, S, chip.n_rows))
                  < 0.4).astype(jnp.float32)
        # Unequal session lengths -> real per-slot masking in the shard.
        lengths = jnp.arange(S) % 4 + 3
        mask = jnp.arange(T)[:, None] < lengths[None, :]

        def step(st, pl, dr, mk):
            o = stlib.run_stream(params, st, dr, cfg, plasticity=pcfg,
                                 plasticity_state=pl, slot_mask=mk)
            return (o.spikes, o.dropped, o.state.inflight,
                    o.plasticity.weights)

        ref = step(state, plast, drives, mask)

        mesh = auto_mesh((8,), ("slot",))
        state_specs = netlib.NetworkState(chips=P(None, "slot"),
                                          inflight=P(None, None, "slot"))
        sharded = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(state_specs, P(None, "slot"), P(None, None, "slot"),
                      P(None, "slot")),
            out_specs=(P(None, None, "slot"), P(None, None, "slot"),
                       P(None, None, "slot"), P(None, "slot"))))
        got = sharded(state, plast, drives, mask)
        ok = all(bool(jnp.array_equal(g, r)) for g, r in zip(got, ref))
        print("ENGINE_SHARD_MATCH", ok)
    """)
    assert "ENGINE_SHARD_MATCH True" in out


def test_sharded_train_step_matches_single_device():
    """The FSDP×TP-sharded train loss equals the unsharded one."""
    out = _run("""
        import dataclasses
        from repro.configs import get_config, smoke_config
        from repro.models import model as M
        from repro.parallel import sharding as shardlib

        cfg = dataclasses.replace(smoke_config(get_config("qwen3-8b")),
                                  dtype="float32")
        params = M.init_params(jax.random.key(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 17), 1,
                                              cfg.vocab_size)}
        base, _ = M.train_loss(params, batch, cfg)

        mesh = auto_mesh((2, 4), ("data", "model"))
        pshard = shardlib.param_shardings(params, mesh)
        params_s = jax.device_put(params, pshard)
        batch_s = jax.device_put(batch, {"tokens": NamedSharding(
            mesh, P("data", None))})
        with mesh, shardlib.activation_shardings(mesh):
            loss_s, _ = jax.jit(lambda p, b: M.train_loss(p, b, cfg))(
                params_s, batch_s)
        print("DELTA", abs(float(base) - float(loss_s)))
    """)
    delta = float(out.split("DELTA")[1].strip())
    assert delta < 1e-4


def test_elastic_reshard_on_load():
    """A checkpoint written unsharded restores onto a 2×4 mesh."""
    out = _run("""
        import dataclasses, shutil
        from repro.configs import get_config, smoke_config
        from repro.models import model as M
        from repro.optim import adamw
        from repro.ckpt import checkpoint as ckpt
        from repro.runtime.elastic import resume_on_mesh

        cfg = smoke_config(get_config("smollm-135m"))
        params = M.init_params(jax.random.key(0), cfg)
        state = {"params": params, "opt": adamw.init(params)}
        shutil.rmtree("/tmp/repro_elastic_test", ignore_errors=True)
        ckpt.save("/tmp/repro_elastic_test", 3, state)

        mesh = auto_mesh((2, 4), ("data", "model"))
        restored, manifest = resume_on_mesh("/tmp/repro_elastic_test", state,
                                            mesh)
        leaf = jax.tree.leaves(restored["params"])[0]
        print("STEP", manifest["step"], "DEVICES",
              len(leaf.sharding.device_set))
    """)
    assert "STEP 3" in out
    assert "DEVICES 8" in out
