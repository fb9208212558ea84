"""The per-layer readings of the program's own scopes and spans.

``xspace.py`` is checked against TensorFlow's protobuf module (where it is
installed) and against ``jax.profiler.ProfileData`` on the stored chip
traces.  The scope arithmetic of ``layers.py`` is checked on them too:
``small_trace`` predates the programs' scopes, so every op reads
``unscoped``; ``small_trace_scoped`` is the same recording
(``record_trace.py``) of the program with its scopes.  The span arithmetic
is checked on hand-made spans and on the spans of the tiny engine cell,
driven on the CPU by the benchmark's own driving path under the profiler.
"""

import glob
import gzip
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace_layers  # noqa: E402
from bench.harness import layers, spec, xspace  # noqa: E402
from bench.harness import trace as tracelib  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data")
OLD = DATA / "small_trace.xplane.pb.gz"
SCOPED = DATA / "small_trace_scoped.xplane.pb.gz"
TRACES = [OLD, SCOPED]


def _unzip(path, tmp_path):
    out = tmp_path / path.name.removesuffix(".gz")
    with gzip.open(path, "rb") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_xspace_matches_tensorflow(path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with gzip.open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = xspace.read(str(path))
    assert [p.name for p in planes] == [p.name for p in space.planes]
    n = 0
    for ref, got in zip(space.planes, planes):
        names = {k: v.name for k, v in ref.stat_metadata.items()}

        def value(stat):
            kind = stat.WhichOneof("value")
            v = getattr(stat, kind) if kind else None
            return names.get(v, "") if kind == "ref_value" else v

        assert [ln.name for ln in got.lines] == [ln.name for ln in ref.lines]
        for line, gline in zip(ref.lines, got.lines):
            assert len(gline.events) == len(line.events)
            for ev, gev in zip(line.events, gline.events):
                md = ref.event_metadata[ev.metadata_id]
                stats = {names[s.metadata_id]: value(s) for s in md.stats}
                stats.update({names[s.metadata_id]: value(s)
                              for s in ev.stats})
                assert gev.name == md.name
                assert gev.stats == stats
                assert gev.start_ns == pytest.approx(
                    line.timestamp_ns + ev.offset_ps / 1000, abs=1e-6)
                assert gev.duration_ns == ev.duration_ps / 1000
                n += 1
    assert n > 1000


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_xspace_matches_profile_data(path, tmp_path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_unzip(path, tmp_path))
    planes = xspace.read(str(path))
    ref = {p.name: p for p in pd.planes}
    for plane in planes:
        if plane.name not in ref:
            continue
        for line, rline in zip(plane.lines, ref[plane.name].lines):
            assert line.name == rline.name
            for ev, rev in zip(line.events, rline.events):
                assert ev.name == rev.name
                assert ev.start_ns == pytest.approx(rev.start_ns, abs=1)
                assert ev.duration_ns == pytest.approx(rev.duration_ns,
                                                       abs=1)
                for k, v in rev.stats:
                    assert ev.stats[k] == v


def test_hlo_instructions_match_tensorflow():
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    (meta,) = [p for p in xspace.read(str(SCOPED))
               if p.name == "/host:metadata"]
    assert meta.metadata
    for module, stats in meta.metadata.items():
        ref = hlo_pb2.HloProto()
        ref.ParseFromString(stats["Hlo Proto"])
        names = {i.id: i.name for c in ref.hlo_module.computations
                 for i in c.instructions}
        got = xspace.hlo_instructions(stats["Hlo Proto"])
        assert len(got) == len(names)
        for c in ref.hlo_module.computations:
            for i in c.instructions:
                assert got[i.name] == xspace.Instruction(
                    i.metadata.op_name, [names[k] for k in i.operand_ids])


def test_read_by_takes_the_nearest_scoped_reader():
    ins = {"made": xspace.Instruction("", []),
           "copy": xspace.Instruction("", ["made"]),
           "loop": xspace.Instruction("jit(f)/while", ["made"]),
           "search": xspace.Instruction("jit(f)/vmap(egress)/gather",
                                        ["copy"]),
           "row": xspace.Instruction("jit(f)/ingress/add", ["search"])}
    users = {"made": ["copy", "loop"], "copy": ["search"],
             "search": ["row"]}
    assert layers.read_by(ins, users, "made") == "jit(f)/vmap(egress)/gather"
    assert layers.read_by(ins, users, "made", depth=1) == ""
    assert layers.read_by(ins, users, "row") == ""


@pytest.fixture(scope="module", params=TRACES, ids=lambda p: p.name)
def traced(request, tmp_path_factory):
    path = _unzip(request.param, tmp_path_factory.mktemp("trace"))
    summary = tracelib.load(path)
    scope_ns, level_ns = layers.scope_times(xspace.read(path),
                                            summary.window)
    return request.param, summary, scope_ns, level_ns


def test_scopes_add_up_to_the_programs_own_op_times(traced):
    """Every op of the window lies in the one ``jit_stream_call`` module,
    so the scopes and ``unscoped`` add up to the own op times that
    ``trace.load`` reduces independently."""
    path, summary, scope_ns, _ = traced
    assert list(scope_ns) == [m for m in summary.module_ns
                              if "jit_stream_call" in m]
    total = sum(v for parts in scope_ns.values() for v in parts.values())
    assert total == pytest.approx(sum(summary.op_ns.values()), abs=20)


def test_scoped_trace_names_each_layer(traced):
    path, summary, scope_ns, level_ns = traced
    (parts,) = scope_ns.values()
    if path == OLD:
        assert set(parts) == {layers.UNSCOPED}
        assert not level_ns
        return
    assert {"chip", "egress", "fabric", "ingress"} <= set(parts)
    assert "plasticity" not in parts and "slots" not in parts
    assert parts.get(layers.UNSCOPED, 0.0) < 0.05 * sum(parts.values())
    (fabric,) = level_ns.values()
    assert set(fabric) == {"leaf", "level0", "level1", "merge"}
    assert sum(fabric.values()) == pytest.approx(parts["fabric"], abs=20)
    # The Pallas pack kernel carries its own name and lies in the merge.
    assert all(k.startswith("spike_router_pack") for k in summary.kernel_ns)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(stream_call)/while/body/closed_call/vmap(vmap(jit(searchsorted)))"
     "/while", (layers.UNSCOPED, None)),
    ("jit(_step)/slots/dynamic_slice", ("slots", None)),
    ("jit(f)/while/body/closed_call/vmap(egress)/jit(searchsorted)/gather",
     ("egress", None)),
    ("jit(f)/while/body/closed_call/vmap(vmap(fabric))/level1/jit(sort)",
     ("fabric", "level1")),
    ("jit(f)/vmap(fabric)/merge/jit(fused_merge_pack)/spike_router_pack",
     ("fabric", "merge")),
    ("jit(f)/vmap(fabric)/gather", ("fabric", layers.FABRIC_OTHER)),
    ("jit(f)/vmap(fabric)/level0/vmap(egress)/x", ("fabric", "level0")),
    ("", (layers.UNSCOPED, None)),
])
def test_scope_of(tf_op, scope):
    assert layers.scope_of(tf_op) == scope


SPANS = [  # (name, start, end, stats): two steps of a host loop, in ns
    ("engine.step", 0, 100, {"live": 10, "slot_steps": 16, "finished": 1}),
    ("engine.upload", 0, 5, {"bytes": 1000}),
    ("engine.wait", 10, 60, {}),
    ("engine.account", 60, 80, {}),
    ("engine.readback", 62, 75, {"bytes": 2_000_000, "what": "raster"}),
    ("engine.finalize", 80, 95, {"session": 3}),
    ("engine.readback", 81, 90, {"bytes": 5_000_000,
                                 "what": "plasticity"}),
    ("engine.step", 200, 260, {"live": 6, "slot_steps": 16,
                               "finished": 0}),
    ("engine.account", 230, 250, {}),
    ("engine.readback", 231, 240, {"bytes": 2_000_000, "what": "raster"}),
    ("engine.admit", 270, 271, {"session": 7, "queued_ms": 0.5}),
]


def test_self_ns_subtracts_nested_spans():
    own = layers.self_ns(SPANS)
    assert own["engine.step"] == (100 - 5 - 50 - 20 - 15) + (60 - 20)
    assert own["engine.account"] == (20 - 13) + (20 - 9)
    assert own["engine.finalize"] == 15 - 9
    assert own["engine.readback"] == 13 + 9 + 9


def test_innermost_pieces_cover_the_spans():
    pieces = layers.innermost(SPANS)
    assert pieces[:4] == [(0, 5, "engine.upload"), (5, 10, "engine.step"),
                          (10, 60, "engine.wait"),
                          (60, 62, "engine.account")]
    assert (270, 271, "engine.admit") == pieces[-1]
    # Consecutive and disjoint, and no piece outside a span.
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(b - a for a, b, _ in pieces) == 100 + 60 + 1


class _Summary:
    def __init__(self, busy, spans, window=(0, 300)):
        self.window, self.busy, self.program_spans = window, busy, spans


def test_idle_is_split_by_the_innermost_span():
    s = _Summary(busy=[[12, 58], [205, 228]], spans=SPANS)
    idle = layers.idle_by_span(s)
    assert idle == {"engine.upload": 5, "engine.step": 5 + 5 + 5 + 2 + 10,
                    "engine.wait": 2 + 2, "engine.account": 2 + 5 + 1 + 10,
                    "engine.readback": 13 + 9 + 9,
                    "engine.finalize": 1 + 5, "engine.admit": 1,
                    layers.NO_SPAN: 100 + 10 + 29}
    assert sum(idle.values()) == 300 - (58 - 12) - (228 - 205)


def test_span_readers():
    ctx = {"summary": _Summary(busy=[], spans=SPANS), "program": "jit__step",
           "stats": {"emulated_steps": 16}}
    assert layers.readback_mb(ctx) == pytest.approx(9.0 / 2)
    assert layers.slot_occupancy(ctx) == pytest.approx(100 * 16 / 32)
    assert layers.span_ms_per_step(ctx, ("engine.readback",)) == \
        pytest.approx(31e-6 / 2)
    assert layers.span_ms_per_step(
        ctx, ("engine.account", "engine.finalize")) == pytest.approx(
            (18 + 6) * 1e-6 / 2)
    layers.note_idle_by_span(ctx)
    assert ctx["notes"][0].startswith("idle by engine span: host.other")
    # A summary without the program's spans or scopes reads nothing.
    bare = {"summary": object(), "program": "jit__step",
            "stats": {"emulated_steps": 16}}
    assert layers.readback_mb(bare) is None
    assert layers.scope_ms_per_step(bare, "chip") is None
    assert layers.unscoped_share(bare) is None


def test_scope_readers_on_the_scoped_trace(traced):
    path, summary, scope_ns, level_ns = traced
    summary.scope_ns, summary.level_ns = scope_ns, level_ns
    summary.program_spans = []
    ctx = {"summary": summary, "program": "jit_stream_call",
           "traffic": {"path": "stream"}, "stats": {"emulated_steps": 6}}
    got = trace_layers.layer_readings(ctx)
    program_ms = summary.modules_matching("jit_stream_call")[0] * 1e-6 / 6
    scoped = sum(v for k, v in got.items()
                 if k.endswith("_ms_per_step") and k != "stream."
                 "device_ms_per_step")
    assert scoped == pytest.approx(program_ms, rel=0.01)
    if path == SCOPED:
        assert got["stream.egress_ms_per_step"] > 0
        assert ctx["notes"][0].startswith("fabric split")
    else:
        assert got["stream.unscoped_share"] == 100.0


def test_engine_spans_of_the_tiny_cell(tmp_path):
    """The benchmark's engine driving path on the tiny cell under the
    profiler: the program's ``live`` counter equals the path's own count,
    and the readback bytes are those of the shapes."""
    import jax

    cfg = json.loads((DATA / "tiny_ext4case_96chip.json").read_text())
    traffic = json.loads((DATA / "tiny_engine_plastic.json").read_text())
    runner = spec.driver("engine")(cfg, traffic, 2**31 + 77)
    runner.setup()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            stats = runner.window(0.5)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    spans = layers.program_spans(xspace.read(path), (0, float("inf")))
    steps = [sp for sp in spans if sp[0] == layers.STEP_SPAN]
    assert len(steps) == stats["program_calls"]
    assert sum(sp[3]["live"] for sp in steps) == stats["live_row_steps"]
    slots, window = traffic["slots"], traffic["window"]
    n = runner.engine.cfg.n_chips
    neurons = runner.engine.cfg.chip.n_neurons
    rows = runner.engine.cfg.chip.n_rows
    raster = window * n * slots * (neurons + 4) * 4
    row = n * (rows + neurons + rows * neurons) * 4
    finished = sum(sp[3]["finished"] for sp in steps)
    ctx = {"summary": _Summary(busy=[], spans=spans)}
    assert layers.readback_mb(ctx) * len(steps) == pytest.approx(
        (len(steps) * raster + finished * row) * 1e-6)
    assert finished == stats["completed"] > 0
