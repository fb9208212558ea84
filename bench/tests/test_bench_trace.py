"""The trace reduction on a small trace recorded on one TPU v5e
(``record_trace.py``: the tiny stream configuration, one chained call of 6
steps at batch 2, under the benchmark's own spans).

The expected numbers were worked out by hand from the profiler's JSON
export of the same trace (events in microseconds, on one clock): the
``bench.window`` span is 2,786.860 us long; the union of the ``XLA Ops``
intervals inside it is 642.407094 us; the one ``jit_stream_call`` module
event overlaps it by 642.414828 us; the six Pallas kernel events
(``custom_call_target="tpu_custom_call"``) last 36.118828 us together; and
the longest idle gap, 2,144.44525 us from the last op to the window's end,
lies mostly inside ``bench.stream.wait``.  The JSON keeps picoseconds and
``ProfileData`` whole nanoseconds, so the two agree to some nanoseconds.
"""

import gzip
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import trace  # noqa: E402

TRACE = pathlib.Path(__file__).with_name("data") / "small_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load(str(path))


def test_window_and_busy(summary):
    assert summary.n_devices == 1
    assert summary.window_ns == pytest.approx(2_786_860.0, abs=20)
    assert summary.busy_ns == pytest.approx(642_407.094, abs=20)


def test_program_and_kernel_time(summary):
    ns, calls = summary.modules_matching("jit_stream_call")
    assert calls == 1
    assert ns == pytest.approx(642_414.828, abs=20)
    assert summary.kernels_matching("") == pytest.approx(36_118.828, abs=20)
    assert all("fused_merge_pack" in k for k in summary.kernel_ns)


def test_spans_and_idle_gaps(summary):
    names = sorted(s[0] for s in summary.spans)
    assert names == ["bench.stream.call", "bench.stream.wait"]
    gaps = trace.idle_gaps(summary)
    assert gaps[0][0] == "bench.stream.wait"
    assert gaps[0][1] == pytest.approx(2_144_445.25, abs=20)
    assert sum(g for _, g in gaps) == pytest.approx(
        summary.window_ns - summary.busy_ns, abs=20)


def test_breakdown_shape(summary):
    b = trace.breakdown(summary)
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in b[key])
    own = [s for _, s in b["device_ops"]]
    assert own == sorted(own, reverse=True)
    # Own times never exceed the busy time they are part of.
    assert sum(summary.op_ns.values()) <= summary.busy_ns + 20


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def test_own_times_subtract_nested_ops():
    evs = [_Ev("%while.1 = (s32[]) while(...)", 0, 100),
           _Ev("%fusion.2 = s32[8]{0} fusion(...)", 10, 30),
           _Ev("%fusion.3 = s32[8]{0} fusion(...)", 50, 20),
           _Ev("%copy.4 = f32[2,3]{1,0} copy(...)", 120, 10)]
    own = {n: ns for n, ns, _ in trace.own_times(evs, 0, 125)}
    assert own == {"while.1 (s32[])": 50, "fusion.2 s32[8]": 30,
                   "fusion.3 s32[8]": 20, "copy.4 f32[2,3]": 5}
    assert trace.merge_intervals([(0, 5), (3, 8), (10, 12)]) == [[0, 8],
                                                                 [10, 12]]
