"""The reduction from a profiler trace to metrics, on a small trace whose
answers are worked out by hand (``fixtures/trace_small.json``).

Device 0's ops, in ns: fusion.1 [100, 300], all-reduce.1 [250, 450],
fusion.2 [500, 600], a while.7 [880, 1100] that holds copy.3 [900, 1100],
and fusion.9 after the window; device 1 runs one op over the whole window
[0, 1000]. Host spans: aggregate [50, 350] and [480, 620], upload
[440, 490].
The ``XLA Modules`` line is not an op line.
"""
import json
from pathlib import Path

import jax
import pytest

from chipbench.trace import Event, Trace, load_xplane, merge, op_name, subtract

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"


@pytest.fixture
def trace():
    events = [Event(*e) for e in json.loads(FIXTURE.read_text())["events"]]
    return Trace.from_events(events)


def test_window_comes_from_the_window_span(trace):
    assert trace.window == (0, 1000)
    assert trace.window_s == pytest.approx(1e-6)
    assert trace.devices == [0, 1]


def test_busy_is_the_union_of_ops_clipped_to_the_window(trace):
    # device 0: [100, 450] + [500, 600] + [880, 1000] = 570 ns; device 1: 1000
    assert trace.busy_s() == pytest.approx((570 + 1000) / 2 / 1e9)
    assert trace.idle_share(0) == pytest.approx(0.43)
    assert trace.idle_share(1) == pytest.approx(0.0)
    assert trace.idle_share(7) is None


def test_device_time_inside_host_spans(trace):
    # [100, 350] inside the first aggregate span, [500, 600] inside the second
    assert trace.device_time_in("aggregate", 0) == pytest.approx(350 / 1e9)
    assert trace.span_count("aggregate") == 2
    assert trace.device_time_in("fetch", 0) == 0.0


def test_breakdown_names_gaps_by_the_span_they_fall_in(trace):
    b = trace.breakdown(0)
    ops = dict(b["device_ops"])
    # own time inside the window: the while op's is [880, 900]
    assert ops == pytest.approx({"fusion.1": 200e-9, "all-reduce.1 f32[1024]": 200e-9,
                                 "fusion.2": 100e-9, "copy.3": 100e-9,
                                 "while.7": 20e-9})
    assert [n for n, _ in b["idle_gaps"]] == ["host", "aggregate", "upload"]
    assert [t for _, t in b["idle_gaps"]] == pytest.approx([280e-9, 100e-9, 50e-9])


def test_op_names_drop_the_hlo_text():
    assert op_name("%fusion.5 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %x)") == \
        "fusion.5 f32[8,128]"
    assert op_name("%copy-start = (f32[256]{0:T(256)S(1)}, u32[]) copy-start(%x)") == \
        "copy-start"
    assert op_name("fusion.1") == "fusion.1"


def test_interval_arithmetic():
    assert merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError, match="window"):
        Trace.from_events([Event("/host:CPU", "t", "step", 0, 1)])


def test_load_xplane_reads_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: x * 2)
    f(1.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("aggregate"):
            f(2.0).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    trace = Trace.from_events(load_xplane(str(path)))
    assert trace.span_count("aggregate") == 1
    assert trace.window_s > 0
    # the CPU has no TPU plane: the device metrics find nothing to read
    assert trace.devices == [] and trace.busy_s() is None
