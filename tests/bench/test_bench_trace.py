"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from harness import tracing  # noqa: E402
from harness.tracing import Event  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def op(start, dur, name="%fusion.3 = f32[8]{0} fusion()", plane=DEV):
    return Event(plane, tracing.OPS_LINE, name, start * MS, dur * MS)


def span(name, start, dur):
    return Event(HOST, "python", name, start * MS, dur * MS)


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    events = [span(tracing.WINDOW, 10, 100),
              op(0, 15),          # clipped to the window: 5 ms
              op(20, 10), op(25, 10),               # union 15 ms
              op(50, 5, "%tpu_custom_call.1 = f32[8,6]{1,0} custom-call()"),
              op(105, 20)]        # clipped: 5 ms
    t = tracing.reduce_trace(events, [])
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(0.030)
    assert t.idle_share == pytest.approx(0.7)
    assert t.kernel_s("tpu_custom_call") == pytest.approx(0.005)


def test_busy_is_averaged_over_chips():
    events = [span(tracing.WINDOW, 0, 100), op(0, 40),
              op(0, 20, plane="/device:TPU:1")]
    t = tracing.reduce_trace(events, [])
    assert t.chips == 2
    assert t.busy_s == pytest.approx(0.030)


def test_a_gap_is_labelled_by_the_innermost_open_span():
    events = [span(tracing.WINDOW, 0, 100),
              span("nsga2.generation", 0, 100),
              span("explore.evaluate", 40, 20),
              op(0, 10), op(50, 5), op(90, 10)]
    t = tracing.reduce_trace(events, ["nsga2.generation",
                                      "explore.evaluate"])
    gaps = dict(t.gaps)
    # 10..50 (midpoint 30) and 55..90 (midpoint 72.5) lie outside the
    # evaluation; no gap's midpoint lies inside it
    assert gaps == pytest.approx({"nsga2.generation": 0.075})


def test_a_kernel_is_found_by_its_kind_not_by_a_consumer_naming_it():
    kernel = "%tpu_custom_call.1 = f32[4096,6]{1,0} custom-call(s32[4096,1])"
    consumer = ("%copy.16 = f32[4096,6]{0,1} copy(f32[4096,6]{1,0} "
                "%tpu_custom_call.1)")
    t = tracing.reduce_trace([span(tracing.WINDOW, 0, 10), op(1, 2, kernel),
                              op(3, 1, consumer)], [])
    assert t.kernel_s("tpu_custom_call") == pytest.approx(0.002)
    assert dict(t.top_ops()) == pytest.approx(
        {"tpu_custom_call": 0.002, "copy": 0.001})


def test_a_trace_without_a_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce_trace([op(0, 1)], [])
    with pytest.raises(ValueError):
        tracing.reduce_trace([span(tracing.WINDOW, 0, 10)], [])


def test_recorded_v5e_trace():
    """A 12 ms slice of a traced resnet50 stream on a TPU v5 lite: one
    Pallas sweep-kernel call of 4096 configs inside a long dispatch."""
    doc = json.loads((pathlib.Path(__file__).parent / "data"
                      / "trace_v5e_stream.json").read_text())
    events = [Event(**e) for e in doc["events"]]
    t = tracing.reduce_trace(events, {"sweep.dispatch", "sweep.synthesize",
                                      "sweep.reduce", "sweep.pull"})
    ops = [e for e in events if e.plane == DEV and e.line == "XLA Ops"]
    union = tracing.merge(((e.start_ns, e.end_ns) for e in ops), 0, 12 * MS)
    assert t.window_s == pytest.approx(0.012)
    assert t.busy_s == pytest.approx(sum(b - a for a, b in union) * 1e-9)
    kernel = [e for e in ops if e.name.startswith("%tpu_custom_call")]
    assert len(kernel) == 1
    assert t.kernel_s("tpu_custom_call") == pytest.approx(
        kernel[0].dur_ns * 1e-9)
    assert t.top_ops()[0][0] == "tpu_custom_call"
    assert sum(s for _, s in t.gaps) == pytest.approx(t.window_s - t.busy_s)
    assert t.gaps[0][0] == "sweep.dispatch"


def test_self_time_subtracts_child_spans():
    spans = [dict(span_id=1, parent_id=None, name="nsga2.generation",
                  dur_s=1.0),
             dict(span_id=2, parent_id=1, name="explore.evaluate",
                  dur_s=0.25),
             dict(span_id=3, parent_id=None, name="nsga2.generation",
                  dur_s=0.5)]
    assert tracing.self_seconds(spans, "nsga2.generation") == [0.75, 0.5]
    assert tracing.mean_ms(spans, "nsga2.generation",
                           self_time=True) == pytest.approx(625.0)
    assert tracing.mean_ms(spans, "explore.evaluate") == pytest.approx(250.0)
    assert tracing.mean_ms(spans, "fleet.simulate") is None
