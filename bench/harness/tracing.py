"""The profiler trace of the measured window, and its reduction.

A traced run records the program's own ``repro.obs`` spans in memory and
mirrors them into ``jax.profiler.TraceAnnotation`` so that they share the
device's clock.  The reduction works on plain event records, so a small
recorded trace checks it without a chip:

* the window is the host annotation ``bench.window``;
* device operations are the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane; busy time is the union of their intervals
  inside the window, averaged over the chips;
* a kernel's time is the summed duration of its operations, found by
  their kind (``%tpu_custom_call.1 = ...`` is ``tpu_custom_call``);
* an idle gap is a stretch of the window with no operation on the
  device, labelled by the innermost program span open on the host at
  its midpoint.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import tempfile

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> list[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend(Event(plane.name, line.name, e.name,
                             float(e.start_ns), float(e.duration_ns))
                       for e in line.events)
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def window_of(events: list[Event]) -> tuple[float, float]:
    wins = [e for e in events if e.name == WINDOW
            and not is_device(e.plane)]
    if len(wins) != 1:
        raise ValueError(f"the trace holds {len(wins)} {WINDOW!r} events, "
                         f"expected one")
    return wins[0].start_ns, wins[0].end_ns


def merge(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of intervals, clipped to ``[t0, t1]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_kind(name: str) -> str:
    """``%copy.12 = f32[...] copy(...)`` -> ``copy``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass
class DeviceTrace:
    """The reduced trace of one window."""

    window_s: float
    busy_s: float                   # averaged over the chips
    chips: int
    ops: list[Event]                # device operations inside the window
    gaps: list[tuple[str, float]]   # (label, seconds), longest total first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, kind: str) -> float:
        """Device seconds of the operations of one kind (see
        :func:`op_kind`), summed over the chips."""
        return sum(e.dur_ns for e in self.ops
                   if op_kind(e.name) == kind) * 1e-9

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        tot: dict[str, float] = collections.defaultdict(float)
        for e in self.ops:
            tot[op_kind(e.name)] += e.dur_ns * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def reduce_trace(events: list[Event], span_names) -> DeviceTrace:
    """Busy time, device operations and labelled idle gaps of the window;
    ``span_names`` are the program's span names that label the gaps."""
    t0, t1 = window_of(events)
    by_chip: dict[str, list[Event]] = collections.defaultdict(list)
    for e in events:
        if is_device(e.plane) and e.line == OPS_LINE \
                and e.end_ns > t0 and e.start_ns < t1:
            by_chip[e.plane].append(e)
    if not by_chip:
        raise ValueError("the trace holds no device operation inside the "
                         "window")
    busy = {p: merge(((e.start_ns, e.end_ns) for e in evs), t0, t1)
            for p, evs in by_chip.items()}
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy) * 1e-9
    names = set(span_names)
    host = [e for e in events if not is_device(e.plane)
            and e.name in names and e.end_ns > t0 and e.start_ns < t1]
    first = busy[min(busy)]
    edges = [t0] + [x for iv in first for x in iv] + [t1]
    gaps: dict[str, float] = collections.defaultdict(float)
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        open_ = [h for h in host if h.start_ns <= mid < h.end_ns]
        label = max(open_, key=lambda h: h.start_ns).name if open_ \
            else "(no program span)"
        gaps[label] += (e - s) * 1e-9
    ops = [e for evs in by_chip.values() for e in evs]
    return DeviceTrace(window_s=(t1 - t0) * 1e-9, busy_s=busy_s,
                       chips=len(busy), ops=ops,
                       gaps=sorted(gaps.items(), key=lambda kv: -kv[1]))


class Capture:
    """Trace the ``with`` block when ``enabled``: the program's spans in
    memory and on the profiler's clock, the device in the profiler.
    After the block, ``spans`` holds the program's closed spans (dicts)
    and ``trace`` the reduced :class:`DeviceTrace`."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.spans: list[dict] = []
        self.trace: DeviceTrace | None = None

    def __enter__(self):
        if not self.enabled:
            return self
        import jax
        from repro.obs import trace as obs_trace
        self._dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        obs_trace.configure(enabled=True, jax_annotations=True, reset=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir.name, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        import jax
        from repro.obs import trace as obs_trace
        try:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            obs_trace.disable()
            self.spans = [s.as_dict() for s in obs_trace.get_tracer().spans()]
            if exc[0] is None:
                path = sorted(glob.glob(os.path.join(
                    self._dir.name, "**", "*.xplane.pb"), recursive=True))
                self.trace = reduce_trace(
                    load_events(path[-1]),
                    {s["name"] for s in self.spans})
        finally:
            self._dir.cleanup()
        return False


def self_seconds(spans: list[dict], name: str) -> list[float]:
    """Each ``name`` span's duration less the part its child spans
    cover (children of one span do not overlap: they nest per thread)."""
    child: dict[int, float] = collections.defaultdict(float)
    for s in spans:
        if s["parent_id"] is not None:
            child[s["parent_id"]] += s["dur_s"]
    return [s["dur_s"] - child[s["span_id"]] for s in spans
            if s["name"] == name]


@dataclasses.dataclass(frozen=True)
class Readout:
    """What a per-layer metric's reader reads: the cell's name, the
    program's spans of the window, the reduced device trace, the logical
    shape ``(n, l, w, mixed)`` of every sweep-kernel call, the device's
    published peaks, and the networks (``spec.Network``) whose layers
    every call holds, whose layer models count its work (None: every
    layer is ``row_stationary``)."""

    cell: str
    spans: list
    trace: DeviceTrace
    peaks: dict
    calls: list
    networks: tuple | None = None


def mean_ms(spans: list[dict], name: str, self_time: bool = False):
    """Mean duration (or self time) of the ``name`` spans in ms; None
    when the window recorded none."""
    xs = self_seconds(spans, name) if self_time else \
        [s["dur_s"] for s in spans if s["name"] == name]
    return 1e3 * sum(xs) / len(xs) if xs else None
