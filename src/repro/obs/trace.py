"""Nestable span tracing with a bounded ring, Chrome-trace and JSONL export.

The exploration runtime's stage-level clock: a *span* is a named interval
with wall and CPU duration, structured attributes, and a parent — the
synthesis of chunk 17, generation 42 of an NSGA-II run, one checkpoint
save.  Spans land in a bounded in-memory ring (oldest evicted first) and,
when configured, are appended to a JSONL event log that survives
preemption alongside checkpoints (each line is a complete JSON object
flushed at span end, so a SIGKILL loses at most the spans still open).

Two recording APIs:

* ``with span("synthesize", chunk=i):`` — the common nested form; spans
  nest per thread, and each records its parent and depth.
* ``h = span_start("sweep_chunked")`` / ``span_end(h)`` — explicit
  start/stop for work whose begin and end live in different scopes
  (a whole stream, closed from its success or its error path).

**The disabled path is a no-op**: ``span()`` returns a shared singleton
context manager and ``span_start`` returns ``None`` — no allocation, no
clock reads — so instrumented hot loops cost nothing until
:func:`configure` turns tracing on (the ``telemetry-smoke`` CI job gates
the *enabled* overhead at <2% on a real sweep).

``configure(jax_annotations=True)`` additionally wraps every
context-manager span in ``jax.profiler.TraceAnnotation``, so the same
stage names show up inside XLA device profiles.  Every span also stamps
its start and end as ``t0_ns`` / ``t1_ns`` in ``time.time_ns()`` units,
the profiler's own clock: a span's ``t0_ns`` less the
``profile_start_time`` of the profile's ``Task Environment`` plane is
the ``start_ns`` of its mirrored xplane event, to a few microseconds
(the event opens just before the span's clock read).

Exports: :func:`export_chrome_trace` writes the standard
``{"traceEvents": [...]}`` Chrome ``trace_event`` document (loadable in
Perfetto / ``chrome://tracing``); :func:`load_jsonl` replays an event
log back into span dicts, tolerating the torn final line a SIGKILL can
leave.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time


class Span:
    """One closed (or still-open) traced interval."""

    __slots__ = ("span_id", "parent_id", "name", "t0_s", "dur_s",
                 "cpu_dur_s", "t0_ns", "t1_ns", "tid", "depth", "attrs",
                 "status", "_cpu0_s")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 t0_s: float, tid: int, depth: int, attrs: dict):
        # start and end on the profiler's clock: unix nanoseconds, the
        # clock jax.profiler stamps (profile_start_time + xplane start_ns)
        self.t0_ns = time.time_ns()
        self.t1_ns: int | None = None
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0_s = t0_s            # seconds since the tracer epoch
        self.dur_s: float | None = None
        self.cpu_dur_s: float | None = None
        self.tid = tid
        self.depth = depth
        self.attrs = attrs
        self.status = "ok"
        self._cpu0_s = time.process_time()

    def set(self, **attrs) -> None:
        """Attach/overwrite structured attributes while the span is open."""
        self.attrs.update(attrs)

    def as_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "t0_s": self.t0_s,
            "dur_s": self.dur_s,
            "cpu_dur_s": self.cpu_dur_s,
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "tid": self.tid,
            "depth": self.depth,
            "status": self.status,
            "attrs": self.attrs,
            "pid": os.getpid(),
        }


class _NoopSpan:
    """Shared do-nothing stand-in returned while tracing is disabled —
    supports the full ``Span`` surface so instrumented code never
    branches on the telemetry switch itself."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NOOP = _NoopSpan()


class _SpanCtx:
    """Context-manager wrapper that opens/closes one traced span (and,
    when configured, a ``jax.profiler.TraceAnnotation`` of the same
    name, opened just before the span's clock reads and closed just
    after, so its profiler event brackets the span)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_span", "_jax_ctx")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._span: Span | None = None
        self._jax_ctx = None

    def __enter__(self) -> Span:
        ann = _STATE["jax_annotation"]
        if ann is not None:
            try:
                self._jax_ctx = ann(self._name)
                self._jax_ctx.__enter__()
            except Exception:       # device profiler not active / usable
                self._jax_ctx = None
        self._span = self._tracer.start(self._name, self._attrs,
                                        on_stack=True)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        self._tracer.end(self._span,
                         status="error" if exc_type is not None else "ok",
                         pop_stack=True)
        if self._jax_ctx is not None:
            with contextlib.suppress(Exception):
                self._jax_ctx.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Bounded ring of spans plus the per-thread nesting stacks.

    ``ring_size`` bounds memory for marathon runs: the ring keeps the
    newest N *closed* spans (eviction counted in ``n_evicted``), while
    the JSONL log — when configured — keeps everything.
    """

    def __init__(self, ring_size: int = 65536):
        self.ring_size = int(ring_size)
        self._ring: list[Span] = []
        self._ring_pos = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self.epoch_s = time.perf_counter()
        self.epoch_unix_s = time.time()
        self.n_recorded = 0
        self.n_evicted = 0

    # -- per-thread nesting ------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self) -> Span | None:
        st = self._stack()
        return st[-1] if st else None

    # -- record ------------------------------------------------------------
    def start(self, name: str, attrs: dict, *,
              on_stack: bool = False) -> Span:
        st = self._stack()
        parent = st[-1] if st else None
        sp = Span(span_id=next(self._ids),
                  parent_id=parent.span_id if parent is not None else None,
                  name=name,
                  t0_s=time.perf_counter() - self.epoch_s,
                  tid=threading.get_ident(),
                  depth=len(st),
                  attrs=attrs)
        if on_stack:
            st.append(sp)
        return sp

    def end(self, sp: Span, *, status: str = "ok",
            pop_stack: bool = False) -> None:
        sp.dur_s = time.perf_counter() - self.epoch_s - sp.t0_s
        sp.t1_ns = time.time_ns()
        sp.cpu_dur_s = time.process_time() - sp._cpu0_s
        sp.status = status
        if pop_stack:
            st = self._stack()
            if st and st[-1] is sp:
                st.pop()
        with self._lock:
            if len(self._ring) < self.ring_size:
                self._ring.append(sp)
            else:
                self._ring[self._ring_pos] = sp
                self._ring_pos = (self._ring_pos + 1) % self.ring_size
                self.n_evicted += 1
            self.n_recorded += 1
        sink = _STATE["jsonl"]
        if sink is not None:
            _write_jsonl(sink, sp)

    # -- read --------------------------------------------------------------
    def spans(self, name: str | None = None) -> list[Span]:
        """Closed spans in end order (oldest surviving first)."""
        with self._lock:
            out = self._ring[self._ring_pos:] + self._ring[:self._ring_pos]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring = []
            self._ring_pos = 0
            self.n_recorded = 0
            self.n_evicted = 0


# ---------------------------------------------------------------------------
# Module state: one process tracer behind one enable switch
# ---------------------------------------------------------------------------

_TRACER = Tracer()
_STATE: dict = {
    "enabled": False,
    "jsonl": None,              # open file object (append mode) or None
    "jsonl_path": None,
    "jsonl_lock": threading.Lock(),
    "jax_annotation": None,     # jax.profiler.TraceAnnotation when wired
}


def _write_jsonl(sink, sp: Span) -> None:
    line = json.dumps(sp.as_dict(), separators=(",", ":"),
                      default=_json_default)
    with _STATE["jsonl_lock"]:
        sink.write(line + "\n")
        sink.flush()            # each closed span survives a later SIGKILL


def _json_default(o):
    # numpy scalars and other non-JSON attrs degrade to their repr rather
    # than poisoning the whole log line
    try:
        return o.item()
    except Exception:
        return repr(o)


def is_enabled() -> bool:
    return _STATE["enabled"]


def get_tracer() -> Tracer:
    """The process tracer (its ring fills only while tracing is enabled)."""
    return _TRACER


def configure(enabled: bool = True, *,
              jsonl_path=None,
              ring_size: int | None = None,
              jax_annotations: bool = False,
              reset: bool = False) -> None:
    """Flip the process-wide tracing switch.

    ``jsonl_path`` opens (append) a line-per-span event log flushed at
    every span end; ``ring_size`` rebuilds the in-memory ring with a new
    bound; ``jax_annotations`` mirrors every context-manager span into
    ``jax.profiler.TraceAnnotation`` so stages appear in XLA device
    profiles (silently skipped when jax is unavailable); ``reset`` clears
    the ring first.  Disabling closes the JSONL log.
    """
    if ring_size is not None:
        _TRACER.ring_size = int(ring_size)
        _TRACER.clear()
    elif reset:
        _TRACER.clear()
    if _STATE["jsonl"] is not None and (
            not enabled or jsonl_path is None
            or str(jsonl_path) != _STATE["jsonl_path"]):
        with contextlib.suppress(Exception):
            _STATE["jsonl"].close()
        _STATE["jsonl"] = None
        _STATE["jsonl_path"] = None
    if enabled and jsonl_path is not None and _STATE["jsonl"] is None:
        path = os.fspath(jsonl_path)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        _STATE["jsonl"] = open(path, "a", encoding="utf-8")
        _STATE["jsonl_path"] = path
    ann = None
    if enabled and jax_annotations:
        try:
            from jax.profiler import TraceAnnotation as ann
        except Exception:
            ann = None
    _STATE["jax_annotation"] = ann
    _STATE["enabled"] = bool(enabled)


def disable() -> None:
    """Turn tracing off and close the JSONL log (ring is kept)."""
    configure(enabled=False)


@contextlib.contextmanager
def configured(telemetry):
    """Scoped :func:`configure` for the facade's ``ExploreSpec(telemetry=...)``.

    ``None`` leaves the global switch untouched; ``True``/``False`` flip
    it for the duration; a dict is splatted into :func:`configure`
    (e.g. ``{"jsonl_path": ..., "jax_annotations": True}``).  The prior
    state is restored on exit, so one instrumented ``run()`` never leaks
    its telemetry setup into the next.
    """
    if telemetry is None:
        yield
        return
    prev = {"enabled": _STATE["enabled"],
            "jsonl_path": _STATE["jsonl_path"],
            "jax": _STATE["jax_annotation"] is not None}
    if isinstance(telemetry, dict):
        configure(**{"enabled": True, **telemetry})
    else:
        configure(enabled=bool(telemetry))
    try:
        yield
    finally:
        configure(enabled=prev["enabled"],
                  jsonl_path=prev["jsonl_path"],
                  jax_annotations=prev["jax"])


# ---------------------------------------------------------------------------
# Recording API used by instrumented code
# ---------------------------------------------------------------------------

def span(name: str, **attrs):
    """Context manager recording one nested span; a shared no-op while
    tracing is disabled (no allocation, no clock reads)."""
    if not _STATE["enabled"]:
        return _NOOP
    return _SpanCtx(_TRACER, name, attrs)


def span_start(name: str, **attrs) -> Span | None:
    """Open an *un-stacked* span for work that ends in another scope.
    Returns ``None`` while disabled — pass the handle straight to
    :func:`span_end`, which ignores ``None``."""
    if not _STATE["enabled"]:
        return None
    return _TRACER.start(name, attrs)


def span_end(handle: Span | None, *, status: str = "ok", **attrs) -> None:
    """Close a :func:`span_start` handle (no-op for ``None``)."""
    if handle is None:
        return
    if attrs:
        handle.attrs.update(attrs)
    _TRACER.end(handle, status=status)


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def export_chrome_trace(path=None, *, tracer: Tracer | None = None) -> dict:
    """Render the ring as a Chrome ``trace_event`` document.

    Complete spans become ``"ph": "X"`` duration events (microsecond
    timestamps relative to the tracer epoch, and each span's ``t0_ns``
    on the profiler's clock in its ``args``); thread ids are remapped to
    small ints in first-seen order so Perfetto's track names stay
    readable.  When ``path`` is given the document is also written there
    as JSON.  Loadable in ``chrome://tracing`` / https://ui.perfetto.dev.
    """
    tr = tracer if tracer is not None else _TRACER
    tid_map: dict[int, int] = {}
    events = []
    for sp in tr.spans():
        tid = tid_map.setdefault(sp.tid, len(tid_map))
        events.append({
            "name": sp.name,
            "cat": "repro",
            "ph": "X",
            "ts": sp.t0_s * 1e6,
            "dur": (sp.dur_s or 0.0) * 1e6,
            "pid": os.getpid(),
            "tid": tid,
            "args": dict(sp.attrs, span_id=sp.span_id,
                         parent_id=sp.parent_id, status=sp.status,
                         cpu_dur_s=sp.cpu_dur_s, t0_ns=sp.t0_ns),
        })
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "epoch_unix_s": tr.epoch_unix_s,
            "n_recorded": tr.n_recorded,
            "n_evicted": tr.n_evicted,
        },
    }
    if path is not None:
        with open(os.fspath(path), "w", encoding="utf-8") as fh:
            json.dump(doc, fh, default=_json_default)
    return doc


def load_jsonl(path) -> list[dict]:
    """Replay a JSONL event log into span dicts (end order).

    Tolerates the torn final line a SIGKILL can leave mid-write — every
    *complete* line is returned, a trailing partial one is dropped.
    """
    out: list[dict] = []
    with open(os.fspath(path), "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue            # torn tail from a kill mid-write
    return out


def validate_chrome_trace(doc: dict) -> list[str]:
    """Schema check for an exported (or re-loaded) Chrome trace document;
    returns a list of problems (empty = valid)."""
    problems: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document is not a dict with a 'traceEvents' key"]
    ev = doc["traceEvents"]
    if not isinstance(ev, list):
        return ["'traceEvents' is not a list"]
    for i, e in enumerate(ev):
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in e:
                problems.append(f"event {i} missing {k!r}")
        if e.get("ph") == "X" and "dur" not in e:
            problems.append(f"event {i} is 'X' but has no 'dur'")
        if not isinstance(e.get("ts", 0), (int, float)) \
                or e.get("ts", 0) < 0:
            problems.append(f"event {i} has non-numeric/negative ts")
        if e.get("ph") == "X" and (
                not isinstance(e.get("dur", 0), (int, float))
                or e.get("dur", 0) < 0):
            problems.append(f"event {i} has non-numeric/negative dur")
    return problems
