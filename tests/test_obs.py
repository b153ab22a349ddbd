"""Unified telemetry layer (`repro.obs`): span tracing, metrics registry,
exporters, and the no-behavior-change contract.

Layers under test:

* `trace` — nesting/ordering invariants, the async start/stop handle
  path, Chrome trace_event schema validity, JSONL round-trip including
  the torn-final-line tolerance a SIGKILL leaves, and the configure /
  configured scoping (the disabled path returns shared no-op objects).
* `metrics` — counter/gauge/histogram semantics and the flat snapshot.
* instrumentation — enabling telemetry changes **nothing**: chunked-sweep
  fronts and synthesis-cache accounting are bit-identical with tracing
  on and off (both backends), Evaluator stats attribute per search via
  `reset_stats`, and a failed sweep attempt still flushes `wall_s` and
  the registry totals (the satellite bugfixes of ISSUE 8).
"""

import json
import time

import numpy as np
import pytest

from repro import obs
from repro.core.accelerator import design_space_soa
from repro.core.dse import ExploreSpec, run
from repro.core.dse_batch import _sweep_chunked
from repro.core.synthesis import PersistentSynthesisCache
from repro.core.workloads import get_workload

CHUNK = 16
GRID = dict(glb_kbs=(64, 256), bws=(8.0, 16.0, 32.0, 64.0))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with tracing off and a fresh ring +
    registry — telemetry state is process-global."""
    obs.disable()
    obs.configure(enabled=False, reset=True)
    obs.reset_metrics()
    yield
    obs.disable()
    obs.configure(enabled=False, reset=True)
    obs.reset_metrics()


def _space():
    return design_space_soa(chunk_size=CHUNK, **GRID)


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    obs.configure(enabled=True)
    with obs.span("outer", a=1) as outer:
        with obs.span("inner"):
            pass
        with obs.span("inner2") as sp:
            sp.set(extra="x")
    spans = obs.get_tracer().spans()
    by_name = {s.name: s for s in spans}
    assert set(by_name) == {"outer", "inner", "inner2"}
    # children closed before the parent, parent/depth recorded
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]
    assert by_name["outer"].parent_id is None
    assert by_name["outer"].depth == 0
    for child in ("inner", "inner2"):
        assert by_name[child].parent_id == by_name["outer"].span_id
        assert by_name[child].depth == 1
    assert by_name["inner2"].attrs["extra"] == "x"
    assert by_name["outer"].attrs["a"] == 1
    # durations are non-negative and children start within the parent
    for s in spans:
        assert s.dur_s >= 0.0
        assert s.cpu_dur_s >= 0.0
    assert by_name["inner"].t0_s >= by_name["outer"].t0_s


def test_span_status_on_exception():
    obs.configure(enabled=True)
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (sp,) = obs.get_tracer().spans("boom")
    assert sp.status == "error"


def test_async_start_end_handles():
    obs.configure(enabled=True)
    h1 = obs.span_start("kernel", chunk=0)
    h2 = obs.span_start("kernel", chunk=1)
    obs.span_end(h2, status="ok", n=5)
    obs.span_end(h1)
    spans = obs.get_tracer().spans("kernel")
    assert [s.attrs["chunk"] for s in spans] == [1, 0]   # end order
    assert spans[0].attrs["n"] == 5
    # async spans are not pushed on the nesting stack
    assert all(s.depth == 0 for s in spans)


def test_disabled_path_is_noop():
    assert not obs.is_enabled()
    a = obs.span("x")
    b = obs.span("y", attr=1)
    assert a is b                      # shared singleton, no allocation
    with a as sp:
        sp.set(ignored=True)           # full Span surface, does nothing
    assert obs.span_start("x") is None
    obs.span_end(None)                 # ignores the disabled handle
    assert obs.get_tracer().spans() == []


def test_ring_bound_evicts_oldest():
    obs.configure(enabled=True, ring_size=4)
    for i in range(10):
        with obs.span("s", i=i):
            pass
    tr = obs.get_tracer()
    spans = tr.spans()
    assert len(spans) == 4
    assert [s.attrs["i"] for s in spans] == [6, 7, 8, 9]
    assert tr.n_recorded == 10 and tr.n_evicted == 6
    obs.configure(enabled=False, ring_size=65536)


def test_spans_stamp_the_profiler_clock():
    """Stacked spans and span_start/span_end handles both carry t0_ns /
    t1_ns in time.time_ns() units, bracketing their wall duration."""
    obs.configure(enabled=True)
    lo = time.time_ns()
    with obs.span("stacked"):
        h = obs.span_start("handle")
        time.sleep(0.002)
        obs.span_end(h)
    hi = time.time_ns()
    for sp in obs.get_tracer().spans():
        d = sp.as_dict()
        assert lo <= d["t0_ns"] <= d["t1_ns"] <= hi
        assert abs((d["t1_ns"] - d["t0_ns"]) * 1e-9 - d["dur_s"]) < 1e-3
    doc = obs.export_chrome_trace()
    assert all(e["args"]["t0_ns"] >= lo for e in doc["traceEvents"])


def test_span_t0_ns_matches_its_profiler_event(tmp_path):
    """A span's t0_ns, less the profile's start time, is the start of
    its mirrored TraceAnnotation event in the xplane."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    obs.configure(enabled=True, jax_annotations=True, reset=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(4):
            with obs.span(f"clock.{i}"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    path, = tmp_path.rglob("*.xplane.pb")
    planes = list(ProfileData.from_file(str(path)).planes)
    start_ns = dict(next(p for p in planes
                         if p.name == "Task Environment").stats)[
        "profile_start_time"]
    events = {e.name: e.start_ns for p in planes for ln in p.lines
              for e in ln.events if e.name.startswith("clock.")}
    spans = obs.get_tracer().spans()
    assert len(spans) == 4 and set(events) == {s.name for s in spans}
    for sp in spans:
        assert abs(start_ns + events[sp.name] - sp.t0_ns) < 1e6


def test_configured_scoping_restores_prior_state(tmp_path):
    with obs.configured(None):
        assert not obs.is_enabled()    # None leaves the switch alone
    with obs.configured(True):
        assert obs.is_enabled()
    assert not obs.is_enabled()
    with obs.configured({"jsonl_path": tmp_path / "t.jsonl"}):
        assert obs.is_enabled()
        with obs.span("inside"):
            pass
    assert not obs.is_enabled()
    assert len(obs.load_jsonl(tmp_path / "t.jsonl")) == 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_content(tmp_path):
    obs.configure(enabled=True)
    with obs.span("parent", k="v"):
        with obs.span("child"):
            pass
    path = tmp_path / "trace.json"
    doc = obs.export_chrome_trace(path)
    assert obs.validate_chrome_trace(doc) == []
    reloaded = json.loads(path.read_text())
    assert obs.validate_chrome_trace(reloaded) == []
    events = {e["name"]: e for e in reloaded["traceEvents"]}
    assert set(events) == {"parent", "child"}
    for e in events.values():
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert events["parent"]["args"]["k"] == "v"
    assert (events["child"]["args"]["parent_id"]
            == events["parent"]["args"]["span_id"])
    # child nests inside the parent on the trace timeline
    assert events["child"]["ts"] >= events["parent"]["ts"]
    assert (events["child"]["ts"] + events["child"]["dur"]
            <= events["parent"]["ts"] + events["parent"]["dur"] + 1e-3)


def test_validate_chrome_trace_flags_problems():
    assert obs.validate_chrome_trace({}) != []
    assert obs.validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": -1.0,
                            "pid": 1, "tid": 0}]}
    problems = obs.validate_chrome_trace(bad)
    assert any("dur" in p for p in problems)
    assert any("negative" in p for p in problems)


def test_jsonl_roundtrip_and_truncation_tolerance(tmp_path):
    path = tmp_path / "events.jsonl"
    obs.configure(enabled=True, jsonl_path=path)
    for i in range(3):
        with obs.span("chunk", i=i):
            pass
    obs.disable()
    rows = obs.load_jsonl(path)
    assert [r["attrs"]["i"] for r in rows] == [0, 1, 2]
    assert all(r["name"] == "chunk" and r["dur_s"] >= 0 for r in rows)
    # a SIGKILL mid-write leaves a torn final line: replay drops it only
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"name": "torn", "attrs": {"i": 3')
    rows2 = obs.load_jsonl(path)
    assert [r["attrs"]["i"] for r in rows2] == [0, 1, 2]


def test_jsonl_nonserializable_attrs_degrade(tmp_path):
    path = tmp_path / "events.jsonl"
    obs.configure(enabled=True, jsonl_path=path)
    with obs.span("np_attrs", n=np.int64(7), f=np.float64(0.5),
                  arr=np.arange(2)):
        pass
    obs.disable()
    (row,) = obs.load_jsonl(path)
    assert row["attrs"]["n"] == 7
    assert row["attrs"]["f"] == 0.5      # numpy scalars -> JSON numbers


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_instruments_and_snapshot():
    reg = obs.get_registry()
    reg.inc("a.count")
    reg.inc("a.count", 4)
    reg.set("a.gauge", 2.5)
    for v in (1.0, 3.0):
        reg.observe("a.hist", v)
    snap = obs.snapshot()
    assert snap["a.count"] == 5
    assert snap["a.gauge"] == 2.5
    assert snap["a.hist.count"] == 2
    assert snap["a.hist.sum"] == 4.0
    assert snap["a.hist.min"] == 1.0
    assert snap["a.hist.max"] == 3.0
    assert snap["a.hist.mean"] == 2.0
    assert list(snap) == sorted(snap)
    json.dumps(snap)                     # provenance-block serializable
    # get-or-create returns the same instrument
    assert reg.counter("a.count") is reg.counter("a.count")
    obs.reset_metrics()
    assert obs.snapshot() == {}


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def test_summarize_and_render():
    obs.configure(enabled=True)
    with obs.span("sweep.synthesize"):
        pass
    reg = obs.get_registry()
    reg.inc("synth_cache.hits", 30)
    reg.inc("synth_cache.misses", 10)
    reg.inc("sweep.configs", 1000)
    reg.inc("sweep.wall_s", 2.0)
    reg.inc("explore.requested_evals", 50)
    reg.inc("explore.eval_seconds", 0.5)
    s = obs.summarize()
    assert s["spans"]["sweep.synthesize"]["count"] == 1
    assert s["derived"]["synth_cache_hit_rate"] == pytest.approx(0.75)
    assert s["derived"]["sweep_configs_per_s"] == pytest.approx(500.0)
    assert s["derived"]["explore_evals_per_s"] == pytest.approx(100.0)
    text = obs.render_text(s)
    assert "sweep.synthesize" in text
    assert "synth_cache_hit_rate" in text


# ---------------------------------------------------------------------------
# instrumentation: no behavior change, consistent totals
# ---------------------------------------------------------------------------

def _sweep_once(backend: str):
    cache = PersistentSynthesisCache()
    res = _sweep_chunked(get_workload("vgg16"), _space(),
                         backend=backend, chunk_size=CHUNK, cache=cache,
                         save_cache=False)
    return res, {"hits": cache.hits, "misses": cache.misses}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_bit_identity_telemetry_on_vs_off(backend, jax_usable):
    if backend == "jax" and not jax_usable:
        pytest.skip("jax unusable on this host")
    ref, ref_acct = _sweep_once(backend)
    obs.configure(enabled=True, reset=True)
    try:
        on, on_acct = _sweep_once(backend)
    finally:
        obs.disable()
    assert on_acct == ref_acct
    assert on.n_configs == ref.n_configs
    assert on.n_chunks == ref.n_chunks
    for m in ref.front_metrics:
        assert np.array_equal(on.front_metrics[m], ref.front_metrics[m])
    for k in ref.front_soa:
        assert np.array_equal(on.front_soa[k], ref.front_soa[k])
    # the instrumented run actually recorded the stage spans
    names = {s.name for s in obs.get_tracer().spans()}
    assert {"sweep_chunked", "sweep.synthesize", "synth.digest",
            "synth.lookup", "synth.model", "synth.insert", "kernel.wait",
            "sweep.reduce"} <= names


def test_sweep_metrics_always_on():
    res, acct = _sweep_once("numpy")
    snap = obs.snapshot()
    assert snap["sweep.chunks"] == res.n_chunks
    assert snap["sweep.configs"] == res.n_configs
    assert snap["sweep.wall_s"] == pytest.approx(res.timings["wall_s"])
    assert snap["synth_cache.hits"] == acct["hits"]
    assert snap["synth_cache.misses"] == acct["misses"]
    assert obs.get_tracer().spans() == []      # tracing stayed off


def test_synth_cache_evictions_counter():
    """The registry counts the rows each compaction drops, as the cache
    itself does."""
    cache = PersistentSynthesisCache(max_rows=24)
    _sweep_chunked(get_workload("vgg16"), _space(), backend="numpy",
                   chunk_size=CHUNK, cache=cache, save_cache=False)
    assert cache.evictions > 0
    assert obs.snapshot()["synth_cache.evictions"] == cache.evictions


def test_wall_s_flushed_on_injected_failure():
    """Satellite bugfix: a failed attempt still reports its wall time —
    both into the (discarded) timings dict and the metrics registry —
    and resumed runs report consistent totals."""
    from repro.runtime.fault_tolerance import InjectedFailure
    wl = get_workload("vgg16")
    with pytest.raises(InjectedFailure):
        _sweep_chunked(wl, _space(), backend="numpy", chunk_size=CHUNK,
                       fail_at={2: 1})
    snap = obs.snapshot()
    assert snap["sweep.failures"] == 1
    assert snap["sweep.wall_s"] > 0.0
    assert snap["sweep.chunks"] == 2           # chunks 0..1 before the boom


def test_resumed_run_totals_consistent(tmp_path):
    """Across restarts the registry counts work actually performed:
    chunks replayed from a snapshot are not re-counted, while the
    in-flight chunk the failed attempt synthesized but never
    checkpointed *is* (it genuinely runs twice — that is the cost of
    the preemption)."""
    from repro.runtime.dse_checkpoint import resume_sweep
    wl = get_workload("vgg16")
    ref = _sweep_chunked(wl, _space(), backend="numpy", chunk_size=CHUNK)
    obs.reset_metrics()
    res = resume_sweep(wl, _space, checkpoint_dir=str(tmp_path),
                       checkpoint_every=1, chunk_size=CHUNK,
                       backend="numpy", fail_at={2: 1})
    assert res.timings["restarts"] == 1
    snap = obs.snapshot()
    assert snap["sweep.restarts"] == 1
    assert snap["sweep.failures"] == 1
    assert snap["checkpoint.saves"] >= 2
    assert snap["checkpoint.restores"] >= 1
    # every chunk counted at least once, and the redo is bounded by the
    # pipeline depth (at most one dispatched-but-undrained chunk)
    assert ref.n_chunks <= snap["sweep.chunks"] <= ref.n_chunks + 1
    assert (ref.n_configs <= snap["sweep.configs"]
            <= ref.n_configs + CHUNK)
    # the result itself reports the de-duplicated totals
    assert res.n_chunks == ref.n_chunks
    assert res.n_configs == ref.n_configs


def test_root_span_error_status_on_failure():
    from repro.runtime.fault_tolerance import InjectedFailure
    obs.configure(enabled=True, reset=True)
    try:
        with pytest.raises(InjectedFailure):
            _sweep_chunked(get_workload("vgg16"), _space(),
                           backend="numpy", chunk_size=CHUNK,
                           fail_at={1: 1})
    finally:
        obs.disable()
    (root,) = obs.get_tracer().spans("sweep_chunked")
    assert root.status == "error"
    assert root.attrs["wall_s"] > 0.0


def test_evaluator_reset_stats():
    """Satellite bugfix: eval counters can be reset so a reused evaluator
    attributes stats per search instead of accumulating forever."""
    from repro.explore.search import Evaluator
    from repro.explore.space import space_for_workload
    space = space_for_workload("vgg16")
    ev = Evaluator(space, "vgg16", backend="numpy")
    rng = np.random.default_rng(0)
    g = space.random_population(8, rng)
    ev.evaluate(g)
    first = ev.stats()
    assert first["requested_evals"] == 8
    assert first["eval_seconds"] > 0.0
    ev.reset_stats()
    zeroed = ev.stats()
    assert zeroed["requested_evals"] == 0
    assert zeroed["kernel_evals"] == 0
    assert zeroed["memo_hits"] == 0
    assert zeroed["eval_seconds"] == 0.0
    # the memo survives the reset: re-evaluating the same genomes is all
    # memo hits, and the rows are identical
    F1 = ev.evaluate(g)
    assert ev.stats()["memo_hits"] == 8
    assert ev.stats()["kernel_evals"] == 0
    ev2 = Evaluator(space, "vgg16", backend="numpy")
    assert np.array_equal(F1, ev2.evaluate(g))
    # registry mirror counted both rounds
    snap = obs.snapshot()
    assert snap["explore.requested_evals"] == 24
    assert snap["explore.memo_hits"] == 8


def test_explore_spec_telemetry_field(tmp_path):
    with pytest.raises(ValueError, match="telemetry"):
        ExploreSpec.single("vgg16", chunk_size=None, telemetry="yes")
    spec = ExploreSpec.mixed("vgg16", method="random", budget=8,
                             seed=3, backend="numpy",
                             telemetry={"jsonl_path":
                                        tmp_path / "run.jsonl"})
    res = run(spec)
    assert not obs.is_enabled()            # scoped to the run
    rows = obs.load_jsonl(tmp_path / "run.jsonl")
    assert any(r["name"] == "explore.evaluate" for r in rows)
    assert res.stats["eval_seconds"] > 0.0
    # telemetry=None (default) leaves the global switch untouched and
    # changes nothing about the result
    res2 = run(ExploreSpec.mixed("vgg16", method="random", budget=8,
                                 seed=3, backend="numpy"))
    assert np.array_equal(res.genomes, res2.genomes)
    assert np.array_equal(res.front_objectives, res2.front_objectives)


# ---------------------------------------------------------------------------
# spans where the work happens: synthesis, the kernel call, nsga2
# ---------------------------------------------------------------------------

SYNTH_PHASES = ("synth.digest", "synth.lookup", "synth.model",
                "synth.insert")


def _tree(spans):
    """(span dicts by id, children dicts by parent id); checks that no
    span's children outlast it."""
    by_id = {s["span_id"]: s for s in spans}
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    for pid, cs in kids.items():
        if pid is not None:
            assert sum(c["dur_s"] for c in cs) <= by_id[pid]["dur_s"]
    return by_id, kids


def _names_under(kids, parent) -> list[str]:
    return [c["name"] for c in kids.get(parent["span_id"], [])]


def _traced(fn):
    obs.configure(enabled=True, reset=True)
    try:
        out = fn()
    finally:
        obs.disable()
    return out, [s.as_dict() for s in obs.get_tracer().spans()]


def _pallas_feed():
    from repro.core.accelerator import AcceleratorConfig
    from repro.core.pe import PEType
    rng = np.random.default_rng(5)
    types = tuple(PEType)
    return [AcceleratorConfig(
        pe_type=types[int(rng.integers(len(types)))],
        pe_rows=int(rng.integers(4, 33)), pe_cols=int(rng.integers(4, 33)),
        glb_kb=int(rng.choice([64, 128, 256, 512])),
        dram_bw_gbps=float(rng.choice([6.4, 12.8, 25.6])))
        for _ in range(36)]


@pytest.mark.parametrize("path", ["numpy", "pallas-interpret"])
def test_synthesis_phases_nest_under_sweep_synthesize(path, jax_usable):
    if path != "numpy" and not jax_usable:
        pytest.skip("jax unusable on this host")
    wl = get_workload("vgg16")
    if path == "numpy":
        res, spans = _traced(lambda: _sweep_chunked(
            wl, _space(), backend="numpy", chunk_size=CHUNK,
            cache=PersistentSynthesisCache(), save_cache=False))
    else:
        res, spans = _traced(lambda: _sweep_chunked(
            wl, [_pallas_feed()], backend="jax", use_pallas=True,
            chunk_size=16, cache=PersistentSynthesisCache(),
            save_cache=False))
    by_id, kids = _tree(spans)
    synth = [s for s in spans if s["name"] == "sweep.synthesize"]
    assert len(synth) == res.n_chunks
    for s in synth:
        assert _names_under(kids, s) == list(SYNTH_PHASES)
    waits = [s for s in spans if s["name"] == "kernel.wait"]
    assert sorted(w["attrs"]["chunk"] for w in waits) == list(
        range(res.n_chunks))
    launches = [s for s in spans if s["name"] == "kernel.launch"]
    if path == "numpy":
        assert launches == []
        return
    assert len(launches) == res.n_chunks
    l = len(wl.layers)
    for s in launches:
        assert by_id[s["parent_id"]]["name"] == "sweep.dispatch"
        assert (s["attrs"]["n"], s["attrs"]["l"], s["attrs"]["w"]) == (
            16, l, 1)
        # one packed buffer: 15 config columns, 11 layer rows, 1 MAC sum
        assert (s["attrs"]["buffers"], s["attrs"]["bytes"]) == (
            1, 4 * (15 * 16 + 11 * l + 1))


def test_nsga2_generation_spans():
    from repro.explore.search import nsga2
    from repro.explore.space import space_for_workload
    space = space_for_workload("vgg16")
    res, spans = _traced(lambda: nsga2(space, "vgg16", 24, pop_size=8,
                                       seed=1, backend="numpy"))
    by_id, kids = _tree(spans)
    gens = [s for s in spans if s["name"] == "nsga2.generation"]
    assert len(gens) == 2
    for g in gens:
        assert sorted(_names_under(kids, g)) == sorted(
            ["nsga2.rank", "nsga2.rank", "nsga2.archive",
             "nsga2.hypervolume", "explore.evaluate"])
    evals = [s for s in spans if s["name"] == "explore.evaluate"]
    assert len(evals) == 3                 # the first population, then
    for e in evals:                        # one per generation
        assert _names_under(kids, e).count("explore.objectives") == 1


@pytest.mark.parametrize("suite", [("vgg16",), ("vgg16", "resnet34")])
def test_evaluator_kernel_launch_and_wait(suite, jax_usable):
    """One Pallas call per evaluation: its launch and the host's wait
    on its outputs are siblings under explore.evaluate, before the
    objective matrix."""
    if not jax_usable:
        pytest.skip("jax unusable on this host")
    from repro.explore.search import Evaluator
    from repro.explore.space import space_for_workload, space_for_workloads
    space = (space_for_workload(suite[0]) if len(suite) == 1
             else space_for_workloads(suite))
    ev = Evaluator(space, suite[0] if len(suite) == 1 else list(suite),
                   backend="jax", use_pallas=True)
    g = space.random_population(8, np.random.default_rng(0))
    _, spans = _traced(lambda: ev.evaluate(g))
    by_id, kids = _tree(spans)
    (e,) = [s for s in spans if s["name"] == "explore.evaluate"]
    names = [n for n in _names_under(kids, e)
             if n in ("kernel.launch", "kernel.wait", "explore.objectives")]
    assert names == ["kernel.launch", "kernel.wait", "explore.objectives"]
    (launch,) = [s for s in spans if s["name"] == "kernel.launch"]
    assert launch["attrs"]["w"] == len(suite)
