"""Preemption-safe exploration runtime (ISSUE 7 tentpole): checkpoint/
resume for chunked sweeps and NSGA-II searches, deterministic fault
injection, the chunk watchdog, and jax->numpy degradation.

The contract under test: a run killed at *any* chunk / generation
boundary and resumed from its newest valid snapshot produces a Pareto
front **bit-identical** to the uninterrupted run on the numpy backend —
including synthesis-cache hit/miss accounting — and within 1e-6 on jax.
"""

import os

import numpy as np
import pytest

from repro.core.accelerator import AcceleratorConfig
from repro.core.dse import ExploreSpec, run
from repro.core import dse_batch
from repro.core.dse_batch import ChunkDeadlineExceeded, _sweep_chunked
from repro.core.pe import PEType
from repro.core.synthesis import PersistentSynthesisCache
from repro.core.workloads import ConvLayer, Workload, get_workload
from repro.explore import CoExploreSpace, nsga2
from repro.runtime.dse_checkpoint import (SearchCheckpointer,
                                          SweepCheckpointer, resume_search,
                                          resume_sweep)
from repro.runtime.fault_tolerance import InjectedFailure

WL = get_workload("vgg16")
SPACE = [
    AcceleratorConfig(pe_type=t, pe_rows=r, pe_cols=c, glb_kb=g,
                      dram_bw_gbps=bw)
    for t in tuple(PEType)
    for (r, c, g, bw) in [(8, 8, 64, 6.4), (12, 14, 128, 12.8),
                          (16, 16, 256, 12.8), (32, 32, 512, 25.6)]
]
FEED = SPACE * 7                 # 112 configs; chunk_size=11 -> 11 chunks
CHUNK = 11
N_CHUNKS = 11

TINY_WL = Workload("tiny", (
    ConvLayer("c1", 58, 58, 64, 64),
    ConvLayer("c2", 30, 30, 64, 128, 3, 3, 2),
    ConvLayer("fc", 1, 1, 512, 1000, 1, 1),
))
SEARCH_SPACE = CoExploreSpace(n_layers=len(TINY_WL.layers))


def _assert_same_sweep(a, b):
    assert a.n_configs == b.n_configs
    assert a.n_chunks == b.n_chunks
    assert a.front_size == b.front_size
    for m in a.front_metrics:
        assert np.array_equal(a.front_metrics[m], b.front_metrics[m]), m
    for k in a.front_soa:
        assert np.array_equal(a.front_soa[k], b.front_soa[k]), k


def _assert_same_search(a, b, *, exact=True):
    eq = np.array_equal if exact else \
        lambda x, y: np.allclose(x, y, rtol=1e-6, atol=0)
    assert np.array_equal(a.genomes, b.genomes)
    assert eq(a.front_objectives, b.front_objectives)
    assert np.array_equal(a.population, b.population)
    assert eq(a.population_objectives, b.population_objectives)
    assert eq(a.all_objectives, b.all_objectives)
    assert a.n_evals == b.n_evals
    assert [e for e, _ in a.history] == [e for e, _ in b.history]
    np.testing.assert_allclose([h for _, h in a.history],
                               [h for _, h in b.history],
                               rtol=0 if exact else 1e-6, atol=0)


# ---------------------------------------------------------------------------
# sweep checkpoint/resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ref_sweep():
    return _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="numpy")


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("boundary", range(N_CHUNKS))
def test_sweep_resume_bit_identical_at_every_boundary(
        tmp_path, ref_sweep, overlap, boundary):
    """Kill the stream once at each chunk boundary: the resumed front is
    byte-for-byte the uninterrupted one, under both pipeline modes."""
    res = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                       checkpoint_every=2, fail_at={boundary: 1},
                       chunk_size=CHUNK, backend="numpy", overlap=overlap)
    assert res.timings["restarts"] == 1
    _assert_same_sweep(res, ref_sweep)


def test_sweep_resume_repeated_failures(tmp_path, ref_sweep):
    res = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                       checkpoint_every=2, fail_at={3: 1, 6: 2},
                       chunk_size=CHUNK, backend="numpy")
    assert res.timings["restarts"] == 3
    _assert_same_sweep(res, ref_sweep)


def test_sweep_resume_cache_accounting_identical(tmp_path, ref_sweep):
    """Hit/miss/eviction counters of the persisted synthesis cache replay
    exactly through a preempted-and-resumed stream."""
    clean_cache = PersistentSynthesisCache(tmp_path / "clean.npz")
    clean = _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="numpy",
                           cache=clean_cache)
    faulty_cache = PersistentSynthesisCache(tmp_path / "faulty.npz")
    res = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_every=2, fail_at={2: 1, 7: 1},
                       cache=faulty_cache, chunk_size=CHUNK,
                       backend="numpy")
    assert res.timings["restarts"] == 2
    _assert_same_sweep(res, clean)
    for stat in ("hits", "misses", "evictions"):
        assert getattr(faulty_cache, stat) == getattr(clean_cache, stat), \
            stat
    assert len(faulty_cache) == len(clean_cache)


def test_sweep_resume_after_completion_is_idempotent(tmp_path, ref_sweep):
    """Resuming a finished run restores the terminal snapshot and skips
    the whole feed — no re-synthesis, identical front."""
    first = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                         checkpoint_every=4, chunk_size=CHUNK,
                         backend="numpy")
    cache = PersistentSynthesisCache(tmp_path / "c.npz")
    again = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                         checkpoint_every=4, cache=cache,
                         chunk_size=CHUNK, backend="numpy")
    assert again.timings["restarts"] == 0
    _assert_same_sweep(first, again)
    _assert_same_sweep(again, ref_sweep)
    # every chunk was skipped: the cache never synthesized a row
    assert cache.misses == 0 and cache.hits == 0


def test_sweep_corrupt_snapshot_falls_back_to_older(tmp_path, ref_sweep):
    ck = SweepCheckpointer(str(tmp_path), every=2)
    _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="numpy",
                   checkpoint=ck)
    steps = sorted(d for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps, "expected snapshots on disk"
    with open(tmp_path / steps[-1] / "arrays.npz", "r+b") as f:
        f.seek(8)
        f.write(b"\xde\xad\xbe\xef")           # corrupt the newest one
    res = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                       checkpoint_every=2, chunk_size=CHUNK,
                       backend="numpy")
    assert res.timings["restarts"] == 0
    _assert_same_sweep(res, ref_sweep)          # replayed the tail


def test_sweep_resume_exhausts_max_restarts(tmp_path):
    with pytest.raises(InjectedFailure):
        resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                     fail_at={0: 5}, max_restarts=2, chunk_size=CHUNK,
                     backend="numpy")


def test_sweep_non_retryable_propagates(tmp_path):
    calls = {"n": 0}

    def feed():
        calls["n"] += 1
        raise KeyError("feed exploded")

    with pytest.raises(KeyError):
        resume_sweep(WL, feed, checkpoint_dir=str(tmp_path),
                     chunk_size=CHUNK, backend="numpy")
    assert calls["n"] == 1                      # no blind retry


def test_sweep_resume_jax_backend(tmp_path, jax_usable):
    if not jax_usable:
        pytest.skip("jax not usable on this host")
    clean = _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="jax")
    res = resume_sweep(WL, [FEED], checkpoint_dir=str(tmp_path),
                       checkpoint_every=2, fail_at={4: 1},
                       chunk_size=CHUNK, backend="jax")
    assert res.timings["restarts"] == 1
    assert res.n_configs == clean.n_configs
    assert res.front_size == clean.front_size
    for m in clean.front_metrics:               # same kernel replayed on
        np.testing.assert_allclose(             # the same chunks
            res.front_metrics[m], clean.front_metrics[m],
            rtol=1e-6, atol=0, err_msg=m)


def test_sweep_checkpointer_ignores_foreign_snapshots(tmp_path):
    """A sweep restore refuses a search snapshot sharing the directory
    (and vice versa) instead of mis-restoring."""
    rng = np.random.default_rng(0)
    sck = SearchCheckpointer(str(tmp_path), every=1)
    sck.save(gen=0, evals=4, pop=np.zeros((4, 7), dtype=np.int64),
             F=np.zeros((4, 2)), arch_g=np.zeros((2, 7), dtype=np.int64),
             arch_F=np.zeros((2, 2)), ref=np.ones(2),
             history=[(4, 0.0)], all_F=[np.zeros((4, 2))],
             rng_state=rng.bit_generator.state, eps_vec=None)
    assert SweepCheckpointer(str(tmp_path)).restore() is None
    wck = SweepCheckpointer(str(tmp_path / "s"), every=1)
    wck.save(cursor=1, n_total=8, front_soa={}, front_metrics={},
             cache_state=None)
    assert SearchCheckpointer(str(tmp_path / "s")).restore() is None


# ---------------------------------------------------------------------------
# watchdog + degradation
# ---------------------------------------------------------------------------

def test_watchdog_redispatches_stuck_chunk(tmp_path, monkeypatch,
                                           ref_sweep):
    """A chunk kernel exceeding the deadline is cancelled and recomputed
    serially: the stream finishes with the exact front."""
    real_kernel = dse_batch._sweep_kernel
    state = {"calls": 0}

    def slow_once(xp, cfg, lay, **kw):
        state["calls"] += 1
        if state["calls"] == 3:                 # one mid-stream chunk
            import time
            time.sleep(0.5)
        return real_kernel(xp, cfg, lay, **kw)

    monkeypatch.setattr(dse_batch, "_sweep_kernel", slow_once)
    with pytest.warns(RuntimeWarning, match="watchdog deadline"):
        res = _sweep_chunked(WL, [FEED], chunk_size=CHUNK,
                             backend="numpy", overlap=True,
                             chunk_deadline_s=0.1)
    assert res.timings["watchdog_redispatches"] >= 1
    _assert_same_sweep(res, ref_sweep)


def test_watchdog_zombie_worker_does_not_cascade(monkeypatch, ref_sweep):
    """Regression (ISSUE 9): ``fut.cancel()`` cannot interrupt a running
    kernel, so before the executor-replacement fix the zombie worker kept
    occupying the 1-worker pool and every later chunk queued behind it
    into its own deadline.  A deliberately slow *first* chunk must now
    fire the watchdog exactly once, replace the executor, and let the
    rest of the stream (including chunks already queued on the torn-down
    executor) finish cleanly on the exact front."""
    real_kernel = dse_batch._sweep_kernel
    state = {"calls": 0}

    def slow_first(xp, cfg, lay, **kw):
        state["calls"] += 1
        if state["calls"] == 1:
            import time
            time.sleep(0.9)
        return real_kernel(xp, cfg, lay, **kw)

    monkeypatch.setattr(dse_batch, "_sweep_kernel", slow_first)
    with pytest.warns(RuntimeWarning) as rec:
        res = _sweep_chunked(WL, [FEED], chunk_size=CHUNK,
                             backend="numpy", overlap=True,
                             prefetch_depth=4, chunk_deadline_s=0.3)
    deadline_warns = [w for w in rec
                     if "watchdog deadline" in str(w.message)]
    assert len(deadline_warns) == 1          # no cascading deadlines
    t = res.timings
    assert t["watchdog_redispatches"] == 1
    assert t["executor_replacements"] == 1
    # chunks queued behind the zombie surface as cancellations and are
    # recomputed serially, never as their own watchdog fires
    assert 0 < t["cancelled_recomputes"] < N_CHUNKS
    _assert_same_sweep(res, ref_sweep)


class _SlowBuf:
    """Array-like whose materialization blocks — a wedged device buffer."""

    def __init__(self, arr, delay):
        self.arr, self.delay = arr, delay

    def __array__(self, dtype=None):
        import time
        time.sleep(self.delay)
        return np.asarray(self.arr, dtype=dtype)


def test_jax_watchdog_drops_abandoned_buffers(monkeypatch):
    """Regression (ISSUE 9): the daemon materialize thread the watchdog
    abandons used to park the chunk's host+device buffers in its result
    box for the life of the process.  The orphan must now discard its
    result on completion and the ledger must return to zero live."""
    import time
    from repro.core.dse_batch import abandoned_finalizers

    n = 4
    out = {"latency_s": _SlowBuf(np.ones(n), 0.8),
           "energy_j": _SlowBuf(np.ones(n), 0.0)}
    monkeypatch.setattr(dse_batch, "get_jax_kernel",
                        lambda mesh, outputs: (lambda c, l: out, False))
    monkeypatch.setattr(dse_batch, "_to_jax_inputs",
                        lambda cfg, lay, exact: (cfg, lay))
    a0 = abandoned_finalizers.abandoned
    c0 = abandoned_finalizers.completed
    finalize = dse_batch._dispatch_chunk(
        {"pe_rows": np.ones(n)}, {}, "jax", None, n, n, None)
    with pytest.raises(ChunkDeadlineExceeded):
        finalize(timeout=0.1)
    assert abandoned_finalizers.abandoned == a0 + 1
    deadline = time.time() + 5.0
    while abandoned_finalizers.completed < c0 + 1:
        if time.time() > deadline:            # pragma: no cover
            pytest.fail("orphaned finalizer never completed")
        time.sleep(0.05)
    assert abandoned_finalizers.live == (a0 - c0)   # back to baseline


def test_jax_watchdog_stream_counts_abandoned_finalizers(monkeypatch,
                                                         ref_sweep):
    """Stream-level: a jax chunk that never materializes within the
    deadline is recomputed on numpy, counted in
    ``timings['abandoned_finalizers']``, and the stream finishes with
    the exact front (no cascade, no unbounded orphan growth)."""
    from repro.core.dse_batch import abandoned_finalizers
    real_kernel = dse_batch._sweep_kernel
    state = {"calls": 0}

    def jax_fn(cfg, lay):
        state["calls"] += 1
        out = real_kernel(np, cfg, lay, outputs="aggregates")
        if state["calls"] == 1:
            return {k: _SlowBuf(v, 0.9) for k, v in out.items()}
        return out

    monkeypatch.setattr(dse_batch, "resolve_backend",
                        lambda b="auto": "jax")
    monkeypatch.setattr(dse_batch, "_require_jax_mesh", lambda mesh: None)
    monkeypatch.setattr(dse_batch, "get_jax_kernel",
                        lambda mesh, outputs: (jax_fn, False))
    monkeypatch.setattr(dse_batch, "_to_jax_inputs",
                        lambda cfg, lay, exact: (cfg, lay))
    a0 = abandoned_finalizers.abandoned
    with pytest.warns(RuntimeWarning) as rec:
        res = _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="jax",
                             overlap=True, prefetch_depth=3,
                             chunk_deadline_s=0.3)
    assert len([w for w in rec
                if "watchdog deadline" in str(w.message)]) == 1
    assert res.timings["watchdog_redispatches"] == 1
    assert res.timings["abandoned_finalizers"] == 1
    assert abandoned_finalizers.abandoned == a0 + 1
    _assert_same_sweep(res, ref_sweep)


def test_jax_failure_degrades_stream_to_numpy(monkeypatch, ref_sweep):
    """A jax failure mid-stream falls back to the numpy kernel with a
    warning instead of losing the accumulated front."""
    monkeypatch.setattr(dse_batch, "resolve_backend", lambda b="auto": "jax")
    monkeypatch.setattr(dse_batch, "_require_jax_mesh", lambda mesh: None)

    def boom(mesh=None, outputs="full"):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(dse_batch, "get_jax_kernel", boom)
    with pytest.warns(RuntimeWarning, match="degrading stream to numpy"):
        res = _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="jax",
                             degrade_on_failure=True)
    assert res.backend == "numpy"
    assert res.timings["degraded"] is True
    _assert_same_sweep(res, ref_sweep)


def test_jax_failure_raises_when_degradation_disabled(monkeypatch):
    """Degradation is opt-in: by default a jax failure mid-stream raises
    instead of finishing the run on the host."""
    monkeypatch.setattr(dse_batch, "resolve_backend", lambda b="auto": "jax")
    monkeypatch.setattr(dse_batch, "_require_jax_mesh", lambda mesh: None)

    def boom(mesh=None, outputs="full"):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(dse_batch, "get_jax_kernel", boom)
    with pytest.raises(RuntimeError, match="device wedged"):
        _sweep_chunked(WL, [FEED], chunk_size=CHUNK, backend="jax")


# ---------------------------------------------------------------------------
# nsga2 checkpoint/resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ref_search():
    return nsga2(SEARCH_SPACE, TINY_WL, 120, pop_size=16, seed=3,
                 backend="numpy")


@pytest.mark.parametrize("boundary", range(8))   # init + 7 generations
def test_search_resume_bit_identical_at_every_generation(
        tmp_path, ref_search, boundary):
    """Kill the search once at each generation boundary (including before
    the initial population): resumed result is bit-identical — front,
    population, RNG-threaded history, and the full objective trail."""
    res = resume_search(SEARCH_SPACE, TINY_WL, 120,
                        checkpoint_dir=str(tmp_path), checkpoint_every=1,
                        fail_at_generation={boundary: 1},
                        pop_size=16, seed=3, backend="numpy")
    assert res.stats["restarts"] == 1
    _assert_same_search(res, ref_search)


def test_search_resume_repeated_failures(tmp_path, ref_search):
    res = resume_search(SEARCH_SPACE, TINY_WL, 120,
                        checkpoint_dir=str(tmp_path), checkpoint_every=2,
                        fail_at_generation={1: 1, 5: 2, 7: 1},
                        pop_size=16, seed=3, backend="numpy")
    assert res.stats["restarts"] == 4
    _assert_same_search(res, ref_search)


def test_search_resume_with_epsilon_archive(tmp_path):
    clean = nsga2(SEARCH_SPACE, TINY_WL, 120, pop_size=16, seed=3,
                  backend="numpy", archive_epsilon=0.05)
    res = resume_search(SEARCH_SPACE, TINY_WL, 120,
                        checkpoint_dir=str(tmp_path), checkpoint_every=1,
                        fail_at_generation={2: 1, 5: 1},
                        pop_size=16, seed=3, backend="numpy",
                        archive_epsilon=0.05)
    assert res.stats["restarts"] == 2
    _assert_same_search(res, clean)
    assert res.stats["archive_epsilon"] == clean.stats["archive_epsilon"]
    assert res.stats["archive_size"] == clean.stats["archive_size"]


def test_resume_search_rejects_non_nsga2(tmp_path):
    with pytest.raises(ValueError, match="nsga2"):
        resume_search(SEARCH_SPACE, TINY_WL, 64,
                      checkpoint_dir=str(tmp_path), method="random")


# ---------------------------------------------------------------------------
# ExploreSpec / run() facade wiring
# ---------------------------------------------------------------------------

def test_explore_spec_checkpoint_validation():
    with pytest.raises(ValueError, match="checkpoint_every needs"):
        ExploreSpec.single(WL, [FEED], chunk_size=CHUNK,
                           checkpoint_every=4)
    with pytest.raises(ValueError, match="no resumable stream"):
        ExploreSpec.single(WL, [FEED],
                           checkpoint_dir="/tmp/nope")
    with pytest.raises(ValueError, match="checkpoint_every must be >= 1"):
        ExploreSpec.single(WL, [FEED], chunk_size=CHUNK,
                           checkpoint_dir="/tmp/nope", checkpoint_every=0)


def test_run_checkpointed_chunked_sweep(tmp_path, ref_sweep):
    spec = ExploreSpec.single(WL, [FEED],
                              chunk_size=CHUNK, backend="numpy",
                              use_cache=False,
                              checkpoint_dir=str(tmp_path),
                              checkpoint_every=2)
    first = run(spec)
    _assert_same_sweep(first, ref_sweep)
    assert first.timings["restarts"] == 0
    again = run(spec)                   # resumes the terminal snapshot
    _assert_same_sweep(again, ref_sweep)


def test_run_checkpointed_search_requires_nsga2(tmp_path):
    spec = ExploreSpec.mixed("vgg16", method="random", budget=32,
                             checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="nsga2"):
        run(spec)


# ---------------------------------------------------------------------------
# property test: resume from an arbitrary failure schedule (hypothesis)
# ---------------------------------------------------------------------------

def test_sweep_resume_any_failure_schedule(ref_sweep):
    """Property: *any* schedule of kills at chunk boundaries, any
    snapshot cadence, either pipeline mode — the resumed front is
    bit-identical (the deterministic boundary sweep above is the
    always-on baseline; this widens it when hypothesis is available)."""
    pytest.importorskip("hypothesis")
    import tempfile

    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(st.dictionaries(st.integers(0, N_CHUNKS - 1),
                           st.integers(1, 2), max_size=3),
           st.integers(1, 5), st.booleans())
    def check(fail_at, every, overlap):
        with tempfile.TemporaryDirectory() as d:
            res = resume_sweep(WL, [FEED], checkpoint_dir=d,
                               checkpoint_every=every,
                               fail_at=dict(fail_at), max_restarts=16,
                               chunk_size=CHUNK, backend="numpy",
                               overlap=overlap)
        assert res.timings["restarts"] == sum(fail_at.values())
        _assert_same_sweep(res, ref_sweep)

    check()
