"""Guided multi-objective search over the joint design space.

Three engines share one chunked, memoized evaluator that routes every
genome population through the fused mixed-precision sweep kernel
(:func:`repro.core.dse_batch.sweep_mixed`, aggregates-only outputs) and
the digest-keyed synthesis caches:

* :func:`random_search` — the baseline the guided searches must beat at
  equal evaluation budget (benchmarked in ``BENCH_coexplore.json``);
* :func:`nsga2` — NSGA-II-style evolutionary loop: non-dominated sorting,
  crowding distance, binary tournaments, uniform crossover + resampling
  mutation;
* :func:`successive_halving` — a budget-aware racing loop that screens
  large populations on cheap layer-prefix subsets of the workload and
  promotes only the best fraction to full evaluation.

Determinism: every loop threads one explicit ``numpy.random.Generator``
(no hidden global RNG), random draws happen in data-independent order, and
all ranking ties break stably by index — the same seed reproduces the same
search trajectory, and the numpy/jax kernel parity (~1e-7) makes the final
fronts match across backends (asserted in ``tests/test_explore.py``).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Sequence

import numpy as np

from repro.core.dse_batch import (_mesh_shards, _sweep_mixed,
                                  _sweep_mixed_many, resolve_backend,
                                  resolve_use_pallas)
from repro.core.workloads import Workload, get_workload
from repro.explore.accuracy import resolve_accuracy
from repro.explore.objectives import (DEFAULT_MULTI_OBJECTIVES,
                                      DEFAULT_OBJECTIVES,
                                      DEFAULT_SERVING_OBJECTIVES,
                                      SERVING_OBJECTIVES,
                                      multi_objective_matrix,
                                      objective_matrix,
                                      resolve_objectives)
from repro.explore.pareto import (EpsilonDominanceArchive,
                                  crowding_distance, epsilon_from_reference,
                                  hypervolume, nondominated_sort,
                                  pareto_mask_k, reference_point)
from repro.explore.space import CoExploreManySpace, CoExploreSpace
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclasses.dataclass
class SearchResult:
    """Outcome of one co-exploration run.

    ``genomes`` / ``front_objectives`` hold the final non-dominated set;
    ``history`` is ``(evaluations, hypervolume)`` pairs under
    ``ref_point``; ``all_objectives`` keeps every *full-workload*
    objective row (successive-halving's subset-rung rows are excluded —
    they live on a different scale) so runs can be re-scored under a
    shared reference point.
    """

    method: str
    workload: str
    objectives: tuple[str, ...]
    seed: int
    space: CoExploreSpace
    genomes: np.ndarray
    front_objectives: np.ndarray
    ref_point: np.ndarray
    history: list[tuple[int, float]]
    all_objectives: np.ndarray
    n_evals: int
    stats: dict
    # final evolutionary population (nsga2 only): the returned front is the
    # unbounded external archive, which is a superset of this population's
    # own non-dominated set
    population: np.ndarray | None = None
    population_objectives: np.ndarray | None = None
    # tier-2 quantized-forward elite validation, attached by
    # repro.core.dse when the accuracy spec asks for it
    validation: object | None = None

    @property
    def front_size(self) -> int:
        return len(self.genomes)

    def hypervolume(self, ref: np.ndarray | None = None) -> float:
        """Front hypervolume under ``ref`` (default: the run's own)."""
        return hypervolume(self.front_objectives,
                           self.ref_point if ref is None else ref)

    def front_points(self) -> list[dict]:
        """Materialize the front: config objects, per-layer mode names,
        objective values — sorted by the first objective.

        Multi-workload runs report ``modes`` as a dict keyed by workload
        name (each value the workload's own per-layer mode tuple) instead
        of a flat tuple.
        """
        from repro.core.accelerator import soa_to_configs
        from repro.core.pe import PEType
        types = tuple(PEType)
        soa, assign = self.space.decode(self.genomes)
        cfgs = soa_to_configs(soa)
        order = np.argsort(self.front_objectives[:, 0], kind="stable")
        if isinstance(self.space, CoExploreManySpace):
            names = (self.space.workload_names
                     or tuple(f"workload{w}"
                              for w in range(self.space.n_workloads)))

            def modes_of(i):
                return {nm: tuple(types[j].value for j in assign[i, s:e])
                        for nm, (s, e) in zip(names,
                                              self.space.segment_bounds)}
        else:
            def modes_of(i):
                return tuple(types[j].value for j in assign[i])
        return [{
            "config": cfgs[i],
            "modes": modes_of(i),
            **{name: float(self.front_objectives[i, k])
               for k, name in enumerate(self.objectives)},
        } for i in order]


def _fold_floor(accuracy, sqnr_floor_db, *, stacklevel: int = 3):
    """Fold the deprecated ``sqnr_floor_db=`` side-channel into an
    accuracy spec (``AccuracySpec(floor_db=...)``).  Raises if the caller
    supplies both spellings — floors ride on the accuracy model now."""
    if sqnr_floor_db is None:
        return accuracy
    warnings.warn(
        "sqnr_floor_db= is deprecated; pass "
        "accuracy=AccuracySpec(floor_db=...) instead",
        DeprecationWarning, stacklevel=stacklevel)
    if accuracy is not None:
        raise ValueError(
            "pass either accuracy= or the deprecated sqnr_floor_db=, not "
            "both; put the floor on the accuracy spec (floor_db=)")
    from repro.explore.accuracy import AccuracySpec
    return AccuracySpec(floor_db=sqnr_floor_db)


class Evaluator:
    """Chunked, memoized genome evaluation through the fused sweep.

    Populations are decoded to (hardware SoA, assignment) and pushed
    through :func:`sweep_mixed` with ``outputs="aggregates"`` — under jax
    the (N, L) layer intermediates are dead-code-eliminated, chunks are
    padded to power-of-two shapes so a search compiles O(log) kernels.
    Results are memoized by genome digest, so an evolutionary loop that
    re-visits a genome never re-runs the kernel; hardware re-visits hit
    the digest-keyed synthesis cache inside ``sweep_mixed``.

    **Multi-workload mode** (the QUIDAM co-exploration setting): pass a
    *sequence* of workloads together with a
    :class:`~repro.explore.space.CoExploreManySpace` — genomes then carry
    one mode segment per workload, evaluation routes through
    :func:`sweep_mixed_many` (one fused kernel call for all W workloads,
    synthesis shared per hardware digest), and objectives come from
    :func:`repro.explore.objectives.multi_objective_matrix` (worst-case /
    weighted-mean across the suite).

    ``accuracy`` selects the accuracy tier scoring the
    ``accuracy_noise`` columns — anything
    :func:`repro.explore.accuracy.resolve_accuracy` takes (``None`` =
    tier-0 proxy); an :class:`~repro.explore.accuracy.AccuracySpec`
    ``floor_db`` turns per-workload SQNR floors into constraints.
    ``sqnr_floor_db`` is the deprecated spelling of that floor.
    """

    def __init__(self, space: CoExploreSpace,
                 workload: Workload | str | Sequence[Workload | str],
                 objectives: Sequence[str] | None = None,
                 *, backend: str = "auto", chunk_size: int = 4096,
                 use_cache: bool = True, weights=None,
                 sqnr_floor_db=None, mesh=None, traffic=None,
                 n_slots: int = 8, use_pallas: bool | None = None,
                 accuracy=None):
        accuracy = _fold_floor(accuracy, sqnr_floor_db, stacklevel=3)
        self.accuracy = (None if accuracy is None
                         else resolve_accuracy(accuracy))
        self.space = space
        self.multi = isinstance(workload, (list, tuple))
        if self.multi:
            wls = tuple(get_workload(w) if isinstance(w, str) else w
                        for w in workload)
            if not isinstance(space, CoExploreManySpace):
                raise ValueError(
                    "a workload sequence needs a CoExploreManySpace "
                    "(see repro.explore.space.space_for_workloads)")
            counts = tuple(len(w.layers) for w in wls)
            if space.layer_counts != counts:
                raise ValueError(
                    f"space layer_counts {space.layer_counts} != workload "
                    f"layer counts {counts}")
            self.workloads = wls
            self.workload = None
        else:
            wl = (get_workload(workload)
                  if isinstance(workload, str) else workload)
            if space.n_layers != len(wl.layers):
                raise ValueError(
                    f"space has {space.n_layers} layer genes but workload "
                    f"{wl.name!r} has {len(wl.layers)} layers")
            self.workloads = (wl,)
            self.workload = wl
        # traffic= switches the default objective set to the serving
        # triple; explicit serving objectives without a trace are an
        # error (the fleet simulator needs a workload to replay), as is
        # serving in multi-workload mode (one trace drives one fleet)
        if objectives is None:
            if traffic is not None and not self.multi:
                objectives = DEFAULT_SERVING_OBJECTIVES
            else:
                objectives = (DEFAULT_MULTI_OBJECTIVES if self.multi
                              else DEFAULT_OBJECTIVES)
        self.objectives = resolve_objectives(
            objectives, stacklevel=3,
            scope="multi" if self.multi else "single")
        serving = [o for o in self.objectives if o in SERVING_OBJECTIVES]
        if serving and self.multi:
            raise ValueError(
                f"serving objectives {serving} are single-workload only "
                f"(one traffic trace drives one fleet)")
        if serving and traffic is None:
            raise ValueError(
                f"objectives {serving} need traffic= (a TrafficTrace, "
                f"TrafficPreset, or preset name)")
        if traffic is not None and not serving:
            raise ValueError(
                f"traffic= given but no serving objective in "
                f"{self.objectives}; add one of {SERVING_OBJECTIVES} or "
                f"drop traffic=")
        if traffic is not None:
            from repro.serving.traffic import resolve_traffic
            traffic = resolve_traffic(traffic)
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.traffic = traffic
        self.n_slots = int(n_slots)
        self.backend = resolve_backend(backend)
        self.chunk_size = int(chunk_size)
        self.use_cache = use_cache
        self.weights = weights
        # mesh= shards every evaluation chunk's genome axis across devices
        # (jax: shard_map via sweep_mixed / sweep_mixed_many; numpy: an
        # int simulates that many shards bit-identically)
        if self.backend == "jax" and isinstance(mesh, int):
            raise ValueError(
                "backend='jax' needs a jax.sharding.Mesh for mesh=, not "
                "an int shard count (see repro.launch.mesh.make_sweep_mesh)")
        self.mesh = mesh
        # use_pallas routes the fused aggregate reduction through the
        # hand-tiled Pallas sweep kernel (None = auto: only when jax has
        # a real accelerator and no mesh is sharding the genome axis)
        self.use_pallas = resolve_use_pallas(use_pallas, self.backend,
                                             mesh=self.mesh)
        self._memo: dict[tuple[bytes, int], np.ndarray] = {}
        self._subsets: dict[int, tuple] = {}
        self.n_requested = 0
        self.n_kernel = 0
        self.n_memo_hits = 0
        self.eval_seconds = 0.0

    @property
    def name(self) -> str:
        """Workload identity for reports: a single name or ``a+b+c``."""
        return "+".join(w.name for w in self.workloads)

    @property
    def full_subset(self) -> int:
        """The ``m`` that means "every layer": per-workload prefix length
        in multi mode, total layer count otherwise."""
        if self.multi:
            return max(self.space.layer_counts)
        return self.space.n_layers

    def _subset(self, m: int) -> tuple:
        """``(workloads, per-workload macs)`` for prefix length ``m`` —
        in multi mode each workload is cut to its first ``min(m, L_w)``
        layers, so successive-halving rungs race on cheap prefixes of the
        whole suite."""
        if m >= self.full_subset:
            m = self.full_subset
        cached = self._subsets.get(m)
        if cached is None:
            wls = tuple(
                w if m >= len(w.layers) else
                Workload(name=f"{w.name}[:{m}]", layers=w.layers[:m])
                for w in self.workloads)
            macs = tuple(np.array([l.macs for l in w.layers],
                                  dtype=np.float64) for w in wls)
            cached = (wls, macs)
            self._subsets[m] = cached
        return cached

    def _pad(self, n: int) -> int:
        if self.backend != "jax":
            return n
        return min(self.chunk_size, 1 << max(3, (n - 1).bit_length()))

    def _objective_rows(self, wls, macs, soa, assign, n_real) -> np.ndarray:
        """One padded chunk through the fused kernel -> (n_real, K)."""
        if self.multi:
            bounds = self.space.segment_bounds
            assigns = [assign[:, s:e][:, :len(w.layers)]
                       for (s, e), w in zip(bounds, wls)]
            agg = _sweep_mixed_many(wls, soa, assigns,
                                    use_cache=self.use_cache,
                                    backend=self.backend, mesh=self.mesh,
                                    use_pallas=self.use_pallas)
            agg = {k: np.asarray(v)[:, :n_real]
                   for k, v in agg.items() if np.ndim(v) == 2}
            with obs_trace.span("explore.objectives"):
                return multi_objective_matrix(
                    agg, [a[:n_real] for a in assigns], macs,
                    self.objectives, weights=self.weights,
                    accuracy=self.accuracy)
        wl, = wls
        agg = _sweep_mixed(wl, soa, assign[:, :len(wl.layers)],
                           use_cache=self.use_cache,
                           backend=self.backend, outputs="aggregates",
                           mesh=self.mesh, use_pallas=self.use_pallas)
        agg = {k: np.asarray(v)[:n_real] for k, v in agg.items()}
        with obs_trace.span("explore.objectives"):
            return objective_matrix(agg, assign[:n_real, :len(wl.layers)],
                                    macs[0], self.objectives,
                                    traffic=self.traffic,
                                    n_slots=self.n_slots,
                                    accuracy=self.accuracy)

    def evaluate(self, genomes: np.ndarray,
                 subset: int | None = None) -> np.ndarray:
        """``(N, K)`` objective rows for a genome matrix.

        ``subset`` evaluates on the first ``subset`` layers only (the
        successive-halving rungs; per workload in multi mode); objective
        rows are float64 regardless of backend.
        """
        t0 = time.perf_counter()
        with obs_trace.span("explore.evaluate", n=len(genomes),
                            subset=subset) as esp:
            g = self.space.validate(genomes, raise_on_invalid=True)
            m = self.full_subset if subset is None else min(
                int(subset), self.full_subset)
            self.n_requested += len(g)
            keys = self.space.genome_keys(g)
            out = np.empty((len(g), len(self.objectives)),
                           dtype=np.float64)
            todo: list[int] = []
            for i, key in enumerate(keys):
                row = self._memo.get((key, m))
                if row is None:
                    todo.append(i)
                else:
                    self.n_memo_hits += 1
                    out[i] = row
            wls, macs = self._subset(m)
            for s in range(0, len(todo), self.chunk_size):
                idx = np.asarray(todo[s:s + self.chunk_size],
                                 dtype=np.intp)
                # rows were validated above; skip the per-chunk repeat
                soa, assign = self.space.decode(g[idx],
                                                skip_validation=True)
                pad = self._pad(len(idx)) - len(idx)
                if pad > 0:
                    soa = {k: np.concatenate([v,
                                              v[-1:].repeat(pad, axis=0)])
                           for k, v in soa.items()}
                    assign = np.concatenate(
                        [assign, assign[-1:].repeat(pad, axis=0)])
                out[idx] = self._objective_rows(wls, macs, soa, assign,
                                                len(idx))
                self.n_kernel += len(idx)
                for j, i in enumerate(idx):
                    # copy: the caller owns `out`, and an in-place edit of
                    # the returned matrix must not poison the memo
                    self._memo[(keys[i], m)] = out[i].copy()
            esp.set(kernel=len(todo), memo_hits=len(g) - len(todo))
        dt = time.perf_counter() - t0
        self.eval_seconds += dt
        reg = obs_metrics.get_registry()
        reg.inc("explore.requested_evals", len(g))
        reg.inc("explore.kernel_evals", len(todo))
        reg.inc("explore.memo_hits", len(g) - len(todo))
        reg.inc("explore.eval_seconds", dt)
        return out

    def reset_stats(self) -> None:
        """Zero the per-search counters so a reused evaluator attributes
        ``stats()`` (and ``SearchResult.stats``) to one search instead of
        accumulating across every search it ever served.  The memo and
        subset caches are deliberately kept — resetting accounting must
        not change evaluation behavior."""
        self.n_requested = 0
        self.n_kernel = 0
        self.n_memo_hits = 0
        self.eval_seconds = 0.0

    def stats(self) -> dict:
        return {
            "requested_evals": self.n_requested,
            "kernel_evals": self.n_kernel,
            "memo_hits": self.n_memo_hits,
            "eval_seconds": self.eval_seconds,
            "backend": self.backend,
            "use_pallas": self.use_pallas,
            "n_workloads": len(self.workloads),
            "mesh_shards": (None if self.mesh is None else
                            _mesh_shards(self.mesh)),
            "traffic": (None if self.traffic is None
                        else self.traffic.name),
            "n_slots": (None if self.traffic is None else self.n_slots),
        }


def _front(genomes: np.ndarray, F: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    keep = pareto_mask_k(F)
    return genomes[keep], F[keep]


def _result(method: str, ev: Evaluator, seed: int, genomes, F,
            ref, history, all_F, n_evals, *, population=None,
            population_objectives=None) -> SearchResult:
    fg, ff = _front(genomes, F)
    return SearchResult(
        method=method, workload=ev.name,
        objectives=ev.objectives, seed=seed, space=ev.space,
        genomes=fg, front_objectives=ff, ref_point=np.asarray(ref),
        history=history, all_objectives=np.concatenate(all_F, axis=0),
        n_evals=n_evals, stats=ev.stats(), population=population,
        population_objectives=population_objectives)


def random_search(space: CoExploreSpace, workload, budget: int, *,
                  objectives: Sequence[str] | None = None,
                  seed: int = 0, backend: str = "auto",
                  chunk_size: int = 4096, batch_size: int | None = None,
                  ref_point: np.ndarray | None = None,
                  weights=None, sqnr_floor_db=None,
                  mesh=None, traffic=None, n_slots: int = 8,
                  use_pallas: bool | None = None,
                  accuracy=None,
                  batch: int | None = None) -> SearchResult:
    """Uniform-random baseline: ``budget`` independent genomes, running
    non-dominated reduction, hypervolume recorded per batch.

    ``workload`` may be a single workload or a sequence (multi-workload
    co-exploration — then ``space`` must be a
    :class:`~repro.explore.space.CoExploreManySpace`; ``weights`` and
    ``accuracy`` configure the suite objectives, see
    :class:`Evaluator`).  ``traffic=`` switches to serving-fleet
    objectives over an ``n_slots`` fleet.  Same for the other engines.
    ``batch=`` is the deprecated spelling of ``batch_size=``,
    ``sqnr_floor_db=`` of ``accuracy=AccuracySpec(floor_db=...)``.
    """
    if batch is not None:
        warnings.warn(
            "random_search(batch=...) is deprecated; use batch_size=",
            DeprecationWarning, stacklevel=2)
        if batch_size is None:
            batch_size = batch
    accuracy = _fold_floor(accuracy, sqnr_floor_db)
    rng = np.random.default_rng(seed)
    ev = Evaluator(space, workload, objectives, backend=backend,
                   chunk_size=chunk_size, weights=weights,
                   accuracy=accuracy, mesh=mesh,
                   traffic=traffic, n_slots=n_slots,
                   use_pallas=use_pallas)
    if budget < 1:
        raise ValueError("budget must be >= 1")
    if batch_size is not None and batch_size < 1:
        raise ValueError("batch_size must be >= 1")
    batch_size = (min(budget, 256) if batch_size is None
                  else min(batch_size, budget))
    front_g = np.empty((0, space.genome_width), dtype=np.int64)
    front_F = np.empty((0, len(ev.objectives)), dtype=np.float64)
    history: list[tuple[int, float]] = []
    all_F: list[np.ndarray] = []
    ref = ref_point
    evals = 0
    while evals < budget:
        n = min(batch_size, budget - evals)
        with obs_trace.span("random_search.batch", n=n, evals=evals):
            g = space.random_population(n, rng)
            F = ev.evaluate(g)
            evals += n
            all_F.append(F)
            if ref is None:
                ref = reference_point(F)
            front_g, front_F = _front(np.concatenate([front_g, g]),
                                      np.concatenate([front_F, F]))
            history.append((evals, hypervolume(front_F, ref)))
    return _result("random", ev, seed, front_g, front_F, ref, history,
                   all_F, evals)


def _ranks_and_crowding(F: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    ranks = nondominated_sort(F)
    crowd = np.empty(len(F), dtype=np.float64)
    for r in np.unique(ranks):
        idx = np.nonzero(ranks == r)[0]
        crowd[idx] = crowding_distance(F[idx])
    return ranks, crowd


def _tournament(rng: np.random.Generator, n_pick: int,
                ranks: np.ndarray, crowd: np.ndarray) -> np.ndarray:
    """Binary tournament on (rank asc, crowding desc, index asc)."""
    a = rng.integers(0, len(ranks), size=n_pick)
    b = rng.integers(0, len(ranks), size=n_pick)
    a_wins = ((ranks[a] < ranks[b])
              | ((ranks[a] == ranks[b]) & (crowd[a] > crowd[b]))
              | ((ranks[a] == ranks[b]) & (crowd[a] == crowd[b])
                 & (a <= b)))
    return np.where(a_wins, a, b)


def nsga2(space: CoExploreSpace, workload, budget: int, *,
          pop_size: int = 64,
          objectives: Sequence[str] | None = None,
          seed: int = 0, backend: str = "auto", chunk_size: int = 4096,
          mutation_rate: float = 0.08,
          ref_point: np.ndarray | None = None,
          weights=None, sqnr_floor_db=None, mesh=None,
          traffic=None, n_slots: int = 8,
          use_pallas: bool | None = None,
          accuracy=None,
          archive_epsilon=None,
          checkpoint_dir: str | None = None,
          checkpoint_every: int = 5,
          fail_at_generation: dict[int, int] | None = None
          ) -> SearchResult:
    """NSGA-II-style evolutionary multi-objective search.

    Classic loop: elitist (mu + lambda) survival over non-domination rank
    then crowding distance, binary-tournament parents, uniform crossover,
    per-gene resampling mutation, compatibility repair.  ``budget`` counts
    requested genome evaluations (initial population included), so runs
    compare 1:1 with :func:`random_search` at the same budget.

    Every evaluated genome also flows through an **external archive** — a
    running non-dominated reduction over the whole search trajectory,
    like random search's running front — so a non-dominated genome that
    crowding truncation drops from the population is never lost.  The
    returned front *is* the archive's non-dominated set (a superset of
    the final population's own front, which is also returned via
    ``population`` / ``population_objectives``); the hypervolume history
    tracks the archive, and with the default unbounded archive is
    therefore monotone.

    ``archive_epsilon`` bounds the archive with an epsilon-dominance grid
    (:class:`~repro.explore.pareto.EpsilonDominanceArchive`) so week-long
    runs hold memory constant: a scalar is a *relative* grid resolution
    (fraction of each objective's (ideal, reference) span,
    :func:`~repro.explore.pareto.epsilon_from_reference`); a sequence is
    an absolute per-objective epsilon.  Hypervolume stays within grid
    resolution of the unbounded archive
    (``tests/test_epsilon_archive.py``); the grid size lands in
    ``stats["archive_epsilon"]`` / ``stats["archive_size"]``.

    ``checkpoint_dir`` snapshots the full search state — generation
    index, population, archive, hypervolume history, and the threaded
    RNG stream — every ``checkpoint_every`` generations
    (:class:`repro.runtime.dse_checkpoint.SearchCheckpointer`); on entry
    the newest valid snapshot is restored and the run continues
    bit-identically.  ``fail_at_generation`` injects deterministic
    :class:`~repro.runtime.fault_tolerance.InjectedFailure`\\ s at
    generation boundaries to exercise that path (decremented in place so
    a dict shared across restarts fails each boundary ``n`` times).
    """
    if budget < 1:
        raise ValueError("budget must be >= 1")
    if pop_size < 4:
        raise ValueError("pop_size must be >= 4")
    fail_at_generation = (fail_at_generation
                          if fail_at_generation is not None else {})

    def maybe_fail(gen: int) -> None:
        if fail_at_generation.get(gen, 0) > 0:
            fail_at_generation[gen] -= 1
            from repro.runtime.fault_tolerance import InjectedFailure
            raise InjectedFailure(
                f"injected failure at generation boundary {gen}")

    ckpt = None
    if checkpoint_dir is not None:
        from repro.runtime.dse_checkpoint import SearchCheckpointer
        ckpt = SearchCheckpointer(checkpoint_dir, every=checkpoint_every)
    accuracy = _fold_floor(accuracy, sqnr_floor_db)
    rng = np.random.default_rng(seed)
    ev = Evaluator(space, workload, objectives, backend=backend,
                   chunk_size=chunk_size, weights=weights,
                   accuracy=accuracy, mesh=mesh,
                   traffic=traffic, n_slots=n_slots,
                   use_pallas=use_pallas)

    def eps_vector(ref, F0) -> np.ndarray | None:
        if archive_epsilon is None:
            return None
        if np.ndim(archive_epsilon) == 0:
            return epsilon_from_reference(ref, F0.min(axis=0),
                                          float(archive_epsilon))
        return np.asarray(archive_epsilon, dtype=np.float64)

    def rebuild_archive(eps, arch_g, arch_F):
        # deterministic reconstruction: re-offering the surviving
        # representatives in stored order reproduces the grid exactly
        archive = EpsilonDominanceArchive(eps)
        archive.add(arch_g, arch_F)
        return archive

    def acc_payload() -> dict:
        if ev.accuracy is None:
            return {}
        return {"accuracy_state": ev.accuracy.state(),
                "accuracy_digest": ev.accuracy.digest()}

    eps_archive = None
    eps_vec = None
    snap = ckpt.restore() if ckpt is not None else None
    if snap is not None:
        # pin the exact accuracy table the interrupted run scored with,
        # and refuse to resume under a *different* calibration (a digest
        # mismatch after restore means the accuracy spec itself changed)
        if ev.accuracy is not None \
                and snap.get("accuracy_state") is not None:
            ev.accuracy.restore_state(snap["accuracy_state"])
            want = snap.get("accuracy_digest")
            got = ev.accuracy.digest()
            if want is not None and want != got:
                raise ValueError(
                    f"checkpoint was scored under accuracy digest "
                    f"{want}; this run's accuracy spec yields {got} — "
                    f"refusing to resume against a different calibration")
        gen = snap["gen"]
        evals = snap["evals"]
        pop, F = snap["pop"], snap["F"]
        arch_g, arch_F = snap["arch_g"], snap["arch_F"]
        ref = snap["ref"]
        history = snap["history"]
        all_F = snap["all_F"]
        rng.bit_generator.state = snap["rng_state"]
        eps_vec = snap["eps_vec"]
        if eps_vec is not None:
            eps_archive = rebuild_archive(eps_vec, arch_g, arch_F)
    else:
        maybe_fail(0)
        pop = space.random_population(min(pop_size, budget), rng)
        F = ev.evaluate(pop)
        evals = len(pop)
        gen = 0
        ref = reference_point(F) if ref_point is None else ref_point
        eps_vec = eps_vector(ref, F)
        if eps_vec is not None:
            eps_archive = EpsilonDominanceArchive(eps_vec)
            eps_archive.add(pop, F)
            arch_g, arch_F = eps_archive.genomes, eps_archive.objectives
        else:
            arch_g, arch_F = _front(pop, F)
        history = [(evals, hypervolume(arch_F, ref))]
        all_F = [F]
        if ckpt is not None and ckpt.should_save(0, done=evals >= budget):
            ckpt.save(gen=0, evals=evals, pop=pop, F=F, arch_g=arch_g,
                      arch_F=arch_F, ref=ref, history=history,
                      all_F=all_F, rng_state=rng.bit_generator.state,
                      eps_vec=eps_vec, **acc_payload())
    reg = obs_metrics.get_registry()
    while evals < budget:
        maybe_fail(gen + 1)
        n_off = min(pop_size, budget - evals)
        with obs_trace.span("nsga2.generation", gen=gen + 1,
                            evals=evals, n_off=n_off):
            with obs_trace.span("nsga2.rank"):
                ranks, crowd = _ranks_and_crowding(F)
            p1 = _tournament(rng, n_off, ranks, crowd)
            p2 = _tournament(rng, n_off, ranks, crowd)
            children = space.crossover(pop[p1], pop[p2], rng)
            children = space.mutate(children, rng, mutation_rate)
            Fc = ev.evaluate(children)
            evals += n_off
            gen += 1
            all_F.append(Fc)
            with obs_trace.span("nsga2.archive"):
                if eps_archive is not None:
                    eps_archive.add(children, Fc)
                    arch_g = eps_archive.genomes
                    arch_F = eps_archive.objectives
                else:
                    comb_g = np.concatenate([arch_g, children])
                    comb_F = np.concatenate([arch_F, Fc])
                    # a genome re-visited across generations has an
                    # identical memoized objective row; keep one copy
                    # (first occurrence) so the archive stays the *set*
                    # of non-dominated genomes found
                    _, uidx = np.unique(comb_g, axis=0, return_index=True)
                    uidx.sort()
                    arch_g, arch_F = _front(comb_g[uidx], comb_F[uidx])
            comb = np.concatenate([pop, children])
            Fcomb = np.concatenate([F, Fc])
            with obs_trace.span("nsga2.rank"):
                ranks2, crowd2 = _ranks_and_crowding(Fcomb)
            order = np.lexsort((np.arange(len(comb)), -crowd2, ranks2))
            sel = order[:pop_size]
            pop, F = comb[sel], Fcomb[sel]
            with obs_trace.span("nsga2.hypervolume"):
                hv = hypervolume(arch_F, ref)
            history.append((evals, hv))
        reg.inc("nsga2.generations")
        reg.set("nsga2.archive_size", int(len(arch_F)))
        if ckpt is not None and ckpt.should_save(gen,
                                                 done=evals >= budget):
            ckpt.save(gen=gen, evals=evals, pop=pop, F=F, arch_g=arch_g,
                      arch_F=arch_F, ref=ref, history=history,
                      all_F=all_F, rng_state=rng.bit_generator.state,
                      eps_vec=eps_vec, **acc_payload())
    res = _result("nsga2", ev, seed, arch_g, arch_F, ref, history, all_F,
                  evals, population=pop, population_objectives=F)
    res.stats["archive_size"] = int(len(arch_F))
    if eps_vec is not None:
        res.stats["archive_epsilon"] = [float(e) for e in eps_vec]
    return res


def successive_halving(space: CoExploreSpace, workload, budget: int, *,
                       eta: int = 3,
                       objectives: Sequence[str] | None = None,
                       seed: int = 0, backend: str = "auto",
                       chunk_size: int = 4096, min_layers: int = 2,
                       ref_point: np.ndarray | None = None,
                       weights=None, sqnr_floor_db=None,
                       mesh=None, traffic=None, n_slots: int = 8,
                       use_pallas: bool | None = None,
                       accuracy=None) -> SearchResult:
    """Successive halving over workload layer-prefix subsets.

    Rung ``r`` evaluates its population on the first ``m_r`` layers only
    (a cheap, correlated proxy of the full workload; per workload in the
    multi-workload setting), keeps the best ``1/eta`` by (non-domination
    rank, crowding), and promotes them to the next, larger subset; the
    final rung is the full workload.  Every requested evaluation counts
    one unit of ``budget`` regardless of subset size, so the comparison
    with the other engines is conservative.
    """
    if budget < 1:
        raise ValueError("budget must be >= 1")
    if eta < 2:
        raise ValueError("eta must be >= 2")
    accuracy = _fold_floor(accuracy, sqnr_floor_db)
    rng = np.random.default_rng(seed)
    ev = Evaluator(space, workload, objectives, backend=backend,
                   chunk_size=chunk_size, weights=weights,
                   accuracy=accuracy, mesh=mesh,
                   traffic=traffic, n_slots=n_slots,
                   use_pallas=use_pallas)
    L = ev.full_subset
    sizes = [L]
    while sizes[-1] > min(min_layers, L) and len(sizes) < 4:
        nxt = max(min(min_layers, L), -(-sizes[-1] // eta))
        if nxt == sizes[-1]:
            break
        sizes.append(nxt)
    sizes = sizes[::-1]                    # small -> full
    r_count = len(sizes)
    # n0 * (1 + 1/eta + ...) ~= budget
    geo = sum(eta ** -r for r in range(r_count))
    n0 = max(eta ** (r_count - 1), int(budget / geo))
    pops = [max(1, n0 // eta ** r) for r in range(r_count)]
    total = sum(pops)
    if total > budget:                      # trim the cheap first rung
        pops[0] = max(1, pops[0] - (total - budget))
    pop = space.random_population(pops[0], rng)
    evals = 0
    all_F = []
    history: list[tuple[int, float]] = []
    F = None
    for r, (m, n_r) in enumerate(zip(sizes, pops)):
        with obs_trace.span("successive_halving.rung", rung=r,
                            subset=m, n=n_r):
            pop = pop[:n_r]
            F = ev.evaluate(pop, subset=None if m == L else m)
            evals += len(pop)
            if m == L:
                # only full-workload rows are comparable across runs;
                # subset-rung objectives live on a different scale and
                # must not leak into all_objectives / shared reference
                # points
                all_F.append(F)
            if r < r_count - 1:
                ranks, crowd = _ranks_and_crowding(F)
                order = np.lexsort((np.arange(len(pop)), -crowd, ranks))
                pop = pop[order]
    # the last rung ran on the full workload: its objectives are the
    # comparable ones
    ref = reference_point(F) if ref_point is None else ref_point
    history.append((evals, hypervolume(F[pareto_mask_k(F)], ref)))
    return _result("successive_halving", ev, seed, pop, F, ref, history,
                   all_F, evals)


SEARCH_METHODS = {
    "random": random_search,
    "nsga2": nsga2,
    "successive_halving": successive_halving,
}
