"""Telemetry smoke: instrumented sweep + search produce valid traces and
the disabled path stays free (the `telemetry-smoke` CI job).

Four gates:

* **trace validity** — an instrumented ``sweep_chunked`` + ``nsga2`` run
  exports Chrome ``trace_event`` JSON that passes the schema check and
  carries the expected stage spans (pull / synthesize / synthesis math /
  dispatch / kernel wait / reduce, per-generation spans, evaluate
  spans).
* **metrics content** — the registry snapshot after the run has
  per-stage times (``sweep.synth_s`` / ``sweep.kernel_wait_s`` /
  ``sweep.wall_s``), synthesis-cache hit/miss counters, and the evals/s
  inputs (``explore.requested_evals`` / ``explore.eval_seconds``).
* **bit-identity** — running the same sweep and search with telemetry
  enabled vs disabled yields byte-identical Pareto fronts and identical
  synthesis-cache hit/miss accounting.
* **overhead** — enabling telemetry costs <2% wall time on the sweep
  (min-of-N repeats, interleaved enabled/disabled so machine drift hits
  both arms, and up to three measurement rounds so one noisy round
  cannot fail the job).

Writes ``--out`` JSON and ``--trace-out`` (the Chrome trace, uploaded as
a CI artifact; load it at https://ui.perfetto.dev).

  PYTHONPATH=src python benchmarks/telemetry_smoke.py
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from dse_sweep_bench import provenance  # noqa: E402  (shared helper)

from repro import obs  # noqa: E402
from repro.core.accelerator import design_space_soa  # noqa: E402
from repro.core.dse import ExploreSpec, run  # noqa: E402
from repro.core.synthesis import PersistentSynthesisCache  # noqa: E402
from repro.core.workloads import get_workload  # noqa: E402

CHUNK = 1024
GRID = dict(glb_kbs=(64, 128, 256, 512),
            bws=tuple(float(b) for b in np.linspace(2.0, 64.0, 24)))

EXPECTED_SWEEP_SPANS = ("sweep_chunked", "sweep.pull", "sweep.synthesize",
                        "synth.model", "sweep.dispatch", "kernel.wait",
                        "sweep.reduce")
EXPECTED_SEARCH_SPANS = ("nsga2.generation", "explore.evaluate")


def _space():
    return design_space_soa(chunk_size=CHUNK, **GRID)


def _sweep(telemetry, cache=None):
    spec = ExploreSpec.single("vgg16", _space(), chunk_size=CHUNK,
                              backend="numpy", cache=cache,
                              save_cache=False, telemetry=telemetry)
    return run(spec)


def _search(telemetry):
    spec = ExploreSpec.mixed("vgg16", method="nsga2", budget=96,
                             seed=7, backend="numpy",
                             telemetry=telemetry, pop_size=16)
    return run(spec)


def instrumented_run(trace_out: pathlib.Path | None) -> tuple[dict, list]:
    """One instrumented sweep + nsga2; returns (report, failures)."""
    failures: list[str] = []
    obs.reset_metrics()
    obs.configure(enabled=True, reset=True)
    try:
        sweep = _sweep(telemetry=None)        # switch already on
        search = _search(telemetry=None)
    finally:
        obs.disable()

    doc = obs.export_chrome_trace(trace_out)
    problems = obs.validate_chrome_trace(doc)
    if problems:
        failures.append(f"chrome trace schema: {problems[:5]}")
    names = {e["name"] for e in doc["traceEvents"]}
    for want in EXPECTED_SWEEP_SPANS + EXPECTED_SEARCH_SPANS:
        if want not in names:
            failures.append(f"missing expected span {want!r}")
    # the exported file must round-trip as JSON
    if trace_out is not None:
        reloaded = json.loads(trace_out.read_text())
        if obs.validate_chrome_trace(reloaded):
            failures.append("trace JSON file failed schema after reload")

    snap = obs.snapshot()
    for key in ("sweep.wall_s", "sweep.synth_s", "sweep.kernel_wait_s",
                "sweep.chunks", "sweep.configs", "synth_cache.hits",
                "synth_cache.misses", "explore.requested_evals",
                "explore.kernel_evals", "explore.eval_seconds",
                "nsga2.generations"):
        if key not in snap:
            failures.append(f"metrics snapshot missing {key}")
    summary = obs.summarize(metrics=snap)
    derived = summary["derived"]
    for key in ("synth_cache_hit_rate", "sweep_configs_per_s",
                "explore_evals_per_s"):
        if key not in derived:
            failures.append(f"derived summary missing {key}")
    report = {
        "n_trace_events": len(doc["traceEvents"]),
        "span_names": sorted(names),
        "metrics": snap,
        "derived": derived,
        "sweep_front_size": sweep.front_size,
        "search_front_size": search.front_size,
        "search_eval_seconds": search.stats["eval_seconds"],
    }
    print(obs.render_text(summary), file=sys.stderr)
    return report, failures


def bit_identity() -> tuple[dict, list]:
    """Telemetry on vs off: identical fronts, identical cache counters."""
    failures: list[str] = []

    def sweep_with(telemetry):
        cache = PersistentSynthesisCache()
        res = _sweep(telemetry=telemetry, cache=cache)
        return res, {"hits": cache.hits, "misses": cache.misses}

    obs.disable()
    ref, ref_acct = sweep_with(False)
    ref_search = _search(False)
    on, on_acct = sweep_with(True)
    on_search = _search(True)

    front_identical = ref.front_size == on.front_size and all(
        np.array_equal(ref.front_metrics[m], on.front_metrics[m])
        for m in ref.front_metrics) and all(
        np.array_equal(ref.front_soa[k], on.front_soa[k])
        for k in ref.front_soa)
    if not front_identical:
        failures.append("sweep front changed when telemetry was enabled")
    if ref_acct != on_acct:
        failures.append(
            f"cache accounting changed under telemetry: {ref_acct} "
            f"vs {on_acct}")
    search_identical = (
        np.array_equal(ref_search.genomes, on_search.genomes)
        and np.array_equal(ref_search.front_objectives,
                           on_search.front_objectives))
    if not search_identical:
        failures.append("nsga2 front changed when telemetry was enabled")
    if obs.is_enabled():
        failures.append("ExploreSpec(telemetry=True) leaked: the global "
                        "switch is still on after run()")
    return {
        "front_identical": front_identical,
        "cache_accounting_identical": ref_acct == on_acct,
        "search_identical": search_identical,
        "cache_accounting": ref_acct,
    }, failures


def overhead_gate(limit: float, reps: int, rounds: int
                  ) -> tuple[dict, list]:
    """min-of-N wall time, telemetry on vs off, interleaved arms."""
    soa_all = list(_space())       # materialize once: feed cost is shared
    wl = get_workload("vgg16")

    from repro.core.dse_batch import _sweep_chunked

    def one(telemetry: bool) -> float:
        if telemetry:
            obs.configure(enabled=True, reset=True)
        else:
            obs.disable()
        try:
            t0 = time.perf_counter()
            _sweep_chunked(wl, iter(soa_all), chunk_size=CHUNK,
                           backend="numpy")
            return time.perf_counter() - t0
        finally:
            obs.disable()

    one(False)                     # warm page / allocator caches
    ratios = []
    for _ in range(rounds):
        best_off = best_on = float("inf")
        for _ in range(reps):      # interleave so drift hits both arms
            best_off = min(best_off, one(False))
            best_on = min(best_on, one(True))
        ratios.append(best_on / best_off)
        if ratios[-1] < limit:
            break
    failures = []
    if min(ratios) >= limit:
        failures.append(
            f"telemetry overhead {min(ratios):.4f}x >= {limit}x gate "
            f"(ratios per round: {[f'{r:.4f}' for r in ratios]})")
    return {"overhead_ratios": ratios, "overhead_best": min(ratios),
            "overhead_limit": limit}, failures


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", type=pathlib.Path,
                    default=pathlib.Path("/tmp/bench_telemetry_smoke.json"))
    ap.add_argument("--trace-out", type=pathlib.Path,
                    default=pathlib.Path("/tmp/telemetry_smoke_trace.json"))
    ap.add_argument("--overhead-limit", type=float, default=1.02)
    ap.add_argument("--overhead-reps", type=int, default=5)
    ap.add_argument("--overhead-rounds", type=int, default=3)
    ap.add_argument("--skip-overhead", action="store_true",
                    help="trace/metrics/bit-identity gates only")
    args = ap.parse_args()

    failures: list[str] = []
    r: dict = {"provenance": provenance()}

    rep, f = instrumented_run(args.trace_out)
    r.update(rep)
    failures += f

    rep, f = bit_identity()
    r.update(rep)
    failures += f

    if not args.skip_overhead:
        rep, f = overhead_gate(args.overhead_limit, args.overhead_reps,
                               args.overhead_rounds)
        r.update(rep)
        failures += f

    r["failures"] = failures
    args.out.write_text(json.dumps(r, indent=2, sort_keys=True,
                                   default=str) + "\n")
    print(f"trace events: {r['n_trace_events']}  "
          f"front sizes: sweep={r['sweep_front_size']} "
          f"search={r['search_front_size']}")
    print(f"bit-identity: front={r['front_identical']} "
          f"cache={r['cache_accounting_identical']} "
          f"search={r['search_identical']}")
    if "overhead_best" in r:
        print(f"overhead: {r['overhead_best']:.4f}x "
              f"(gate {r['overhead_limit']}x)")
    print(f"wrote {args.out} and {args.trace_out}")
    if failures:
        raise SystemExit("telemetry smoke FAILED:\n  "
                         + "\n  ".join(failures))
    print("telemetry smoke OK")


if __name__ == "__main__":
    main()
