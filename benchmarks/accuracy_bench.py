"""Accuracy-tier benchmark: proxy vs calibrated vs measured (ISSUE 10).

Measures the cost of each accuracy tier and gates the claims the tiered
subsystem makes:

* **tier-1 fidelity** — Spearman rank correlation between tier-0 proxy
  and tier-1 calibrated scores over 512 random genomes must stay >= 0.8
  (the calibrated table refines the proxy, it does not contradict it);
* **tier-1 cost** — cold calibration wall time (real zoo tensors through
  the real quantizers) and the npz-cache hit on re-run (a warm load must
  actually hit the cache, and costs ~ms);
* **front shift** — the committed ``calibrated-quick`` preset must select
  a different Pareto-front membership than the proxy ``quick`` campaign
  at the same seed/budget;
* **tier-2 cost** — quantized-forward elite validation on the smallest
  zoo model must finish in under 120 s;
* **backend parity** — an nsga2 campaign under the calibrated table is
  bit-identical between the numpy and jax evaluation backends.

Emits ``BENCH_accuracy.json`` so the trajectory is tracked across PRs.

  PYTHONPATH=src python benchmarks/accuracy_bench.py [--quick]
      [--out BENCH_accuracy.json] [--check-against BENCH_accuracy.json]
      [--regen-golden]

``--check-against`` additionally fails on a >3x cold-calibration slowdown
vs the committed baseline; ``--regen-golden`` rewrites
``tests/golden_calibrated_front.json`` from the committed preset.
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

from repro.core.dse import ExploreSpec, run as run_spec  # noqa: E402
from repro.core.dse_batch import resolve_backend  # noqa: E402
from repro.core.workloads import get_workload  # noqa: E402
from repro.explore.accuracy import (AccuracySpec,  # noqa: E402
                                    CalibratedAccuracy, validate_elites)
from repro.explore.objectives import quant_noise  # noqa: E402
from repro.explore.search import nsga2  # noqa: E402
from repro.explore.space import space_for_workload  # noqa: E402
from repro.quant.calibrate import (calibrate_model,  # noqa: E402
                                   calibration_cache_stats,
                                   reset_calibration_cache_stats)

REPO = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_OUT = REPO / "BENCH_accuracy.json"
GOLDEN = REPO / "tests" / "golden_calibrated_front.json"

MODEL = "mamba2-130m"                  # smallest zoo config
SPEARMAN_FLOOR = 0.8
TIER2_BUDGET_S = 120.0


def spearman(a: np.ndarray, b: np.ndarray) -> float:
    """Spearman rank correlation with average ranks for ties (Pearson of
    the rank vectors) — no scipy dependency."""
    def avg_ranks(x: np.ndarray) -> np.ndarray:
        order = np.argsort(x, kind="mergesort")
        xs = x[order]
        r = np.empty(len(x), dtype=np.float64)
        i = 0
        while i < len(xs):
            j = i
            while j + 1 < len(xs) and xs[j + 1] == xs[i]:
                j += 1
            r[order[i:j + 1]] = 0.5 * (i + j)
            i = j + 1
        return r

    ra, rb = avg_ranks(np.asarray(a)), avg_ranks(np.asarray(b))
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra ** 2).sum() * (rb ** 2).sum())
    return float((ra * rb).sum() / max(denom, 1e-300))


def bench(quick: bool = False, seed: int = 0, with_jax: bool = True) -> dict:
    wl_name = "vgg16"
    wl = get_workload(wl_name)
    space = space_for_workload(wl)
    macs = np.array([l.macs for l in wl.layers], dtype=np.float64)

    out: dict = {"quick": quick, "seed": seed, "model": MODEL,
                 "workload": wl_name, "provenance": provenance()}

    # -- tier-1 calibration cost + cache hit on re-run ----------------------
    t0 = time.perf_counter()
    tab = calibrate_model(MODEL, refresh=True)      # cold: real measurement
    out["calibrate_cold_s"] = time.perf_counter() - t0
    reset_calibration_cache_stats()
    t0 = time.perf_counter()
    tab2 = calibrate_model(MODEL)
    out["calibrate_warm_s"] = time.perf_counter() - t0
    stats = calibration_cache_stats()
    out["cache_hit_on_rerun"] = stats == {"hits": 1, "misses": 0}
    out["calibration_digest"] = tab.digest()
    out["calibration_layers"] = tab.n_layers
    assert tab2.digest() == tab.digest()

    # -- tier-1 vs tier-0 rank fidelity on 512 genomes ----------------------
    cal = CalibratedAccuracy(AccuracySpec(tier=1, model=MODEL))
    n_genomes = 512
    _, assign = space.decode(space.random_population(
        n_genomes, np.random.default_rng(seed)))
    t0 = time.perf_counter()
    s0 = quant_noise(assign, macs)
    out["tier0_score_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    s1 = cal.score(assign, macs)
    out["tier1_score_s"] = time.perf_counter() - t0
    out["n_genomes"] = n_genomes
    out["tier1_vs_tier0_spearman"] = spearman(s0, s1)

    # -- front shift: the committed calibrated-quick preset -----------------
    res_cal = run_spec(ExploreSpec.mixed(wl_name, preset="calibrated-quick",
                                         seed=seed, backend="numpy"))
    res_prox = run_spec(ExploreSpec.mixed(wl_name, preset="quick",
                                          seed=seed, backend="numpy"))
    keys_cal = set(res_cal.space.genome_keys(res_cal.genomes))
    keys_prox = set(res_prox.space.genome_keys(res_prox.genomes))
    out["calibrated_front_size"] = len(keys_cal)
    out["proxy_front_size"] = len(keys_prox)
    out["front_membership_differs"] = keys_cal != keys_prox
    out["front_jaccard"] = (len(keys_cal & keys_prox)
                            / max(1, len(keys_cal | keys_prox)))

    # -- tier 2: quantized-forward elite validation -------------------------
    budget, pop = (96, 12) if quick else (384, 24)
    spec2 = AccuracySpec(tier=2, model=MODEL, max_elites=8)
    res2 = nsga2(space, wl, budget, pop_size=pop, seed=seed,
                 backend="numpy", accuracy=spec2)
    t0 = time.perf_counter()
    v = validate_elites(res2, spec2)
    out["tier2_validation_s"] = time.perf_counter() - t0
    out["tier2_n_elites"] = int(len(v.elite_indices))
    out["tier2_baseline_loss"] = float(v.baseline_loss)
    out["tier2_max_loss_delta"] = float(v.loss_delta.max())
    out["tier2_n_surviving"] = int(v.pareto_mask.sum())
    out["tier2_within_budget"] = out["tier2_validation_s"] < TIER2_BUDGET_S

    # -- backend parity under the calibrated table --------------------------
    if with_jax:
        try:
            resolve_backend("jax")
        except RuntimeError:
            pass
        else:
            res_np = nsga2(space, wl, budget, pop_size=pop, seed=seed,
                           backend="numpy", accuracy=cal)
            res_jx = nsga2(space, wl, budget, pop_size=pop, seed=seed,
                           backend="jax", accuracy=cal)

            def row_sorted(g):
                return g[np.lexsort(g.T[::-1])]

            out["jax_front_matches_numpy"] = (
                res_np.genomes.shape == res_jx.genomes.shape
                and bool(np.array_equal(row_sorted(res_np.genomes),
                                        row_sorted(res_jx.genomes))))
    return out


def regen_golden(seed: int = 0) -> None:
    """Rewrite tests/golden_calibrated_front.json from the committed
    ``calibrated-quick`` preset (run after an intentional change to the
    calibrator, the quantizers, or the search engine)."""
    res = run_spec(ExploreSpec.mixed("vgg16", preset="calibrated-quick",
                                     seed=seed, backend="numpy"))
    prox = run_spec(ExploreSpec.mixed("vgg16", preset="quick", seed=seed,
                                      backend="numpy"))
    ck = set(res.space.genome_keys(res.genomes))
    pk = set(prox.space.genome_keys(prox.genomes))
    if ck == pk:
        raise SystemExit("calibrated-quick front membership no longer "
                         "differs from the proxy's — the golden claim "
                         "would be vacuous; investigate before committing")
    golden = {
        "preset": "calibrated-quick", "workload": "vgg16", "seed": seed,
        "backend": "numpy", "pop_size": 24, "budget": 384,
        "objectives": list(res.objectives),
        "calibration_digest": calibrate_model(MODEL).digest(),
        "front_genomes_u16": res.space.pack_genomes(res.genomes).tolist(),
        "front_objectives": np.asarray(res.front_objectives).tolist(),
    }
    GOLDEN.write_text(json.dumps(golden, indent=1) + "\n")
    print(f"wrote {GOLDEN} ({len(golden['front_genomes_u16'])} front "
          f"genomes, symm-diff vs proxy {len(ck ^ pk)})")


def check_against(r: dict, baseline_path: pathlib.Path) -> None:
    """CI gate: >3x cold-calibration slowdown vs the committed baseline
    fails (same pattern as the other benches)."""
    base = json.loads(baseline_path.read_text())
    base_s, got_s = base["calibrate_cold_s"], r["calibrate_cold_s"]
    print(f"regression check: cold calibration {got_s:.2f}s vs baseline "
          f"{base_s:.2f}s (ceiling {base_s * 3:.2f}s)")
    if got_s > base_s * 3.0:
        raise SystemExit(
            f"tier-1 calibration regressed >3x: {got_s:.2f}s vs "
            f"baseline {base_s:.2f}s")


def enforce_gates(r: dict) -> None:
    """The accuracy-smoke claims, enforced on every run (no baseline
    needed: these are absolute contracts, not throughput trends)."""
    if r["tier1_vs_tier0_spearman"] < SPEARMAN_FLOOR:
        raise SystemExit(
            f"tier-1/tier-0 Spearman {r['tier1_vs_tier0_spearman']:.3f} "
            f"fell below {SPEARMAN_FLOOR}: the calibrated table "
            f"contradicts the proxy ordering")
    if not r["cache_hit_on_rerun"]:
        raise SystemExit("calibration npz cache missed on re-run")
    if not r["front_membership_differs"]:
        raise SystemExit("calibrated-quick selected the same front as the "
                         "proxy — the tier-1 signal is not reaching the "
                         "search")
    if not r["tier2_within_budget"]:
        raise SystemExit(
            f"tier-2 elite validation took {r['tier2_validation_s']:.1f}s "
            f"(budget {TIER2_BUDGET_S:.0f}s)")
    if not r.get("jax_front_matches_numpy", True):
        raise SystemExit("calibrated nsga2 front differs between numpy "
                         "and jax backends")


def run():
    """benchmarks/run.py entry: CSV rows (name, us_per_call, derived)."""
    r = bench(quick=True)
    enforce_gates(r)
    return [
        ("accuracy/calibrate_cold", r["calibrate_cold_s"] * 1e6,
         f"layers={r['calibration_layers']}"),
        ("accuracy/calibrate_warm", r["calibrate_warm_s"] * 1e6,
         f"cache_hit={r['cache_hit_on_rerun']}"),
        ("accuracy/tier1_score_512", r["tier1_score_s"] * 1e6,
         f"spearman={r['tier1_vs_tier0_spearman']:.3f}"),
        ("accuracy/tier2_validate", r["tier2_validation_s"] * 1e6,
         f"elites={r['tier2_n_elites']}"),
    ]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced tier-2 campaign (CI smoke mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--check-against", type=pathlib.Path, default=None,
                    help="baseline BENCH json; fail on >3x regression")
    ap.add_argument("--regen-golden", action="store_true",
                    help="rewrite tests/golden_calibrated_front.json")
    args = ap.parse_args()

    if args.regen_golden:
        regen_golden(seed=args.seed)
        return

    r = bench(quick=args.quick, seed=args.seed)
    args.out.write_text(json.dumps(r, indent=2, sort_keys=True) + "\n")

    print(f"model: {r['model']}  ({r['calibration_layers']} layers)"
          f"{'  (quick)' if r['quick'] else ''}")
    print(f"calibrate  cold {r['calibrate_cold_s'] * 1e3:8.1f} ms   "
          f"warm {r['calibrate_warm_s'] * 1e3:6.1f} ms   "
          f"cache hit: {r['cache_hit_on_rerun']}")
    print(f"tier1 vs tier0 on {r['n_genomes']} genomes: "
          f"spearman {r['tier1_vs_tier0_spearman']:.3f}   "
          f"(score {r['tier1_score_s'] * 1e3:.1f} ms vs "
          f"{r['tier0_score_s'] * 1e3:.1f} ms)")
    print(f"front shift (calibrated-quick vs quick): "
          f"{r['calibrated_front_size']} vs {r['proxy_front_size']} "
          f"genomes, jaccard {r['front_jaccard']:.3f}, "
          f"differs: {r['front_membership_differs']}")
    print(f"tier2 validation: {r['tier2_validation_s']:.1f} s for "
          f"{r['tier2_n_elites']} elites "
          f"({r['tier2_n_surviving']} survive measured re-scoring)")
    if "jax_front_matches_numpy" in r:
        print(f"jax front matches numpy: {r['jax_front_matches_numpy']}")
    print(f"wrote {args.out}")

    if args.check_against is not None:
        check_against(r, args.check_against)
    enforce_gates(r)


if __name__ == "__main__":
    main()
