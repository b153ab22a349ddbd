"""Serving-fleet DSE benchmark: traffic-aware objectives vs per-inference
EDP objectives at equal search budget.

For each traffic preset, runs the guided co-exploration engine under the
serving objective set (p99 latency under SLO, energy per served token,
quantization noise) and under the per-inference EDP set, then reports:

* evaluation throughput (genomes/s through the fused kernel + fleet sim),
* the *front shift*: whether the serving-fleet Pareto front selects a
  different genome set than the per-inference front (the paper-level
  claim that queueing pressure changes which designs win),
* numpy vs jax front parity (<= 1e-6 on objectives, identical genomes),
* raw fleet-simulator throughput (candidate-traces/s).

Emits ``BENCH_serving_dse.json`` so the trajectory is tracked across PRs.

  PYTHONPATH=src python benchmarks/serving_dse_bench.py [--quick]
      [--workload vgg16] [--out BENCH_serving_dse.json]
      [--check-against BENCH_serving_dse.json]

``--quick`` is the CI smoke mode.  ``--check-against`` fails on a >3x
evals/s regression vs the committed baseline; the front-shift
requirement (serving front != EDP front on >= 1 preset) is always
enforced.
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
from repro.core.synthesis import clear_synthesis_cache  # noqa: E402
from repro.serving.fleet_sim import simulate_fleet  # noqa: E402
from repro.serving.traffic import make_trace  # noqa: E402

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_serving_dse.json"

SERVING_OBJS = ("p99_latency_s", "energy_per_token_j", "accuracy_noise")
EDP_OBJS = ("edp", "accuracy_noise")


def _genome_set(res) -> set:
    return {g.tobytes() for g in res.genomes}


def _campaign(workload: str, budget: int, pop: int, seed: int,
              backend: str, *, traffic: str | None,
              objectives) -> tuple[object, float]:
    clear_synthesis_cache()
    t0 = time.perf_counter()
    res = run_spec(ExploreSpec.mixed(
        workload, preset="quick", budget=budget, seed=seed,
        backend=backend, objectives=objectives, traffic=traffic,
        pop_size=pop))
    return res, time.perf_counter() - t0


def bench_fleet_sim(n_candidates: int = 256, preset: str = "steady") -> dict:
    """Raw simulator throughput over an (N, R) grid."""
    rng = np.random.default_rng(0)
    step = rng.uniform(0.02, 0.9, n_candidates)
    etok = rng.uniform(0.3, 3.0, n_candidates)
    trace = make_trace(preset)
    simulate_fleet(step[:2], etok[:2], trace)          # warm-up
    t0 = time.perf_counter()
    res = simulate_fleet(step, etok, trace)
    dt = time.perf_counter() - t0
    return {
        "fleet_sim_candidates": n_candidates,
        "fleet_sim_requests": trace.n_requests,
        "fleet_sim_s": dt,
        "fleet_sim_candidates_per_s": n_candidates / dt,
        "fleet_sim_horizon_iters": res.n_iters,
    }


def bench(workload: str = "vgg16", quick: bool = False,
          seed: int = 0, with_jax: bool = True) -> dict:
    budget = 256 if quick else 1024
    pop = 24 if quick else 48
    presets = ["quick"] if quick else ["steady", "bursty", "interactive"]
    jax_ok = False
    if with_jax:
        try:
            resolve_backend("jax")
            jax_ok = True
        except RuntimeError:
            pass

    out: dict = {
        "workload": workload, "quick": quick, "seed": seed,
        "budget": budget, "pop_size": pop, "presets": presets,
        "provenance": provenance(),
    }
    out.update(bench_fleet_sim(n_candidates=64 if quick else 256))

    # the per-inference EDP baseline front, shared across presets
    res_edp, dt_edp = _campaign(workload, budget, pop, seed, "numpy",
                                traffic=None, objectives=EDP_OBJS)
    out["edp_evals_per_s"] = res_edp.n_evals / dt_edp
    out["edp_front_size"] = res_edp.front_size
    edp_genomes = _genome_set(res_edp)

    shifted = []
    for preset in presets:
        res_s, dt_s = _campaign(workload, budget, pop, seed, "numpy",
                                traffic=preset, objectives=SERVING_OBJS)
        shift = _genome_set(res_s) != edp_genomes
        shifted.append(shift)
        out[f"{preset}_evals_per_s"] = res_s.n_evals / dt_s
        out[f"{preset}_front_size"] = res_s.front_size
        out[f"{preset}_front_shifted_vs_edp"] = bool(shift)
        if preset == presets[0]:
            out["serving_evals_per_s"] = out[f"{preset}_evals_per_s"]
            if jax_ok:
                res_j, dt_j = _campaign(workload, budget, pop, seed,
                                        "jax", traffic=preset,
                                        objectives=SERVING_OBJS)
                out["serving_jax_evals_per_s"] = res_j.n_evals / dt_j
                same = (res_j.genomes.shape == res_s.genomes.shape
                        and bool(np.array_equal(
                            np.sort(res_j.genomes, axis=0),
                            np.sort(res_s.genomes, axis=0))))
                a, b = res_s.front_objectives, res_j.front_objectives
                if same and a.shape == b.shape:
                    denom = np.where(a == 0, 1.0, a)
                    rel = float(np.max(np.abs(b / denom - 1.0))) \
                        if a.size else 0.0
                else:
                    rel = float("inf")
                out["serving_jax_front_matches_numpy"] = same
                out["serving_jax_front_rel_err"] = rel

    out["front_shift_presets"] = int(sum(shifted))
    out["front_shift_claim"] = bool(any(shifted))

    if not quick:
        # quick-mode numbers recorded by full runs keep the CI regression
        # gate like-for-like (see check_against)
        q = bench(workload=workload, quick=True, seed=seed,
                  with_jax=False)
        out["quick_serving_evals_per_s"] = q["serving_evals_per_s"]
        out["quick_edp_evals_per_s"] = q["edp_evals_per_s"]
    return out


def check_against(r: dict, baseline_path: pathlib.Path) -> None:
    """CI gate: >3x serving evals/s regression vs the committed baseline
    fails (same pattern as the sweep/coexplore benches)."""
    base = json.loads(baseline_path.read_text())
    if r["quick"] and "quick_serving_evals_per_s" in base:
        base_eps = base["quick_serving_evals_per_s"]
        label = "quick baseline"
    else:
        base_eps = base["serving_evals_per_s"]
        label = "baseline"
    got = r["serving_evals_per_s"]
    print(f"regression check: serving {got:.0f} evals/s vs {label} "
          f"{base_eps:.0f} (floor {base_eps / 3:.0f})")
    if got * 3.0 < base_eps:
        raise SystemExit(
            f"serving DSE regressed >3x: {got:.0f} evals/s vs "
            f"{label} {base_eps:.0f}")


def run():
    """benchmarks/run.py entry: CSV rows (name, us_per_call, derived)."""
    r = bench(quick=True)
    return [
        ("serving/nsga2", 1e6 / r["serving_evals_per_s"],
         f"evals_per_s={r['serving_evals_per_s']:.0f}"),
        ("serving/edp_baseline", 1e6 / r["edp_evals_per_s"],
         f"evals_per_s={r['edp_evals_per_s']:.0f}"),
        ("serving/fleet_sim", 1e6 / r["fleet_sim_candidates_per_s"],
         f"candidates_per_s={r['fleet_sim_candidates_per_s']:.0f}"),
        ("serving/front_shift", 0.0,
         f"presets_shifted={r['front_shift_presets']}"),
    ]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced budget (CI smoke mode)")
    ap.add_argument("--workload", default="vgg16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--check-against", type=pathlib.Path, default=None,
                    help="baseline BENCH json; fail on >3x regression")
    args = ap.parse_args()

    r = bench(workload=args.workload, quick=args.quick, seed=args.seed)
    args.out.write_text(json.dumps(r, indent=2, sort_keys=True) + "\n")

    print(f"workload: {r['workload']}  budget: {r['budget']} evals"
          f"{'  (quick)' if r['quick'] else ''}")
    print(f"fleet sim: {r['fleet_sim_candidates_per_s']:.0f} candidate-"
          f"traces/s over {r['fleet_sim_horizon_iters']} iterations")
    print(f"edp baseline: {r['edp_evals_per_s']:.0f} evals/s  "
          f"front={r['edp_front_size']}")
    for preset in r["presets"]:
        print(f"{preset:12s} {r[f'{preset}_evals_per_s']:9.0f} evals/s  "
              f"front={r[f'{preset}_front_size']}  "
              f"shifted={r[f'{preset}_front_shifted_vs_edp']}")
    if "serving_jax_front_matches_numpy" in r:
        print(f"jax parity: genomes match={r['serving_jax_front_matches_numpy']}  "
              f"rel err={r['serving_jax_front_rel_err']:.2g}")
    print(f"wrote {args.out}")

    if args.check_against is not None:
        check_against(r, args.check_against)
    if not r["front_shift_claim"]:
        raise SystemExit(
            "serving-fleet front matched the per-inference EDP front on "
            "every preset — traffic-aware objectives made no difference")
    if r.get("serving_jax_front_rel_err", 0.0) > 1e-6:
        raise SystemExit(
            f"numpy/jax serving front parity broke: rel err "
            f"{r['serving_jax_front_rel_err']:.3g} > 1e-6")


if __name__ == "__main__":
    main()
