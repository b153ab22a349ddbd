"""Multi-workload co-exploration benchmark: guided (NSGA-II + external
archive) vs random search at equal evaluation budget over the joint
(shared hardware config x per-workload, per-layer precision) space — the
full QUIDAM setting over the paper's three workloads.

Measures evaluation throughput (genomes/s through the fused W-workload
kernel `sweep_mixed_many`), the hypervolume each method reaches under one
shared reference point, the synthesis-cache hit rate the shared-hardware
genome encoding achieves (one synthesis pass serves all W workloads per
hardware config), and whether the NSGA-II external archive supersets the
final population's non-dominated set.  Emits
``BENCH_coexplore_many.json`` so the trajectory is tracked across PRs.

  PYTHONPATH=src python benchmarks/coexplore_many_bench.py [--quick]
      [--workloads vgg16 resnet34 resnet50]
      [--out BENCH_coexplore_many.json]
      [--check-against BENCH_coexplore_many.json]

``--quick`` is the CI smoke mode.  ``--check-against`` fails on a >3x
evals/s regression vs the committed baseline; the guided >= random
hypervolume requirement and the archive-superset invariant are always
enforced, and full runs additionally require a synthesis-cache hit rate
>= 80%.
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

from repro.core.dse import coexplore_many  # noqa: E402
from repro.core.dse_batch import resolve_backend  # noqa: E402
from repro.core.synthesis import (clear_synthesis_cache,  # noqa: E402
                                  synthesis_cache_stats)
from repro.explore.pareto import (hypervolume, pareto_mask_k,  # noqa: E402
                                  reference_point)

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_coexplore_many.json"
MIN_HIT_RATE = 0.80


def bench_method(method: str, workloads, budget: int, seed: int,
                 backend: str, **kwargs) -> tuple[dict, object]:
    clear_synthesis_cache()
    t0 = time.perf_counter()
    res = coexplore_many(workloads, preset="many-default", method=method,
                         budget=budget, seed=seed, backend=backend,
                         **kwargs)
    dt = time.perf_counter() - t0
    stats = synthesis_cache_stats()
    hits, misses = stats["array_hits"], stats["array_misses"]
    return {
        f"{method}_s": dt,
        f"{method}_evals_per_s": res.n_evals / dt,
        f"{method}_front_size": res.front_size,
        f"{method}_kernel_evals": res.stats["kernel_evals"],
        f"{method}_memo_hits": res.stats["memo_hits"],
        f"{method}_synth_cache_hits": hits,
        f"{method}_synth_cache_misses": misses,
        f"{method}_synth_cache_hit_rate": hits / max(1, hits + misses),
        f"{method}_history": [[int(e), float(h)] for e, h in res.history],
    }, res


def _archive_supersets_population(res) -> bool:
    """The archive is a superset of the final population's non-dominated
    set: judging dominance over archive ∪ population (a population member
    beaten by an archived genome from an earlier generation *is*
    dominated), every surviving genome already sits in the archive —
    i.e. the population adds nothing the archive lost."""
    if res.population is None:
        return False
    comb_g = np.concatenate([res.genomes, res.population])
    comb_F = np.concatenate([res.front_objectives,
                             res.population_objectives])
    keep = pareto_mask_k(comb_F)
    return all((res.genomes == row).all(axis=1).any()
               for row in comb_g[keep])


def bench(workloads=("vgg16", "resnet34", "resnet50"), quick: bool = False,
          seed: int = 0, with_jax: bool = True) -> dict:
    budget = 384 if quick else 3072
    pop = 24 if quick else 64
    backends = ["numpy"]
    if with_jax:
        try:
            resolve_backend("jax")
            backends.append("jax")
        except RuntimeError:
            pass

    out: dict = {
        "workloads": list(workloads), "quick": quick, "seed": seed,
        "budget": budget, "pop_size": pop,
        "provenance": provenance(),
    }
    rows_r, res_r = bench_method("random", workloads, budget, seed,
                                 "numpy")
    rows_n, res_n = bench_method("nsga2", workloads, budget, seed,
                                 "numpy", pop_size=pop)
    out.update(rows_r)
    out.update(rows_n)
    out["archive_supersets_population_front"] = \
        _archive_supersets_population(res_n)

    # one shared reference point -> comparable hypervolumes
    ref = reference_point(np.concatenate([res_r.all_objectives,
                                          res_n.all_objectives]))
    hv_r = hypervolume(res_r.front_objectives, ref)
    hv_n = hypervolume(res_n.front_objectives, ref)
    out.update(
        shared_ref_point=[float(x) for x in ref],
        random_hypervolume=hv_r,
        nsga2_hypervolume=hv_n,
        nsga2_vs_random_hypervolume=hv_n / max(hv_r, 1e-300),
        guided_beats_random=bool(hv_n >= hv_r),
    )

    if "jax" in backends:
        rows_j, res_j = bench_method("nsga2", workloads, budget, seed,
                                     "jax", pop_size=pop)
        out["nsga2_jax_evals_per_s"] = rows_j["nsga2_evals_per_s"]
        out["nsga2_jax_s"] = rows_j["nsga2_s"]

        def _row_sorted(g):
            return g[np.lexsort(g.T[::-1])]

        same_front = (res_j.genomes.shape == res_n.genomes.shape
                      and bool(np.array_equal(_row_sorted(res_j.genomes),
                                              _row_sorted(res_n.genomes))))
        out["nsga2_jax_front_matches_numpy"] = same_front

        # sharded evaluation: the same search with every population chunk
        # spread across all devices via shard_map (1 device degenerates to
        # the plain jit path — the multi-device-smoke CI job runs this
        # with 4 forced host devices)
        import jax
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh()
        rows_s, res_s = bench_method("nsga2", workloads, budget, seed,
                                     "jax", pop_size=pop, mesh=mesh)
        out["n_devices"] = jax.device_count()
        out["nsga2_jax_sharded_evals_per_s"] = rows_s["nsga2_evals_per_s"]
        out["nsga2_jax_sharded_s"] = rows_s["nsga2_s"]
        out["nsga2_jax_sharded_front_matches_numpy"] = (
            res_s.genomes.shape == res_n.genomes.shape
            and bool(np.array_equal(_row_sorted(res_s.genomes),
                                    _row_sorted(res_n.genomes))))

    if not quick:
        # quick-mode numbers recorded by full runs keep the CI regression
        # gate like-for-like (see check_against)
        q = bench(workloads=workloads, quick=True, seed=seed,
                  with_jax=False)
        out["quick_nsga2_evals_per_s"] = q["nsga2_evals_per_s"]
        out["quick_random_evals_per_s"] = q["random_evals_per_s"]
    return out


def check_against(r: dict, baseline_path: pathlib.Path) -> None:
    """CI gate: >3x evals/s regression vs the committed baseline fails
    (same pattern as the sweep benches)."""
    base = json.loads(baseline_path.read_text())
    if r["quick"] and "quick_nsga2_evals_per_s" in base:
        base_eps = base["quick_nsga2_evals_per_s"]
        label = "quick baseline"
    else:
        base_eps = base["nsga2_evals_per_s"]
        label = "baseline"
    got = r["nsga2_evals_per_s"]
    print(f"regression check: nsga2 {got:.0f} evals/s vs {label} "
          f"{base_eps:.0f} (floor {base_eps / 3:.0f})")
    if got * 3.0 < base_eps:
        raise SystemExit(
            f"multi-workload co-exploration regressed >3x: {got:.0f} "
            f"evals/s vs {label} {base_eps:.0f}")


def run():
    """benchmarks/run.py entry: CSV rows (name, us_per_call, derived)."""
    r = bench(quick=True)
    return [
        ("coexplore_many/random", 1e6 / r["random_evals_per_s"],
         f"evals_per_s={r['random_evals_per_s']:.0f}"),
        ("coexplore_many/nsga2", 1e6 / r["nsga2_evals_per_s"],
         f"evals_per_s={r['nsga2_evals_per_s']:.0f}"),
        ("coexplore_many/hv_ratio", 0.0,
         f"{r['nsga2_vs_random_hypervolume']:.3f}"),
        ("coexplore_many/cache_hit_rate", 0.0,
         f"{r['nsga2_synth_cache_hit_rate']:.3f}"),
    ]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced budget (CI smoke mode)")
    ap.add_argument("--workloads", nargs="+",
                    default=["vgg16", "resnet34", "resnet50"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--check-against", type=pathlib.Path, default=None,
                    help="baseline BENCH json; fail on >3x regression")
    args = ap.parse_args()

    r = bench(workloads=tuple(args.workloads), quick=args.quick,
              seed=args.seed)
    args.out.write_text(json.dumps(r, indent=2, sort_keys=True) + "\n")

    print(f"workloads: {'+'.join(r['workloads'])}  budget: {r['budget']} "
          f"evals{'  (quick)' if r['quick'] else ''}")
    for m in ("random", "nsga2"):
        print(f"{m:6s}  {r[f'{m}_s'] * 1e3:8.1f} ms  "
              f"{r[f'{m}_evals_per_s']:9.0f} evals/s  "
              f"front={r[f'{m}_front_size']}  "
              f"cache hit rate={r[f'{m}_synth_cache_hit_rate']:.1%}")
    if "nsga2_jax_evals_per_s" in r:
        print(f"nsga2 (jax) {r['nsga2_jax_s'] * 1e3:6.1f} ms  "
              f"{r['nsga2_jax_evals_per_s']:9.0f} evals/s  "
              f"front matches numpy: "
              f"{r['nsga2_jax_front_matches_numpy']}")
    if "nsga2_jax_sharded_evals_per_s" in r:
        print(f"nsga2 (jax, {r['n_devices']}-device mesh) "
              f"{r['nsga2_jax_sharded_s'] * 1e3:6.1f} ms  "
              f"{r['nsga2_jax_sharded_evals_per_s']:9.0f} evals/s  "
              f"front matches numpy: "
              f"{r['nsga2_jax_sharded_front_matches_numpy']}")
    print(f"hypervolume (shared ref): nsga2 {r['nsga2_hypervolume']:.5g} "
          f"vs random {r['random_hypervolume']:.5g}  "
          f"({r['nsga2_vs_random_hypervolume']:.3f}x)")
    print(f"archive supersets population front: "
          f"{r['archive_supersets_population_front']}")
    print(f"wrote {args.out}")

    if args.check_against is not None:
        check_against(r, args.check_against)
    if not r["guided_beats_random"]:
        raise SystemExit(
            "guided search fell below the random baseline hypervolume: "
            f"{r['nsga2_hypervolume']:.5g} < {r['random_hypervolume']:.5g}")
    if not r["archive_supersets_population_front"]:
        raise SystemExit(
            "NSGA-II external archive dropped a non-dominated genome from "
            "the final population")
    if not r.get("nsga2_jax_sharded_front_matches_numpy", True):
        raise SystemExit(
            f"mesh-sharded nsga2 front diverged from the numpy front "
            f"({r.get('n_devices')} device(s))")
    if not r["quick"] and r["nsga2_synth_cache_hit_rate"] < MIN_HIT_RATE:
        raise SystemExit(
            f"synthesis-cache hit rate "
            f"{r['nsga2_synth_cache_hit_rate']:.1%} < "
            f"{MIN_HIT_RATE:.0%}: the shared-hardware genome encoding is "
            f"no longer reusing synthesis across workloads")


if __name__ == "__main__":
    main()
