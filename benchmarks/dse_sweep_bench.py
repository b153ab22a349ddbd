"""DSE sweep engine benchmark: scalar loop vs vectorized batched engine
vs the streamed chunked driver.

Times `explore()` over the full paper design space on a paper workload
with both engines, exercises the x64-free jax jit path and the 100k-config
chunked stream, checks the headline ratios are identical, and emits
``BENCH_dse_sweep.json`` (configs/sec + speedups + provenance) so the perf
trajectory is tracked across PRs and machines.

  PYTHONPATH=src python benchmarks/dse_sweep_bench.py [--quick]
      [--workload vgg16] [--out BENCH_dse_sweep.json]
      [--check-against BENCH_dse_sweep.json]

``--quick`` shrinks the design space and repetitions — the CI smoke mode
that exercises the engine without holding the queue.  ``--check-against``
compares the measured cold throughput to a committed baseline and fails
on a >3x regression.
"""

from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import platform
import subprocess
import sys
import time

import numpy as np

from repro.core.accelerator import design_space, design_space_soa
from repro.core.dse import explore, explore_many, explore_scalar
from repro.core.dse_batch import resolve_backend, sweep_chunked
from repro.core.synthesis import clear_synthesis_cache, synthesis_cache_stats
from repro.core.workloads import get_workload

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_dse_sweep.json"

# widened factor grid for the chunked-scaling entry (~103k configs full,
# ~15k quick); everything else stays the paper's 720-point space
_CHUNKED_FULL = dict(glb_kbs=tuple(2 ** i for i in range(2, 13)),
                     bws=tuple(np.linspace(2.0, 64.0, 156)))
_CHUNKED_QUICK = dict(glb_kbs=(64, 128, 256, 512),
                      bws=tuple(np.linspace(2.0, 64.0, 64)))


def _best_of(fn, reps: int) -> float:
    import gc
    best = float("inf")
    was_enabled = gc.isenabled()
    gc.collect()
    gc.disable()                 # keep collector pauses out of the timings
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
    finally:
        if was_enabled:
            gc.enable()
    return best


def provenance() -> dict:
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=pathlib.Path(__file__).parent
                             ).stdout.strip() or None
    except Exception:
        sha = None
    try:
        import jax
        jax_version = jax.__version__
        n_devices = jax.device_count()
    except Exception:
        jax_version = None
        n_devices = None
    import os
    from repro.obs import snapshot as obs_snapshot
    return {
        "python": platform.python_version(),
        "numpy": np.__version__,
        "jax": jax_version,
        "jax_device_count": n_devices,
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
        "git_sha": sha,
        # process-wide telemetry counters at provenance time: cache
        # hits/misses, chunks/configs streamed, evals/s inputs — lands in
        # every BENCH_*.json that embeds provenance()
        "metrics": obs_snapshot(),
    }


def bench_chunked(workload: str, quick: bool) -> dict:
    """Streamed sweep throughput over the widened grid (no per-config
    Python objects anywhere: SoA chunks in, Pareto front out) — the
    serial per-chunk loop vs the double-buffered pipeline (synthesize
    chunk i+1 on the host while the kernel maps chunk i), with identical
    fronts asserted and the overlap fraction recorded."""
    wl = get_workload(workload)
    grid = _CHUNKED_QUICK if quick else _CHUNKED_FULL
    # quick mode streams ~15k configs: a small chunk keeps several chunks
    # in flight so the smoke run exercises the double-buffered pipeline
    chunk_size = 4096 if quick else 32768

    def space():
        return design_space_soa(chunk_size=chunk_size, **grid)

    n = sum(len(s["pe_rows"]) for s in space())
    out: dict = {"chunked_n_configs": n, "chunked_chunk_size": chunk_size}
    backends = ["numpy"]
    try:
        resolve_backend("jax")
        backends.append("jax")
    except RuntimeError:
        pass
    for backend in backends:
        reps = 1 if quick else 3
        fronts = {}
        for mode, overlap in (("serial", False), ("pipelined", True)):
            best = float("inf")
            res = best_res = None
            for _ in range(reps + 1):       # +1 warmup (page/jit caches)
                t0 = time.perf_counter()
                res = sweep_chunked(wl, space(), backend=backend,
                                    chunk_size=chunk_size, overlap=overlap)
                dt = time.perf_counter() - t0
                if dt < best:
                    best, best_res = dt, res
            fronts[mode] = res.front_metrics
            out[f"chunked_{backend}_{mode}_s"] = best
            out[f"chunked_{backend}_{mode}_configs_per_s"] = n / best
            # stage accounting from the rep that set the headline time
            out[f"chunked_{backend}_{mode}_synth_s"] = \
                best_res.timings["synth_s"]
            out[f"chunked_{backend}_{mode}_kernel_wait_s"] = \
                best_res.timings["kernel_wait_s"]
            out[f"chunked_{backend}_front_size"] = res.front_size
        # depth-k prefetch scaling: one timed run per depth, with the
        # stage accounting (synth_s / kernel_wait_s, surfaced through
        # timings) turned into a per-depth overlap fraction
        for depth in (1, 2, 4):
            t0 = time.perf_counter()
            res = sweep_chunked(wl, space(), backend=backend,
                                chunk_size=chunk_size, overlap=True,
                                prefetch_depth=depth)
            dt = time.perf_counter() - t0
            fronts[f"depth{depth}"] = res.front_metrics
            tm = res.timings
            out[f"chunked_{backend}_depth{depth}_s"] = dt
            out[f"chunked_{backend}_depth{depth}_configs_per_s"] = n / dt
            # stage overlap: (synth + kernel_wait) / wall > 1 means the
            # host and kernel stages ran concurrently (cf. obs report)
            wall = tm["wall_s"]
            if wall > 0:
                out[f"chunked_{backend}_depth{depth}_overlap_fraction"] \
                    = max(0.0, min(1.0, (tm["synth_s"]
                                         + tm["kernel_wait_s"]) / wall
                                   - 1.0))
        # overlap is an invisible optimization: same front, bit for bit,
        # at every prefetch depth
        out[f"chunked_{backend}_pipeline_front_identical"] = bool(all(
            np.array_equal(fronts["serial"][m], fronts[mode][m])
            for mode in fronts if mode != "serial"
            for m in fronts["serial"]))
        serial_s = out[f"chunked_{backend}_serial_s"]
        pipe_s = out[f"chunked_{backend}_pipelined_s"]
        out[f"chunked_{backend}_pipeline_speedup"] = serial_s / pipe_s
        # fraction of the serial wall time the pipeline hid
        out[f"chunked_{backend}_overlap_fraction"] = \
            max(0.0, 1.0 - pipe_s / serial_s)
        # headline chunked numbers stay the (default) pipelined path
        out[f"chunked_{backend}_s"] = pipe_s
        out[f"chunked_{backend}_configs_per_s"] = n / pipe_s
    out["chunked_configs_per_s"] = max(
        out[f"chunked_{b}_configs_per_s"] for b in backends)
    return out


def bench_jax(workload: str, configs, quick: bool) -> dict:
    """The x64-free jit path on the paper space: parity vs numpy + warm
    throughput (post-compile)."""
    try:
        resolve_backend("jax")
    except RuntimeError as exc:
        return {"jax_available": False, "jax_error": str(exc)}
    rn = explore(workload, configs, backend="numpy")
    rj = explore(workload, configs, backend="jax")      # compiles
    hn, hj = rn.headline_ratios(), rj.headline_ratios()
    rel = max(abs(hj[k] - hn[k]) / abs(hn[k]) for k in hn)
    reps = 3 if quick else 10
    warm_s = _best_of(lambda: explore(workload, configs, backend="jax"),
                      reps)
    return {
        "jax_available": True,
        "jax_warm_s": warm_s,
        "jax_warm_configs_per_s": len(configs) / warm_s,
        "jax_vs_numpy_headline_rel": rel,
    }


def bench_pallas(workload: str, quick: bool) -> dict:
    """Interpret-mode Pallas sweep kernel parity against the exact numpy
    kernel over the committed chunked stream (quick: the smoke grid;
    full: the whole ~103k-config grid), gated at ≤1e-6 relative."""
    try:
        resolve_backend("jax")
    except RuntimeError as exc:
        return {"pallas_available": False, "pallas_error": str(exc)}
    from repro.core.accelerator import design_space_soa
    from repro.core.dse_batch import (AGGREGATE_OUTPUTS, _make_cfg_lay,
                                      _sweep_kernel, _workload_batch)
    from repro.core.synthesis import synthesize_soa
    from repro.kernels.sweep_kernel import sweep_aggregates_pallas

    wl = get_workload(workload)
    wb = _workload_batch(wl)
    grid = _CHUNKED_QUICK if quick else _CHUNKED_FULL
    chunk_size = 4096
    max_rel = 0.0
    n_checked = 0
    t_pallas = 0.0
    for soa in design_space_soa(chunk_size=chunk_size, **grid):
        cols = synthesize_soa(soa)
        cfg, lay = _make_cfg_lay(soa, cols, wb)
        t0 = time.perf_counter()
        got = {k: np.asarray(v) for k, v in
               sweep_aggregates_pallas(cfg, lay, interpret=True).items()}
        t_pallas += time.perf_counter() - t0
        want = _sweep_kernel(np, cfg, lay, outputs="aggregates")
        for k in AGGREGATE_OUTPUTS:
            w = np.asarray(want[k], dtype=np.float64)
            rel = np.max(np.abs(got[k] - w)
                         / np.maximum(np.abs(w), 1e-30))
            max_rel = max(max_rel, float(rel))
        n_checked += len(soa["pe_rows"])
    return {
        "pallas_available": True,
        "pallas_parity_n_configs": n_checked,
        "pallas_interpret_max_rel": max_rel,
        "pallas_interpret_configs_per_s": n_checked / t_pallas,
    }


def bench(workload: str = "vgg16", quick: bool = False) -> dict:
    configs = list(design_space())
    if quick:
        configs = list(itertools.islice(configs, 0, None, 4))  # every 4th
    n = len(configs)
    reps_scalar = 1 if quick else 3
    reps_batched = 3 if quick else 10

    scalar_s = _best_of(lambda: explore_scalar(workload, configs),
                        reps_scalar)

    def cold():
        clear_synthesis_cache()
        explore(workload, configs, backend="numpy")

    cold_s = _best_of(cold, reps_batched)
    warm_s = _best_of(lambda: explore(workload, configs, backend="numpy"),
                      reps_batched)

    # identical results is part of the contract, not just speed — pinned
    # to the numpy engine (the bit-exact one on every host; jax parity is
    # gated separately at 1e-6)
    r_scalar = explore_scalar(workload, configs).headline_ratios()
    r_batched = explore(workload, configs,
                        backend="numpy").headline_ratios()
    identical = r_scalar == r_batched

    # multi-workload amortization: one synthesis pass, three mapping passes
    wls = ("vgg16", "resnet34", "resnet50")
    clear_synthesis_cache()
    t0 = time.perf_counter()
    explore_many(wls, configs)
    many_s = time.perf_counter() - t0

    out = {
        "workload": workload,
        "quick": quick,
        "n_configs": n,
        "scalar_s": scalar_s,
        "scalar_configs_per_s": n / scalar_s,
        "batched_cold_s": cold_s,
        "batched_cold_configs_per_s": n / cold_s,
        "batched_warm_s": warm_s,
        "batched_warm_configs_per_s": n / warm_s,
        "speedup_cold": scalar_s / cold_s,
        "speedup_warm": scalar_s / warm_s,
        "explore_many_3wl_s": many_s,
        "explore_many_configs_per_s": 3 * n / many_s,
        "headline_ratios_identical": identical,
        "synthesis_cache": synthesis_cache_stats(),
        "provenance": provenance(),
    }
    out.update(bench_jax(workload, configs, quick))
    out.update(bench_chunked(workload, quick))
    out.update(bench_pallas(workload, quick))
    if not quick:
        # also record the quick-mode cold number so the CI smoke gate can
        # compare like-for-like (quick's smaller space has proportionally
        # more fixed overhead per config)
        q_configs = list(itertools.islice(design_space(), 0, None, 4))

        def q_cold():
            clear_synthesis_cache()
            explore(workload, q_configs, backend="numpy")

        q_s = _best_of(q_cold, reps_batched)
        out["quick_cold_configs_per_s"] = len(q_configs) / q_s
    return out


def check_against(r: dict, baseline_path: pathlib.Path) -> None:
    """CI regression gate: fail if cold throughput fell >3x below the
    committed baseline (machine differences absorbed by the 3x margin).

    A quick-mode run compares against the baseline's quick-mode number
    (recorded by every full run) so the gate is like-for-like; a
    full-mode baseline value is the fallback for older baselines.
    """
    base = json.loads(baseline_path.read_text())
    if r["quick"] and "quick_cold_configs_per_s" in base:
        base_cps = base["quick_cold_configs_per_s"]
        label = "quick baseline"
    else:
        base_cps = base["batched_cold_configs_per_s"]
        label = "baseline"
    got_cps = r["batched_cold_configs_per_s"]
    print(f"regression check: cold {got_cps:.0f} configs/s "
          f"vs {label} {base_cps:.0f} (floor {base_cps / 3:.0f})")
    if got_cps * 3.0 < base_cps:
        raise SystemExit(
            f"cold sweep regressed >3x: {got_cps:.0f} configs/s vs "
            f"{label} {base_cps:.0f}")


def run():
    """benchmarks/run.py entry: CSV rows (name, us_per_call, derived)."""
    r = bench(quick=True)
    n = r["n_configs"]
    rows = [
        ("dse_sweep/scalar", r["scalar_s"] / n * 1e6,
         f"configs_per_s={r['scalar_configs_per_s']:.0f}"),
        ("dse_sweep/batched_cold", r["batched_cold_s"] / n * 1e6,
         f"speedup={r['speedup_cold']:.1f}x"),
        ("dse_sweep/batched_warm", r["batched_warm_s"] / n * 1e6,
         f"speedup={r['speedup_warm']:.1f}x"),
        ("dse_sweep/identical", 0.0,
         str(r["headline_ratios_identical"])),
    ]
    if r.get("jax_available"):
        rows.append(("dse_sweep/jax_warm", r["jax_warm_s"] / n * 1e6,
                     f"headline_rel={r['jax_vs_numpy_headline_rel']:.1e}"))
    rows.append(("dse_sweep/chunked", 1e6 / r["chunked_configs_per_s"],
                 f"configs_per_s={r['chunked_configs_per_s']:.0f}"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced space + reps (CI smoke mode)")
    ap.add_argument("--workload", default="vgg16")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--check-against", type=pathlib.Path, default=None,
                    help="baseline BENCH json; fail on >3x cold regression")
    args = ap.parse_args()

    r = bench(workload=args.workload, quick=args.quick)
    args.out.write_text(json.dumps(r, indent=2, sort_keys=True) + "\n")

    print(f"design points: {r['n_configs']}  workload: {r['workload']}"
          f"{'  (quick)' if r['quick'] else ''}")
    print(f"scalar        {r['scalar_s'] * 1e3:8.1f} ms  "
          f"{r['scalar_configs_per_s']:9.0f} configs/s")
    print(f"batched cold  {r['batched_cold_s'] * 1e3:8.1f} ms  "
          f"{r['batched_cold_configs_per_s']:9.0f} configs/s  "
          f"({r['speedup_cold']:.1f}x)")
    print(f"batched warm  {r['batched_warm_s'] * 1e3:8.1f} ms  "
          f"{r['batched_warm_configs_per_s']:9.0f} configs/s  "
          f"({r['speedup_warm']:.1f}x)")
    if r.get("jax_available"):
        print(f"jax warm      {r['jax_warm_s'] * 1e3:8.1f} ms  "
              f"{r['jax_warm_configs_per_s']:9.0f} configs/s  "
              f"(headline rel {r['jax_vs_numpy_headline_rel']:.1e})")
    print(f"explore_many  {r['explore_many_3wl_s'] * 1e3:8.1f} ms  "
          f"3 workloads, {r['explore_many_configs_per_s']:.0f} configs/s")
    for b in ("numpy", "jax"):
        key = f"chunked_{b}_configs_per_s"
        if key in r:
            print(f"chunked {b:5s} {r[f'chunked_{b}_serial_s'] * 1e3:8.1f}"
                  f" ms serial / {r[f'chunked_{b}_pipelined_s'] * 1e3:.1f}"
                  f" ms pipelined  {r[key]:9.0f} configs/s  "
                  f"(overlap {r[f'chunked_{b}_overlap_fraction']:.0%}, "
                  f"{r['chunked_n_configs']} configs)")
        for d in (1, 2, 4):
            dk = f"chunked_{b}_depth{d}_configs_per_s"
            if dk in r:
                ov = r.get(f"chunked_{b}_depth{d}_overlap_fraction")
                print(f"  depth={d}   {r[dk]:9.0f} configs/s"
                      + (f"  stage overlap {ov:.0%}"
                         if ov is not None else ""))
    if r.get("pallas_available"):
        print(f"pallas parity {r['pallas_parity_n_configs']} configs  "
              f"max rel {r['pallas_interpret_max_rel']:.1e}  "
              f"({r['pallas_interpret_configs_per_s']:.0f} configs/s "
              f"interpret)")
    print(f"headline ratios identical: {r['headline_ratios_identical']}")
    print(f"wrote {args.out}")

    if args.check_against is not None:
        check_against(r, args.check_against)
    if not r["headline_ratios_identical"]:
        raise SystemExit("batched engine diverged from scalar reference")
    for b in ("numpy", "jax"):
        k = f"chunked_{b}_pipeline_front_identical"
        if k in r and not r[k]:
            raise SystemExit(
                f"pipelined chunked sweep diverged from serial ({b}) "
                f"at some prefetch depth")
    if r.get("pallas_available") \
            and r["pallas_interpret_max_rel"] > 1e-6:
        raise SystemExit(
            "pallas sweep kernel diverged from numpy beyond 1e-6: "
            f"{r['pallas_interpret_max_rel']:.2e}")
    best_pipe = max((r[f"chunked_{b}_pipeline_speedup"]
                     for b in ("numpy", "jax")
                     if f"chunked_{b}_pipeline_speedup" in r),
                    default=None)
    # pipelined >= serial: ~1.0x is measurement noise on a loaded /
    # 1-core host, so the gate only catches the pipeline *actively*
    # hurting throughput — a wide margin in quick (1-rep smoke) mode
    floor = 0.5 if r["quick"] else 0.9
    if best_pipe is not None and best_pipe < floor:
        raise SystemExit(
            f"pipelined chunked sweep slower than serial on every "
            f"backend (best {best_pipe:.3f}x < {floor}x floor)")
    if not r["quick"]:
        if r["speedup_cold"] < 10.0:
            raise SystemExit(
                f"speedup gate failed: {r['speedup_cold']:.1f}x < 10x")
        if r.get("jax_available") \
                and r["jax_vs_numpy_headline_rel"] > 1e-6:
            raise SystemExit(
                "jax backend diverged from numpy beyond 1e-6: "
                f"{r['jax_vs_numpy_headline_rel']:.2e}")


if __name__ == "__main__":
    main()
