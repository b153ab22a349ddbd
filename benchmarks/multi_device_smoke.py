"""Multi-device smoke: the sharded + pipelined paths on forced host
devices (the `multi-device-smoke` CI job).

Must be launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=K``
already in the environment (before any jax import): asserts
``jax.device_count()`` matches ``--devices``, then

* shards ``sweep_mixed_many`` over the full device mesh and checks the
  result against the single-device (unsharded) numpy and jax outputs —
  numpy simulated shards bit-exact, jax ``shard_map`` ≤1e-6 relative —
  for both a divisible and a non-divisible batch size;
* runs the double-buffered ``sweep_chunked`` pipeline on the device mesh
  and checks its Pareto front is identical to the serial single-device
  sweep, recording serial/pipelined throughput and the overlap fraction;
* runs a short mesh-sharded ``coexplore_many`` search and checks its
  front matches the unsharded numpy search bit for bit.

Writes one JSON report (``--out``, uploaded as a CI artifact) and exits
non-zero on any parity failure.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      PYTHONPATH=src python benchmarks/multi_device_smoke.py --devices 4
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from dse_sweep_bench import provenance  # noqa: E402  (shared helper)

RTOL = 1e-6
_PARITY_KEYS = ("latency_s", "energy_j", "perf_per_area",
                "throughput_gmacs")


def _max_rel(a: dict, b: dict, keys=_PARITY_KEYS) -> float:
    worst = 0.0
    for k in keys:
        x = np.asarray(a[k], dtype=np.float64)
        y = np.asarray(b[k], dtype=np.float64)
        both_zero = (x == 0) & (y == 0)
        denom = np.where(x == 0, 1.0, x)
        worst = max(worst, float(np.max(np.where(
            both_zero, 0.0, np.abs(y / denom - 1.0)))))
    return worst


def _mixed_many_batch(n: int, seed: int = 5):
    from repro.core.accelerator import AcceleratorConfig, configs_to_soa
    from repro.core.pe import PEType, supported_modes
    from repro.core.workloads import get_workload

    types = tuple(PEType)
    wls = (get_workload("vgg16"), get_workload("resnet34"),
           get_workload("resnet50"))
    rng = np.random.default_rng(seed)
    space = [AcceleratorConfig(pe_type=t, pe_rows=r, pe_cols=c, glb_kb=g,
                               dram_bw_gbps=bw)
             for t in types
             for (r, c, g, bw) in [(8, 8, 64, 6.4), (12, 14, 128, 12.8),
                                   (16, 16, 256, 12.8),
                                   (32, 32, 512, 25.6)]]
    configs = [space[i] for i in rng.integers(0, len(space), size=n)]
    soa = configs_to_soa(configs)
    assigns = []
    for w in wls:
        a = np.empty((n, len(w.layers)), dtype=np.int64)
        for i, c in enumerate(configs):
            modes = [types.index(m) for m in supported_modes(c.pe_type)]
            a[i] = rng.choice(modes, size=len(w.layers))
        assigns.append(a)
    return wls, soa, assigns


def smoke_sharded_many(mesh, n_devices: int) -> dict:
    from repro.core.dse_batch import sweep_mixed_many

    out: dict = {}
    for n in (16 * n_devices, 16 * n_devices + 3):   # divisible + ragged
        wls, soa, assigns = _mixed_many_batch(n)
        un_np = sweep_mixed_many(wls, soa, assigns, backend="numpy",
                                 use_cache=False)
        sh_np = sweep_mixed_many(wls, soa, assigns, backend="numpy",
                                 use_cache=False, mesh=n_devices)
        sh_j = sweep_mixed_many(wls, soa, assigns, backend="jax",
                                use_cache=False, mesh=mesh)
        tag = f"n{n}"
        out[f"sharded_many_{tag}_numpy_bit_exact"] = bool(all(
            np.array_equal(un_np[k], sh_np[k]) for k in un_np))
        out[f"sharded_many_{tag}_jax_max_rel"] = _max_rel(un_np, sh_j)
    return out


def smoke_pipelined_chunked(mesh) -> dict:
    from repro.core.accelerator import design_space_soa
    from repro.core.dse_batch import sweep_chunked
    from repro.core.workloads import get_workload

    wl = get_workload("vgg16")
    grid = dict(glb_kbs=(64, 128, 256, 512),
                bws=tuple(np.linspace(2.0, 64.0, 64)))
    chunk_size = 4096

    def space():
        return design_space_soa(chunk_size=chunk_size, **grid)

    n = sum(len(s["pe_rows"]) for s in space())
    out: dict = {"chunked_n_configs": n}
    runs = {}
    for name, kwargs in (
            ("serial", dict(backend="numpy", overlap=False)),
            ("pipelined", dict(backend="numpy", overlap=True)),
            ("pipelined_jax_mesh", dict(backend="jax", overlap=True,
                                        mesh=mesh))):
        best, res = float("inf"), None
        for _ in range(2):                      # 1 warmup
            t0 = time.perf_counter()
            res = sweep_chunked(wl, space(), chunk_size=chunk_size,
                                **kwargs)
            best = min(best, time.perf_counter() - t0)
        runs[name] = res
        out[f"chunked_{name}_s"] = best
        out[f"chunked_{name}_configs_per_s"] = n / best
    out["chunked_pipeline_speedup"] = (out["chunked_serial_s"]
                                       / out["chunked_pipelined_s"])
    out["chunked_overlap_fraction"] = max(
        0.0, 1.0 - out["chunked_pipelined_s"] / out["chunked_serial_s"])
    fm_s = runs["serial"].front_metrics
    fm_p = runs["pipelined"].front_metrics
    out["chunked_pipeline_front_identical"] = bool(all(
        np.array_equal(fm_s[m], fm_p[m]) for m in fm_s))
    fm_j = runs["pipelined_jax_mesh"].front_metrics
    out["chunked_jax_mesh_front_max_rel"] = (
        float("inf") if fm_j["energy_j"].shape != fm_s["energy_j"].shape
        else _max_rel(
            {m: np.sort(fm_s[m]) for m in fm_s},
            {m: np.sort(fm_j[m]) for m in fm_j},
            keys=tuple(fm_s)))
    return out


def smoke_sharded_search(mesh) -> dict:
    from repro.core.dse import coexplore_many

    wls = ("vgg16", "resnet34", "resnet50")
    base = coexplore_many(wls, preset="many-quick", budget=96, seed=11,
                          backend="numpy")
    t0 = time.perf_counter()
    sharded = coexplore_many(wls, preset="many-quick", budget=96, seed=11,
                             backend="jax", mesh=mesh)
    dt = time.perf_counter() - t0

    def _row_sorted(g):
        return g[np.lexsort(g.T[::-1])]

    return {
        "search_sharded_evals_per_s": sharded.n_evals / dt,
        "search_mesh_shards": sharded.stats["mesh_shards"],
        "search_sharded_front_matches_numpy": bool(
            base.genomes.shape == sharded.genomes.shape
            and np.array_equal(_row_sorted(base.genomes),
                               _row_sorted(sharded.genomes))),
    }


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=4,
                    help="expected jax.device_count()")
    ap.add_argument("--out", type=pathlib.Path,
                    default=pathlib.Path("/tmp/bench_multi_device.json"))
    args = ap.parse_args()

    assert "xla_force_host_platform_device_count" in \
        os.environ.get("XLA_FLAGS", ""), \
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=K"

    import jax

    from repro.launch.mesh import make_sweep_mesh

    n_devices = jax.device_count()
    r: dict = {"expected_devices": args.devices,
               "device_count": n_devices,
               "provenance": provenance()}
    failures: list[str] = []
    if n_devices != args.devices:
        failures.append(
            f"jax.device_count() == {n_devices}, expected {args.devices}")

    mesh = make_sweep_mesh()
    r.update(smoke_sharded_many(mesh, n_devices))
    r.update(smoke_pipelined_chunked(mesh))
    r.update(smoke_sharded_search(mesh))

    for k, v in sorted(r.items()):
        if k == "provenance":
            continue
        print(f"{k}: {v}")
        if k.endswith("_bit_exact") or k.endswith("_identical") \
                or k.endswith("_matches_numpy"):
            if not v:
                failures.append(f"{k} is False")
        elif k.endswith("_max_rel") and v >= RTOL:
            failures.append(f"{k} = {v:.3g} >= {RTOL}")

    args.out.write_text(json.dumps(r, indent=2, sort_keys=True) + "\n")
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit("multi-device smoke FAILED:\n  "
                         + "\n  ".join(failures))
    print(f"multi-device smoke OK on {n_devices} devices")


if __name__ == "__main__":
    main()
