#!/usr/bin/env python3
"""Smoke run of the DSE main path on a TPU, checked against numpy.

Everything goes through the user entry point,
``repro.core.dse.run(ExploreSpec ...)`` with ``backend="jax"``, in one
process (a chip belongs to one process at a time), and every result is
compared with the same spec run on the exact numpy backend:

A. streamed sweep: resnet50 (54 layers) over a 1,056,000-config design
   space in 32,768-config chunks, through the compiled Pallas sweep
   kernel; no degradation to numpy and no recomputed chunk allowed.
   The front must hold the numpy front's configs with metrics within
   1e-6, save configs whose membership is a tie inside that bound (see
   ``compare_sweeps``).
B. suite search: ``many-quick`` nsga2 over vgg16 + resnet34 + resnet50
   (107 layers, per-layer precision columns) through the Pallas kernel;
   the front genomes must equal numpy's.
C. serving search: vgg16 under ``steady`` traffic (the fleet simulator
   runs on the device); the front genomes must equal numpy's.

``--chips 4`` runs only the mesh-sharded paths instead: the
``many-quick`` search and a chunked vgg16 stream with
``mesh=make_sweep_mesh()``, each compared with the same run on one
device (``mesh=None``) and on numpy, and reports how many devices the
sharded kernel output lives on.

The times printed are smoke timings of one cold run, not benchmark
numbers.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed check exits non-zero before it.  Without a TPU the script
exits non-zero at once.

    python chip_smoke.py             # one chip: phases A-C
    python chip_smoke.py --chips 4   # four chips: sharded paths only
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

RTOL = 1e-6
CHUNK = 32768
SWEEP_GRID = dict(glb_kbs=tuple(2 ** i for i in range(2, 13)),
                  bws=tuple(np.linspace(2.0, 64.0, 1600)))
SUITE = ("vgg16", "resnet34", "resnet50")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), and how many compiles hit that cache."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event in self._EVENTS:
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.cache_hits

    def since(self, mark: tuple[float, int]) -> str:
        return (f"compile_s={self.seconds - mark[0]:.1f} "
                f"compile_cache_hits={self.cache_hits - mark[1]}")


def max_rel(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.size == 0:
        return 0.0
    both_zero = (a == 0) & (b == 0)
    denom = np.where(a == 0, 1.0, np.abs(a))
    return float(np.max(np.where(both_zero, 0.0, np.abs(b - a) / denom)))


def _front(res) -> dict:
    """A chunked sweep's front metrics keyed by config identity."""
    from repro.core.dse_batch import _SOA_ID_FIELDS
    keys = zip(*(res.front_soa[k].tolist() for k in _SOA_ID_FIELDS))
    return {key: {m: float(v[i]) for m, v in res.front_metrics.items()}
            for i, key in enumerate(keys)}


def _covered_not_outranked(m: dict, other: dict) -> bool:
    """Whether a config on one front only sits inside the parity bound of
    the other front: some member of the other front matches or beats it
    within RTOL on both objectives, and none beats it by more than RTOL
    on both.  Only then can f32 rounding have decided its membership."""
    lo, hi = 1.0 - RTOL, 1.0 + RTOL
    perf, energy = m["perf_per_area"], m["energy_j"]
    covered = outranked = False
    for o in other.values():
        p, e = o["perf_per_area"], o["energy_j"]
        covered |= p >= perf * lo and e <= energy * hi
        outranked |= p * lo > perf * hi and e * hi < energy * lo
    return covered and not outranked


def compare_sweeps(res, ref, what: str) -> tuple[float, int]:
    """The jax front against the exact reference front.  Configs on both
    fronts must agree within RTOL.  A config on one front only is
    allowed where its membership is a tie inside that bound (two f64
    energies 2e-8 apart round to one f32 value, and one config then
    dominates the other); it must then pass
    :func:`_covered_not_outranked` against the other front.  Returns the
    largest relative metric error and the number of such configs."""
    got, want = _front(res), _front(ref)
    check(bool(got) and bool(want), f"{what}: an empty front")
    common = sorted(got.keys() & want.keys())
    err = max_rel(np.array([list(want[k].values()) for k in common]),
                  np.array([list(got[k].values()) for k in common]))
    check(err <= RTOL, f"{what}: front metrics differ by {err:.3g} > {RTOL}")
    ties = 0
    for mine, other in ((got, want), (want, got)):
        for key in mine.keys() - set(common):
            check(_covered_not_outranked(mine[key], other),
                  f"{what}: config {key} is on one front only, beyond "
                  f"the {RTOL} parity bound")
            ties += 1
    return err, ties


def compare_searches(res, ref, what: str) -> float:
    def rows(r):
        order = np.lexsort(r.genomes.T[::-1])
        return r.genomes[order], r.front_objectives[order]

    g, obj = rows(res)
    ref_g, ref_obj = rows(ref)
    check(g.shape == ref_g.shape and np.array_equal(g, ref_g),
          f"{what}: front has {len(g)} genomes, reference {len(ref_g)}, "
          f"or they differ")
    err = max_rel(ref_obj, obj)
    check(err <= RTOL,
          f"{what}: front objectives differ by {err:.3g} > {RTOL}")
    return err


def sweep_feed():
    from repro.core.accelerator import design_space_soa
    return design_space_soa(chunk_size=CHUNK, **SWEEP_GRID)


def check_stream(res, what: str) -> None:
    t = res.timings
    check(not t["degraded"], f"{what}: degraded to numpy")
    recomputed = (t["watchdog_redispatches"] + t["cancelled_recomputes"])
    check(recomputed == 0, f"{what}: {recomputed} chunk(s) recomputed")


def phase_a(clock: CompileClock, use_pallas=None) -> None:
    from repro.core.dse import ExploreSpec, run
    from repro.kernels.sweep_kernel import resolve_pallas_interpret

    mark = clock.mark()
    t0 = time.perf_counter()
    res = run(ExploreSpec.single("resnet50", sweep_feed(),
                                 chunk_size=CHUNK, backend="jax",
                                 use_pallas=use_pallas))
    wall = time.perf_counter() - t0
    compile_note = clock.since(mark)
    check(res.timings["use_pallas"], "A: the Pallas kernel was not used")
    check_stream(res, "A")
    t0 = time.perf_counter()
    ref = run(ExploreSpec.single("resnet50", sweep_feed(),
                                 chunk_size=CHUNK, backend="numpy"))
    wall_np = time.perf_counter() - t0
    check(res.n_configs == ref.n_configs,
          f"A: {res.n_configs} configs swept, numpy {ref.n_configs}")
    err, ties = compare_sweeps(res, ref, "A")
    mode = "interpreted" if resolve_pallas_interpret() else "compiled"
    print(f"phase A streamed sweep resnet50: pallas={mode} "
          f"configs={res.n_configs} chunks={res.n_chunks} {compile_note} "
          f"wall_s={wall:.2f} configs_per_s={res.n_configs / wall:.0f} "
          f"numpy_wall_s={wall_np:.2f} front={res.front_size} "
          f"numpy_front={ref.front_size} on_one_front_only={ties} "
          f"max_rel={err:.3g} "
          f"degraded={res.timings['degraded']} recomputed=0 "
          f"(smoke timings, not benchmark numbers)", flush=True)


def _search(spec_fn, what: str, clock: CompileClock, use_pallas=None,
            **kw) -> None:
    from repro.core.dse import run
    from repro.kernels.sweep_kernel import resolve_pallas_interpret

    mark = clock.mark()
    t0 = time.perf_counter()
    res = run(spec_fn(backend="jax", use_pallas=use_pallas, **kw))
    wall = time.perf_counter() - t0
    compile_note = clock.since(mark)
    check(res.stats["use_pallas"], f"{what}: the Pallas kernel was not used")
    ref = run(spec_fn(backend="numpy", **kw))
    err = compare_searches(res, ref, what)
    mode = "interpreted" if resolve_pallas_interpret() else "compiled"
    print(f"phase {what}: pallas={mode} {compile_note} wall_s={wall:.2f} "
          f"evals={res.n_evals} evals_per_s={res.n_evals / wall:.0f} "
          f"front={res.front_size} numpy_front={ref.front_size} "
          f"max_rel={err:.3g} (smoke timings, not benchmark numbers)",
          flush=True)


def phase_b(clock: CompileClock, use_pallas=None) -> None:
    from repro.core.dse import ExploreSpec
    _search(lambda **kw: ExploreSpec.many(SUITE, precision="mixed",
                                          preset="many-quick", seed=0, **kw),
            "B suite search vgg16+resnet34+resnet50", clock, use_pallas)


def phase_c(clock: CompileClock, use_pallas=None) -> None:
    from repro.core.dse import ExploreSpec
    _search(lambda **kw: ExploreSpec.mixed("vgg16", traffic="steady", **kw),
            "C serving search vgg16 steady", clock, use_pallas)


def sharded_devices(mesh) -> int:
    """How many devices one sharded kernel output actually lives on."""
    from repro.core.accelerator import design_space_soa
    from repro.core.dse_batch import (_make_cfg_lay, _to_jax_inputs,
                                      _workload_batch, get_jax_kernel)
    from repro.core.synthesis import synthesize_soa
    from repro.core.workloads import get_workload

    soa = next(iter(design_space_soa(chunk_size=1024)))
    cfg, lay = _make_cfg_lay(soa, synthesize_soa(soa),
                             _workload_batch(get_workload("vgg16")))
    fn, exact = get_jax_kernel(mesh, "aggregates")
    out = fn(*_to_jax_inputs(cfg, lay, exact))["energy_j"]
    return len({s.device for s in out.addressable_shards})


def phase_mesh(clock: CompileClock) -> None:
    from repro.core.dse import ExploreSpec, run
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh()
    n_dev = mesh.devices.size

    def many(**kw):
        return ExploreSpec.many(SUITE, precision="mixed",
                                preset="many-quick", seed=0, **kw)

    mark = clock.mark()
    t0 = time.perf_counter()
    sharded = run(many(backend="jax", mesh=mesh))
    wall = time.perf_counter() - t0
    note = clock.since(mark)
    one = run(many(backend="jax"))
    ref = run(many(backend="numpy"))
    err_np = compare_searches(sharded, ref, "mesh search vs numpy")
    err_one = compare_searches(sharded, one, "mesh search vs one device")
    shards = sharded.stats["mesh_shards"]
    check(shards == n_dev, f"mesh search: {shards} shards on {n_dev} devices")
    print(f"mesh suite search: mesh_shards={shards} {note} "
          f"wall_s={wall:.2f} evals={sharded.n_evals} "
          f"evals_per_s={sharded.n_evals / wall:.0f} "
          f"front={sharded.front_size} max_rel_vs_numpy={err_np:.3g} "
          f"max_rel_vs_one_device={err_one:.3g} "
          f"(smoke timings, not benchmark numbers)", flush=True)

    mark = clock.mark()
    t0 = time.perf_counter()
    sharded = run(ExploreSpec.single("vgg16", sweep_feed(),
                                     chunk_size=CHUNK, backend="jax",
                                     mesh=mesh))
    wall = time.perf_counter() - t0
    note = clock.since(mark)
    check_stream(sharded, "mesh stream")
    one = run(ExploreSpec.single("vgg16", sweep_feed(),
                                 chunk_size=CHUNK, backend="jax"))
    ref = run(ExploreSpec.single("vgg16", sweep_feed(),
                                 chunk_size=CHUNK, backend="numpy"))
    err_np, ties_np = compare_sweeps(sharded, ref, "mesh stream vs numpy")
    err_one, ties_one = compare_sweeps(sharded, one,
                                       "mesh stream vs one device")
    placed = sharded_devices(mesh)
    check(placed == n_dev,
          f"mesh stream: sharded output lives on {placed} of {n_dev} "
          f"devices")
    print(f"mesh chunked stream vgg16: devices_holding_output={placed} "
          f"configs={sharded.n_configs} {note} wall_s={wall:.2f} "
          f"configs_per_s={sharded.n_configs / wall:.0f} "
          f"front={sharded.front_size} max_rel_vs_numpy={err_np:.3g} "
          f"one_front_only_vs_numpy={ties_np} "
          f"max_rel_vs_one_device={err_one:.3g} "
          f"one_front_only_vs_one_device={ties_one} "
          f"one_device_pallas={one.timings['use_pallas']} "
          f"(smoke timings, not benchmark numbers)", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: only the "
                         "mesh-sharded paths, across four chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    count = jax.device_count()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found; this smoke runs on a TPU only", file=sys.stderr)
        return 1
    if count < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{count}", file=sys.stderr)
        return 1

    from repro.kernels.sweep_kernel import resolve_pallas_interpret
    from repro.launch.compile_cache import use_compile_cache
    check(not resolve_pallas_interpret(),
          "the Pallas kernel would run interpreted on this device")
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phase_mesh(clock)
    else:
        phase_a(clock)
        phase_b(clock)
        phase_c(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
