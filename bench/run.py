#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name (``bench/harness/spec.py``).  The run sets up (TPU start, compile
or cache fetch, a warm-up of the cell's own shapes), measures for
``--seconds``, reads the device peak memory, then compares what the
window produced with the plain reference.  With ``--trace 1`` the window
is traced and the result carries the per-layer metrics instead of the
end-to-end ones.  ``--control 1`` puts the reference, computed in
bfloat16, in the program's place: its numbers must fail their limits.

The last lines on standard error are the numbers compared, each beside
its limit; the last line on standard output is one JSON object.  Without
a TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 1.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is timed from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import device, spec, tracing  # noqa: E402

DRIVERS = {"stream": "harness.stream", "nsga2": "harness.search",
           "serving": "harness.search"}


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            devices, peaks: dict, control: bool = False,
            t0: float = T0, on_chip: bool = True) -> tuple[dict, list]:
    """Set up, measure and check one run; returns the result object and
    the checks.  ``devices`` are the chips the run may use; ``on_chip``
    requires the compiled Pallas kernel on the timed path."""
    driver = importlib.import_module(
        DRIVERS[cell.traffic["driver"]]).Driver(cell.config, cell.traffic,
                                               seed)
    cache = device.use_compile_cache()
    clock = device.CompileClock()
    pallas = driver.set_up()
    if on_chip and not pallas:
        raise RuntimeError("the timed path did not use the Pallas kernel")
    setup_s = time.perf_counter() - t0
    print(f"set-up {setup_s:.3f} s, compile cache {cache}",
          file=sys.stderr, flush=True)

    mark = clock.mark()
    with tracing.Capture(trace) as cap:
        out = driver.window(seconds)
    inside = clock.since(mark)
    print(f"compiles inside the window: traces={inside['traces']} "
          f"compile_s={inside['compile_s']:.3f} "
          f"cache_hits={inside['cache_hits']}", file=sys.stderr, flush=True)
    dev = devices[0]
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": device.memory_peak_bytes(devices)}

    if trace:
        readout = tracing.Readout(cell=cell.name, spans=cap.spans,
                                  trace=cap.trace, peaks=peaks,
                                  calls=driver.kernel_calls(cap.spans),
                                  networks=driver.networks)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(readout)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result_device.update(busy_s=cap.trace.busy_s,
                             window_s=cap.trace.window_s)
        breakdown = {"device_ops": [list(x) for x in cap.trace.top_ops()],
                     "idle_gaps": [list(x) for x in cap.trace.gaps[:10]]}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None

    driver.release()
    checks = driver.checks(control=control)
    result = {"correct": all(c.ok for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        dev = device.require_tpu(cell.chips)
    except device.NoChip as exc:
        print(exc, file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()[:cell.chips]
    peaks = spec.peaks_for(dev.device_kind)
    result, checks = measure(cell, args.seed, args.seconds,
                             bool(args.trace), devices, peaks,
                             control=bool(args.control))
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
