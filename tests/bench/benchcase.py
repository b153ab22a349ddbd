"""Drive a whole benchmark run on the CPU, past the harness's look for a
chip, at sizes a test run holds; and plant faults in the timed path."""

import contextlib
import dataclasses
import importlib.util
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import device, spec  # noqa: E402

FAULTS = ("stale", "half", "altered")
REDUCTION_FAULTS = ("skip", "keep")


def _bench_run():
    s = importlib.util.spec_from_file_location("bench_run",
                                               ROOT / "bench" / "run.py")
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def run_small(cell_name: str, overrides: dict, seconds: float = 1.0,
              control: bool = False, seed: int = 2 ** 33 + 12345) -> dict:
    """One run of ``cell_name`` with its traffic parameters overridden;
    returns the result object.  The compile cache stays off: tests never
    set one."""
    import jax
    from repro.core.synthesis import clear_synthesis_cache
    cell = spec.load_cell(cell_name)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, **overrides))
    bench_run = _bench_run()
    use_cache = device.use_compile_cache
    device.use_compile_cache = lambda: "off"
    try:
        result, _ = bench_run.measure(
            cell, seed, seconds, False, jax.devices(),
            spec.peaks_for("TPU v5 lite"), control=control, on_chip=False)
    finally:
        device.use_compile_cache = use_cache
        clear_synthesis_cache()
    return result


def corrupt(kind: str, out: dict, state: dict) -> dict:
    """The kernel's output columns (config axis last) after a fault:
    ``stale`` hands back the previous call's output where the shapes
    match (state left unchanged), ``half`` replaces the second half of
    the batch with the first half's answers, ``altered`` raises every
    energy by 0.1%."""
    out = {k: np.asarray(v) for k, v in out.items()}
    if kind == "stale":
        prev = state.get("prev")
        if prev is not None and all(prev[k].shape == v.shape
                                    for k, v in out.items()):
            return prev
        state["prev"] = out
        return out
    if kind == "half":
        def halve(v):
            n = v.shape[-1]
            h = n // 2
            return np.concatenate([v[..., :h], v[..., :n - h]], axis=-1)
        return {k: halve(v) for k, v in out.items()}
    if kind == "altered":
        return dict(out, energy_j=out["energy_j"] * (1 + 1e-3))
    raise ValueError(kind)


@contextlib.contextmanager
def stream_fault(kind: str):
    """Break the stream's kernel stage: every chunk's results pass
    through :func:`corrupt` as they are produced."""
    from repro.core import dse_batch
    orig = dse_batch._dispatch_chunk
    state: dict = {}

    def dispatch(*args, **kwargs):
        finalize = orig(*args, **kwargs)
        return lambda timeout=None: corrupt(kind, finalize(timeout), state)

    dse_batch._dispatch_chunk = dispatch
    try:
        yield
    finally:
        dse_batch._dispatch_chunk = orig


@contextlib.contextmanager
def reduction_fault(kind: str):
    """Break the stream's running Pareto reduction, which calls
    ``pareto_mask`` twice a chunk (the chunk's own front, then the union
    with the running one): ``skip`` drops every other chunk's
    candidates; ``keep`` never drops a member of the running front once
    it has joined, however far a later config dominates it."""
    from repro.core import dse_batch
    orig = dse_batch.pareto_mask
    state = {"calls": 0, "front": 0}

    def mask(perf, energy, *args, **kwargs):
        keep = orig(perf, energy, *args, **kwargs)
        chunk, union = divmod(state["calls"], 2)
        state["calls"] += 1
        if kind == "skip" and not union and chunk % 2:
            keep = np.zeros_like(keep)
        if kind == "keep" and union:
            keep = keep | (np.arange(len(keep)) < state["front"])
            state["front"] = int(keep.sum())
        return keep

    dse_batch.pareto_mask = mask
    try:
        yield
    finally:
        dse_batch.pareto_mask = orig


@contextlib.contextmanager
def search_fault(kind: str, name: str):
    """Break the search's evaluation: the fused sweep ``name`` that the
    Evaluator calls hands its columns through :func:`corrupt`."""
    from repro.explore import search
    orig = getattr(search, name)
    state: dict = {}

    def sweep(*args, **kwargs):
        return corrupt(kind, orig(*args, **kwargs), state)

    setattr(search, name, sweep)
    try:
        yield
    finally:
        setattr(search, name, orig)
