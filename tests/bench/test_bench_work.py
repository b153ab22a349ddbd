"""The sweep kernel's operations and bytes come from logical shapes:
padding and tiling do not change them."""

import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from harness import spec, work  # noqa: E402
from harness.search import Driver as SearchDriver  # noqa: E402
from harness.tracing import Readout  # noqa: E402

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_counts_follow_the_documented_table():
    assert work.kernel_ops(1, 1, 1) == 78 + 6
    assert work.kernel_ops(32768, 54, 1) == 78 * 32768 * 54 + 6 * 32768
    # 15 (N, 1) config columns, 10 layer fields, 6 outputs
    assert work.kernel_bytes(1, 54, 1, mixed=False) == 4 * (15 + 540 + 6)
    # mixed: the three precision columns are (N, L)
    assert work.kernel_bytes(2, 107, 3, mixed=True) == 4 * (
        12 * 2 + 3 * 2 * 107 + 10 * 107 + 6 * 2 * 3)


@pytest.mark.parametrize("n,pad", [(5, 8), (33, 64), (1000, 1024)])
def test_work_is_additive_over_configs_so_padding_only_adds(n, pad):
    for l, w in ((54, 1), (107, 3)):
        assert work.kernel_ops(n, l, w) + work.kernel_ops(pad - n, l, w) \
            == work.kernel_ops(pad, l, w)
        assert work.kernel_ops(n, l, w) < work.kernel_ops(pad, l, w)


def test_roofline_takes_the_larger_bound():
    # 32768 x 54 stream chunk: 138 Mop and 7.3 MB -> memory bound on v5e
    calls = [(32768, 54, 1, False)]
    share, bound = work.roofline(calls, 1e-3, V5E)
    t_mem = work.kernel_bytes(32768, 54, 1, False) / V5E["hbm_bytes_per_s"]
    assert bound == "memory"
    assert share == pytest.approx(100 * t_mem / 1e-3)


def test_search_calls_count_the_genomes_evaluated_not_the_padded_batch():
    """The Evaluator pads 5 genomes to a batch of 8; the work counted is
    that of the 5 it evaluated."""
    from repro.explore.search import Evaluator
    from repro.explore.space import space_for_workloads
    from repro.obs import trace as obs_trace

    cell = spec.load_cell("suite.nsga2")
    driver = SearchDriver(cell.config, cell.traffic, seed=3)
    space = space_for_workloads(driver.workloads, **driver.overrides)
    ev = Evaluator(space, driver.workloads, driver.objectives,
                   backend="jax")
    assert ev._pad(5) == 8
    obs_trace.configure(enabled=True, reset=True)
    try:
        ev.evaluate(space.random_population(5, np.random.default_rng(0)))
        spans = [s.as_dict() for s in obs_trace.get_tracer().spans()]
    finally:
        obs_trace.disable()
        obs_trace.configure(enabled=False, reset=True)
    calls = driver.kernel_calls(spans)
    assert calls == [(5, 107, 3, True)]

    class Trace:
        def kernel_s(self, kind):
            return 1e-3
    run = Readout(cell="suite.nsga2", spans=spans, trace=Trace(),
                  peaks=V5E, calls=calls)
    t_ops = work.kernel_ops(5, 107, 3) / V5E["flops_per_s"]
    t_mem = work.kernel_bytes(5, 107, 3, True) / V5E["hbm_bytes_per_s"]
    assert work.read_roofline(run) == pytest.approx(
        100 * max(t_ops, t_mem) / 1e-3)
