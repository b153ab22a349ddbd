"""The resnet50 stream's comparison with the reference: a sound run is
correct; the control (the reference in bfloat16 in the program's place)
and every fault the cell can have are not; the front it is held to is
the exact front of every config streamed."""

import numpy as np
import pytest

import benchcase
from harness import checks, spec
from harness.stream import Driver

SMALL = {"chunk_size": 4096}


def test_sound_run_is_correct():
    r = benchcase.run_small("resnet50.stream", SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_control_is_not_correct():
    r = benchcase.run_small("resnet50.stream", SMALL, control=True)
    assert not r["correct"]
    c = r["checks"]["rel_err"]
    assert c["value"] > 10 * c["limit"]


@pytest.mark.parametrize("fault", benchcase.FAULTS)
def test_fault_is_not_correct(fault):
    with benchcase.stream_fault(fault):
        r = benchcase.run_small("resnet50.stream", SMALL)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", benchcase.REDUCTION_FAULTS)
def test_reduction_fault_is_not_correct(fault):
    """The running Pareto reduction is compared with the exact front of
    every config streamed: a reduction that drops chunks misses members,
    one that keeps dominated members holds extras."""
    with benchcase.reduction_fault(fault):
        r = benchcase.run_small("resnet50.stream", SMALL, seconds=3.0)
    assert not r["correct"], r["checks"]
    gap = {"skip": "missed_gap", "keep": "extra_gap"}[fault]
    assert r["checks"][gap]["value"] > r["checks"][gap]["limit"]


def _brute_pareto(F):
    return np.array([not ((F <= r).all(axis=1) & (F < r).any(axis=1)).any()
                     for r in F], dtype=bool)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pareto_mask_and_dominance_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    for _ in range(50):
        F = rng.integers(0, 6, size=(rng.integers(1, 40), 2)).astype(float)
        assert (checks.pareto_rows(F) == _brute_pareto(F)).all()
        R = rng.integers(0, 6, size=(20, 2)).astype(float)
        want = [(F <= r).all(axis=1).any() for r in R]
        assert (checks.dominated(F, R) == want).all()


def test_front_gaps():
    front = np.array([[-2.0, 1.0], [-1.0, 0.5]])
    assert checks.missed_gap(front, front) == 0.0
    assert checks.extra_gap(front, front) == 0.0
    # the second member is matched only by a row 100% worse in energy
    assert checks.missed_gap(front, front[:1]) == pytest.approx(1.0)
    # a row 10% worse than the first member on both objectives
    assert checks.extra_gap(front, np.array([[-1.8, 1.1]])) \
        == pytest.approx(0.1)


def test_replay_front_is_the_front_of_every_config_streamed():
    """Leaving out configs whose compute-cycle bound the running front
    already beats changes nothing: the replay's front is the exact front
    of all configs the chunks held."""
    cell = spec.load_cell("resnet50.stream")
    driver = Driver(cell.config, dict(cell.traffic, chunk_size=2048),
                    seed=2 ** 33 + 5)
    driver.n_chunks = 6
    front, _ = driver.replay(np.zeros(0, np.int64), np.zeros(0))
    rows = []
    for i in range(driver.n_chunks):
        m = driver._reference(*driver._draw(0, i))
        rows.append(np.stack([-m[:, 0], m[:, 1]], axis=1))
    rows = np.concatenate(rows)
    want = rows[checks.pareto_rows(rows)]
    assert sorted(map(tuple, front)) == sorted(map(tuple, want))
