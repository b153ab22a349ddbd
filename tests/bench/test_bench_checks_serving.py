"""The resnet50.serving cell's comparison with the reference: a sound run is
correct; the control (the reference in bfloat16 in the program's place)
and every fault the cell can have are not."""

import pytest

import benchcase

SMALL = {"budget": 64, "pop_size": 16}


def test_sound_run_is_correct():
    r = benchcase.run_small("resnet50.serving", SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_control_is_not_correct():
    r = benchcase.run_small("resnet50.serving", SMALL, control=True)
    assert not r["correct"]


@pytest.mark.parametrize("fault", benchcase.FAULTS)
def test_fault_is_not_correct(fault):
    with benchcase.search_fault(fault, "_sweep_mixed"):
        r = benchcase.run_small("resnet50.serving", SMALL)
    assert not r["correct"], r["checks"]
