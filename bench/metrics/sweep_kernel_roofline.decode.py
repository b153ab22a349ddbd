"""Share of the roofline of the sweep kernel in the decode stream: the
least time its calls could take at the published peaks, from their
logical shapes and the decode layer model's work count (harness/work.py,
layers/mla_moe_decode.py), over the device time of its operations in the
trace, %."""

from harness.work import read_roofline


def read(run):
    return read_roofline(run)
