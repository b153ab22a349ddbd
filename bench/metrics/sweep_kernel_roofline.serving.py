"""Share of the roofline of the sweep kernel in the resnet50.serving cell: the
least time its calls could take at the published peaks, from their logical
shapes (harness/work.py), over the device time of its operations in the
trace, %."""

from harness.work import read_roofline


def read(run):
    return read_roofline(run)
