"""The objective matrix per evaluation in the resnet50.serving cell
(explore/objectives.py, from the kernel's aggregates): mean self time of the
explore.objectives spans, less the fleet.simulate spans nested in them, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "explore.objectives", self_time=True)
