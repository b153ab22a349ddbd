"""The serving-fleet simulator per call (serving/fleet_sim.py): mean
duration of the fleet.simulate spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "fleet.simulate")
