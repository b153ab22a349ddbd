"""Exact hypervolume of the archive per generation in the resnet50.serving cell
(explore/pareto.py hypervolume): mean duration of the nsga2.hypervolume
spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "nsga2.hypervolume")
