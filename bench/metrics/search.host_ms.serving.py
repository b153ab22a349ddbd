"""nsga2 host work per generation in the resnet50.serving cell
(explore/search.py: sorting, crowding, variation, archive): mean self time
of nsga2.generation, that is, less its explore.evaluate child, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "nsga2.generation", self_time=True)
