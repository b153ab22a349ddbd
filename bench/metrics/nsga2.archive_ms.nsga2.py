"""Archive update per generation in the suite.nsga2 cell (the epsilon-archive
add, or the unique-and-front update): mean duration of the nsga2.archive
spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "nsga2.archive")
