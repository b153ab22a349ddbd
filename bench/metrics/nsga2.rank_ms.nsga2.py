"""Non-dominated sort and crowding distance in the suite.nsga2 cell
(explore/search.py _ranks_and_crowding, called twice a generation): mean
duration of the nsga2.rank spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "nsga2.rank")
