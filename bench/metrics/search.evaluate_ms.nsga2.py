"""The search Evaluator per call in the suite.nsga2 cell (decode, memo, padded
dispatch, objective matrix): mean duration of the explore.evaluate spans,
ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "explore.evaluate")
