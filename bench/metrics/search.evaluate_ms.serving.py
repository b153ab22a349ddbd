"""The search Evaluator per call in the resnet50.serving cell (decode, memo,
padded dispatch, objective matrix): mean duration of the explore.evaluate
spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "explore.evaluate")
