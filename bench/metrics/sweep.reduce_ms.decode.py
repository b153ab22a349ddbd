"""Running Pareto reduction per chunk of the decode stream
(dse_batch.reduce_chunk): mean duration of the sweep.reduce spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "sweep.reduce")
