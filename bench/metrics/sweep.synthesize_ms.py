"""Host synthesis per chunk of the stream (core/synthesis.py, called by
_sweep_chunked): mean self time of the sweep.synthesize spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "sweep.synthesize", self_time=True)
