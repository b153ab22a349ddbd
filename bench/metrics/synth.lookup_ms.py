"""Synthesis per chunk of the stream, one phase: the per-row probe of the
in-process synthesis cache's index (PersistentSynthesisCache.lookup). Mean
duration of the synth.lookup spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "synth.lookup")
