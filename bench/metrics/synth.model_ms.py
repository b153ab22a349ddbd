"""Synthesis per chunk of the stream, one phase: the synthesis math on the rows
the cache missed (synthesize_soa). Mean duration of the synth.model spans,
ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "synth.model")
