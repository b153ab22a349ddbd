"""Synthesis per chunk of the stream, one phase: the confighash digests of the
chunk's rows (config_digests). Mean duration of the synth.digest spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "synth.digest")
