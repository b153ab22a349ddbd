"""Host side of the Pallas call in the decode stream (operand conversion
and padding of the 322-row layer table, segment mask, the jitted call,
the six output slices): mean duration of the kernel.launch spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "kernel.launch")
