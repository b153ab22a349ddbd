"""Host blocked on a chunk's kernel outputs in the decode stream
(dse_batch.drain_one, until the outputs are on the host; it grows when the
kernel sets the pace): mean duration of the kernel.wait spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "kernel.wait")
