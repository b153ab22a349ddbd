"""Host side of the Pallas sweep-kernel call per chunk of the stream
(kernels/sweep_kernel.py: operand conversion and padding, segment mask, the
jitted call, the six output slices): mean duration of the kernel.launch
spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "kernel.launch")
