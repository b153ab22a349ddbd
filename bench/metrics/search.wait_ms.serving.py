"""Host blocked on the kernel's outputs per evaluation in the resnet50.serving
cell (the np.asarray of the Pallas outputs in dse_batch): mean duration of
the kernel.wait spans, ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "kernel.wait")
