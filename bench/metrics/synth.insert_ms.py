"""Synthesis per chunk of the stream, one phase: the insert of the missed rows
into the cache, with its compaction past the row cap
(PersistentSynthesisCache.insert). Mean duration of the synth.insert spans,
ms."""

from harness.tracing import mean_ms


def read(run):
    return mean_ms(run.spans, "synth.insert")
