"""Operations and bytes of one sweep-kernel call, from its logical shape.

A call evaluates ``n`` configs over ``l`` layers and reduces them to
``w`` workload segments.  The counts depend on ``(n, l, w)``, on whether
the precision columns are per layer (``mixed``) and on the layer models
of the networks whose layers the call holds, never on the padding or
tiling an implementation chooses, so the same work is counted whatever
computes it.

Operations: each network's layer model counts its layers' share
(``kernel_work`` in ``bench/layers/<model>.py``; 78 per (config, layer)
pair for ``row_stationary``), plus 6 per (config, segment) for the
aggregate columns.

Bytes: the float32 config columns (15, of which the three precision
columns are per layer in a mixed call), the layer table (each layer
model's bytes; 10 float32 fields per layer for ``row_stationary``) and
the six aggregate columns per segment, unpadded.

Where no networks are given, every layer counts as a ``row_stationary``
layer.
"""

from __future__ import annotations

from harness import spec

OPS_PER_SEGMENT = 6
CONFIG_COLUMNS = 15
PRECISION_COLUMNS = 3
OUTPUT_COLUMNS = 6
F32 = 4

_CONV = spec.load_layer_model(spec.DEFAULT_LAYER_MODEL)


def layer_work(l: int, networks=None) -> tuple[int, int]:
    """Operations per config and layer-table bytes of a call's ``l``
    layers, which are the layers of ``networks`` in turn."""
    if networks is None:
        return (_CONV.OPS_PER_LAYER * l, F32 * _CONV.LAYER_FIELDS * l)
    if sum(net.n_layers for net in networks) != l:
        raise ValueError(f"a call over {l} layers, networks of "
                         f"{[net.n_layers for net in networks]}")
    work = [net.model.kernel_work(net.rows) for net in networks]
    return sum(ops for ops, _ in work), sum(b for _, b in work)


def kernel_ops(n: int, l: int, w: int, networks=None) -> int:
    return n * layer_work(l, networks)[0] + OPS_PER_SEGMENT * n * w


def kernel_bytes(n: int, l: int, w: int, mixed: bool, networks=None) -> int:
    per_layer = PRECISION_COLUMNS if mixed else 0
    cols = (CONFIG_COLUMNS - per_layer) * n + per_layer * n * l
    return (F32 * (cols + OUTPUT_COLUMNS * n * w)
            + layer_work(l, networks)[1])


def roofline(calls, kernel_s: float, peaks: dict,
             networks=None) -> tuple[float, str]:
    """Share (%) of the least time the calls could take at the published
    peaks, over the device time they took; and which bound sets it.
    Every call holds the layers of ``networks``."""
    ops = sum(kernel_ops(n, l, w, networks) for n, l, w, _ in calls)
    nbytes = sum(kernel_bytes(n, l, w, m, networks) for n, l, w, m in calls)
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / kernel_s, bound


# The trace names the Pallas sweep kernel only by its custom call: it is
# the one custom call these cells run.
KERNEL_OP = "tpu_custom_call"


def read_roofline(run):
    """A reader's body: the sweep kernel's roofline share in the window,
    or None where the trace shows no kernel time."""
    import sys
    t = run.trace.kernel_s(KERNEL_OP)
    if not run.calls or t <= 0:
        return None
    share, bound = roofline(run.calls, t, run.peaks, run.networks)
    print(f"sweep kernel roofline: {bound} bound, {len(run.calls)} calls, "
          f"{t:.6f} device s", file=sys.stderr)
    return share
