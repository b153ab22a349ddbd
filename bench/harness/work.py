"""Operations and bytes of one sweep-kernel call, from its logical shape.

A call evaluates ``n`` configs over ``l`` layers and reduces them to
``w`` workload segments.  The counts depend on ``(n, l, w)`` and on
whether the precision columns are per layer (``mixed``), never on the
padding or tiling an implementation chooses, so the same work is counted
whatever computes it.

Operations: per (config, layer) pair, the arithmetic of the reference's
layer model (``reference.evaluate``) that depends on the config, as
written there, counting each of ``+ - * / // max min floor ceil`` and
each compare-and-select as one operation:

=====================================================  ====
spatial mapping: sets_fit 2, c_sim 1, k_sim 2,           16
fit_horz 1, three ceil-divisions 6, compute cycles 4
byte counts: ifmap/weight/ofmap bytes 9, filter bytes    42
3, k_fit 2, n_k_glb 2, restream 2, dram bytes 3, dram
elements 3, filt_res 2, w_res 1, spill 3, glb ifmap 3,
glb weight 3, glb psum 2, glb elements 4
stalls: memory cycles 2, total cycles 1                   3
energy: spad 1, MAC 1, GLB 1, leakage 3, sum 3            9
compensated sums of cycles and energy, 4 each             8
=====================================================  ====

78 per pair, plus 6 per (config, segment) for the aggregate columns.

Bytes: the float32 config columns (15, of which the three precision
columns are per layer in a mixed call), the layer table (10 fields per
layer) and the six aggregate columns per segment, unpadded.
"""

from __future__ import annotations

OPS_PER_PAIR = 78
OPS_PER_SEGMENT = 6
CONFIG_COLUMNS = 15
PRECISION_COLUMNS = 3
LAYER_FIELDS = 10
OUTPUT_COLUMNS = 6
F32 = 4


def kernel_ops(n: int, l: int, w: int) -> int:
    return OPS_PER_PAIR * n * l + OPS_PER_SEGMENT * n * w


def kernel_bytes(n: int, l: int, w: int, mixed: bool) -> int:
    per_layer = PRECISION_COLUMNS if mixed else 0
    cols = (CONFIG_COLUMNS - per_layer) * n + per_layer * n * l
    return F32 * (cols + LAYER_FIELDS * l + OUTPUT_COLUMNS * n * w)


def roofline(calls, kernel_s: float, peaks: dict) -> tuple[float, str]:
    """Share (%) of the least time the calls could take at the published
    peaks, over the device time they took; and which bound sets it."""
    ops = sum(kernel_ops(n, l, w) for n, l, w, _ in calls)
    nbytes = sum(kernel_bytes(n, l, w, m) for n, l, w, m in calls)
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
    share, bound = roofline(run.calls, t, run.peaks)
    print(f"sweep kernel roofline: {bound} bound, {len(run.calls)} calls, "
          f"{t:.6f} device s", file=sys.stderr)
    return share
