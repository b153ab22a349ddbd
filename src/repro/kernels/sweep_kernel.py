"""Pallas sweep kernel — the hot (N x L) mapping + segment reduction.

The streamed DSE pipeline spends its device time in one place: the
row-stationary mapping + energy model over an ``(N configs, L layers)``
grid followed by a per-workload-segment reduction down to the
:data:`repro.core.dse_batch.AGGREGATE_OUTPUTS` columns.  The generic jax
path jits that as unfused XLA ops; this module writes it as a real Pallas
kernel with explicit tiling, following the tiling / ``pl.when``-epilogue /
scratch-accumulator idiom of :mod:`repro.kernels.w4a8_matmul`:

* grid ``(N/block_n, L/block_l)`` with the **layer axis innermost**, so
  each config tile revisits its output block while four ``(block_n, W)``
  VMEM scratch accumulators carry the running per-segment Kahan sums
  (cycles + energy, value + compensation) across layer tiles;
* the per-tile body *reuses* the shared array-namespace kernel
  (:func:`repro.core.dse_batch._sweep_kernel` with ``exact=False,
  outputs="layer_totals"``) on the tile's refs — one source of truth for
  the PPA math, so Pallas results track the jitted XLA path op-for-op;
* a ``(block_l, W)`` segment mask gates the sequential Kahan update per
  layer column, reproducing :func:`repro.core.dse_batch._kahan_sum_rows`
  over each ``[start, end)`` workload segment exactly (padded layer
  columns carry an all-zero mask and never touch the accumulators);
* the ``pl.when(l == n_l - 1)`` epilogue converts the accumulated sums to
  the six aggregate columns (latency, energy_j, throughput, perf/area)
  with the same formulas as ``_segment_aggregates``, writing one
  ``(block_n, 6 * W)`` output block per config tile.

``interpret=True`` (auto-selected when no accelerator platform is
attached) runs the same kernel through the Pallas interpreter on CPU —
bit-comparable to the jitted XLA path at the usual f32 tolerance, which
CI asserts at ≤1e-6 relative against the exact numpy kernel.  On a TPU
the kernel is compiled by Mosaic, which needs every block's last two
dimensions to be multiples of ``(8, 128)`` or the whole array's: the
layer axis is one tile up to 128 layers and 128-wide tiles beyond.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.dse_batch import (_CFG_INT32, _LAY_INT32, AGGREGATE_OUTPUTS,
                                  _jax_has_accelerator, _sweep_kernel)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# operand order of the pallas_call — every cfg/lay field the mapping +
# energy model reads, one ref each (dicts don't cross the pallas boundary)
CFG_FIELDS = ("pe_rows", "pe_cols", "num_pes", "act_bits", "weight_bits",
              "glb_kb", "glb_bits", "filter_spad", "psum_spad",
              "spad_bits", "dram_bw_gbps", "mac_energy_pj", "clock_ghz",
              "area_mm2", "leak_mw")
LAY_FIELDS = ("r", "s", "e", "f", "c", "k", "h", "w", "batch", "groups",
              "macs")
# the per-layer precision columns that may be (N, L) instead of (N, 1)
MIXED_CFG_FIELDS = ("act_bits", "weight_bits", "mac_energy_pj")
# TPU vector lane width: the layer-axis tile of the compiled kernel
_LANES = 128


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def resolve_pallas_interpret(interpret: bool | None = None) -> bool:
    """``None`` -> interpreter mode exactly when no accelerator platform
    is attached (the CPU-CI path); an explicit bool wins."""
    if interpret is None:
        return not _jax_has_accelerator()
    return bool(interpret)


def default_tiling(n: int, l: int) -> tuple[int, int]:
    """``(block_n, block_l)`` for ``n`` configs over ``l`` layers: config
    tiles of up to 512 rows, and the whole layer axis as one tile up to
    128 layers (128-wide tiles beyond) so every block shape compiles."""
    return min(512, _ceil_to(n, 8)), (l if l <= _LANES else _LANES)


def _sweep_block_body(*refs, n_l: int, block_l: int, w: int):
    """One ``(block_n, block_l)`` tile: mapping + masked segment Kahan
    accumulation, epilogue on the last layer tile."""
    n_cfg, n_lay = len(CFG_FIELDS), len(LAY_FIELDS)
    cfg_refs = refs[:n_cfg]
    lay_refs = refs[n_cfg:n_cfg + n_lay]
    mask_ref, macs_ref, out_ref = refs[n_cfg + n_lay:n_cfg + n_lay + 3]
    acc_c, cmp_c, acc_e, cmp_e = refs[n_cfg + n_lay + 3:]

    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init():
        acc_c[...] = jnp.zeros_like(acc_c)
        cmp_c[...] = jnp.zeros_like(cmp_c)
        acc_e[...] = jnp.zeros_like(acc_e)
        cmp_e[...] = jnp.zeros_like(cmp_e)

    cfg = {k: r[...] for k, r in zip(CFG_FIELDS, cfg_refs)}
    lay = {k: r[...] for k, r in zip(LAY_FIELDS, lay_refs)}
    totals = _sweep_kernel(jnp, cfg, lay, exact=False,
                           outputs="layer_totals")
    tc = totals["total_cycles"]            # (block_n, block_l) f32
    ep = totals["energy_pj"]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block_l), 1)

    # Sequential compensated accumulation, one layer column at a time,
    # gated per segment: a segment's accumulator advances only on its own
    # columns, so each (config, segment) cell sees exactly the Kahan
    # update sequence of _kahan_sum_rows over that segment's slice.  A
    # rolled loop keeps the compiled kernel's size independent of
    # block_l; column j is picked out by a masked lane sum, which is exact
    # (every other term is +0.0).
    def _column(j, carry):
        sel = mask_ref[pl.ds(j, 1), :] > 0.5   # (1, w): layer j's segment(s)
        pick = col == j
        for acc_ref, cmp_ref, x in ((acc_c, cmp_c, tc),
                                    (acc_e, cmp_e, ep)):
            xj = jnp.sum(jnp.where(pick, x, 0.0), axis=1, keepdims=True)
            acc = acc_ref[...]
            comp = cmp_ref[...]
            y = xj - comp                      # (block_n, w)
            t = acc + y
            c2 = (t - acc) - y
            acc_ref[...] = jnp.where(sel, t, acc)
            cmp_ref[...] = jnp.where(sel, c2, comp)
        return carry

    jax.lax.fori_loop(0, block_l, _column, 0)

    @pl.when(l_idx == n_l - 1)
    def _epilogue():
        cycles = acc_c[...]                          # (block_n, w)
        energy = acc_e[...]
        clk = cfg["clock_ghz"]                       # (block_n, 1)
        latency_s = cycles / (clk * 1e9)
        energy_j = energy / 1e12
        throughput = macs_ref[...] / latency_s / 1e9  # (1, w) / (bn, w)
        perf_per_area = throughput / cfg["area_mm2"]
        out_ref[...] = jnp.concatenate(
            [cycles, energy, latency_s, energy_j, throughput,
             perf_per_area], axis=1)


def _build_sweep_call(n_pad: int, l_pad: int, w: int, block_n: int,
                      block_l: int, mixed_wide: tuple[bool, ...],
                      interpret: bool):
    """The pallas_call for one (shape, tiling, mode) signature: 15 config
    operands, 11 layer rows, the ``(l_pad, w)`` segment mask and the
    ``(1, w)`` segment MACs in, one ``(n_pad, 6 * w)`` block out."""
    n_l = l_pad // block_l
    wide = dict(zip(MIXED_CFG_FIELDS, mixed_wide))

    cfg_block = pl.BlockSpec((block_n, 1), lambda i, l: (i, 0))
    cfg_block_wide = pl.BlockSpec((block_n, block_l), lambda i, l: (i, l))
    lay_block = pl.BlockSpec((1, block_l), lambda i, l: (0, l))
    in_specs = [cfg_block_wide if wide.get(name, False) else cfg_block
                for name in CFG_FIELDS]
    in_specs += [lay_block for _ in LAY_FIELDS]
    in_specs.append(pl.BlockSpec((block_l, w), lambda i, l: (l, 0)))
    in_specs.append(pl.BlockSpec((1, w), lambda i, l: (0, 0)))

    return pl.pallas_call(
        functools.partial(_sweep_block_body, n_l=n_l, block_l=block_l,
                          w=w),
        grid=(n_pad // block_n, n_l),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, 6 * w), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 6 * w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, w), jnp.float32)
                        for _ in range(4)],
        interpret=interpret,
    )


# The host hands the program one int32 buffer: every operand but the
# segment mask, flattened row-major one after another, float32 fields
# bit-cast into it.  Each host-to-device transfer and each program launch
# has a fixed cost that dwarfs a chunk's ~2 MB, so one buffer in and one
# program per call.
_INT32_FIELDS = frozenset(_CFG_INT32) | frozenset(_LAY_INT32)


@functools.lru_cache(maxsize=64)
def _packed_layout(n_pad: int, l_pad: int, w: int,
                   mixed_wide: tuple[bool, ...]):
    """``((name, offset, shape), ...)`` of each packed operand, in
    pallas_call order with ``"seg_macs"`` last, and the buffer length."""
    wide = dict(zip(MIXED_CFG_FIELDS, mixed_wide))
    shapes = [(name, (n_pad, l_pad if wide.get(name, False) else 1))
              for name in CFG_FIELDS]
    shapes += [(name, (1, l_pad)) for name in LAY_FIELDS]
    shapes.append(("seg_macs", (1, w)))
    layout, off = [], 0
    for name, shape in shapes:
        layout.append((name, off, shape))
        off += shape[0] * shape[1]
    return tuple(layout), off


def _pack_operands(cfg: dict, lay: dict, n: int, l: int,
                   bounds: tuple[tuple[int, int], ...], layout,
                   size: int) -> np.ndarray:
    """Cast each field to int32 / float32 as it is copied into one
    buffer.  Padded config rows repeat the last row (valid throwaway
    work); padded layer columns repeat layer 0 (masked out of every
    segment)."""
    buf = np.empty(size, dtype=np.int32)
    fbuf = buf.view(np.float32)
    n_cfg = len(CFG_FIELDS)
    for name, off, (rows, width) in layout[:n_cfg]:
        dst = (buf if name in _INT32_FIELDS else fbuf)[
            off:off + rows * width].reshape(rows, width)
        src = cfg[name]
        dst[:n, :src.shape[1]] = src
        if width > src.shape[1]:
            dst[:n, src.shape[1]:] = dst[:n, :1]
        if rows > n:
            dst[n:] = dst[n - 1]
    for name, off, (_, width) in layout[n_cfg:-1]:
        dst = (buf if name in _INT32_FIELDS else fbuf)[off:off + width]
        dst[:l] = lay[name][0]
        dst[l:] = dst[0]
    offsets = {name: off for name, off, _ in layout}
    macs = fbuf[offsets["macs"]:offsets["macs"] + l]
    seg = offsets["seg_macs"]
    fbuf[seg:seg + len(bounds)] = [macs[s:e].sum(dtype=np.float32)
                                   for s, e in bounds]
    return buf


def _segment_mask(bounds: tuple[tuple[int, int], ...],
                  l_pad: int) -> np.ndarray:
    """``(l_pad, w)``: 1 where layer ``j`` belongs to segment ``w``."""
    mask = np.zeros((l_pad, len(bounds)), dtype=np.float32)
    for wi, (s, e) in enumerate(bounds):
        mask[s:e, wi] = 1.0
    return mask


def _unpack_operands(buf, layout, bounds: tuple[tuple[int, int], ...],
                     l_pad: int) -> list:
    """The pallas_call's operands from the packed buffer (traced inside
    the program): XLA slices and reshapes, float32 fields bit-cast back,
    the segment mask a constant of the program."""
    ops = []
    for name, off, shape in layout:
        x = jax.lax.slice(buf, (off,), (off + shape[0] * shape[1],))
        x = x.reshape(shape)
        if name not in _INT32_FIELDS:
            x = jax.lax.bitcast_convert_type(x, jnp.float32)
        ops.append(x)
    seg_macs = ops.pop()
    return ops + [jnp.asarray(_segment_mask(bounds, l_pad)), seg_macs]


@functools.lru_cache(maxsize=64)
def _build_packed_call(n: int, n_pad: int, l_pad: int,
                       bounds: tuple[tuple[int, int], ...], block_n: int,
                       block_l: int, mixed_wide: tuple[bool, ...],
                       squeeze: bool, interpret: bool):
    """One jitted program per signature — cached so a steady-state chunk
    stream traces exactly once: unpack the buffer, run the pallas_call,
    return the six aggregate columns sliced to ``n``, ``(N,)`` when
    ``squeeze`` else ``(W, N)``."""
    w = len(bounds)
    layout, _ = _packed_layout(n_pad, l_pad, w, mixed_wide)
    call = _build_sweep_call(n_pad, l_pad, w, block_n, block_l,
                             mixed_wide, interpret)

    # XLA names the Pallas call's instruction after the jitted function;
    # profiles, and the benchmark's kernel readers, know the kernel's
    # device op as tpu_custom_call
    def tpu_custom_call(buf):
        out = call(*_unpack_operands(buf, layout, bounds, l_pad))
        result = {}
        for idx, name in enumerate(AGGREGATE_OUTPUTS):
            block = out[:n, idx * w:(idx + 1) * w]     # (N, W)
            result[name] = block[:, 0] if squeeze else block.T
        return result

    return jax.jit(tpu_custom_call)


def sweep_aggregates_pallas(cfg: dict, lay: dict, *,
                            bounds: tuple[tuple[int, int], ...] | None = None,
                            block_n: int | None = None,
                            block_l: int | None = None,
                            interpret: bool | None = None) -> dict:
    """Aggregate sweep columns via the Pallas kernel.

    ``cfg`` / ``lay`` are the float64/int64 arrays of
    :func:`repro.core.dse_batch._make_cfg_lay`.  Each field is cast to
    int32 / float32 (the x64-free policy) as it is copied, padded, into
    one packed host buffer; one jitted program per signature unpacks it
    on the device, runs the kernel and slices the outputs, so a call is
    one host-to-device buffer and one program launch.  ``bounds=None``
    treats the whole layer axis as one workload and returns
    ``{column: (N,)}`` like
    ``_run_kernel(..., outputs="aggregates")``; explicit ``bounds``
    returns ``{column: (W, N)}`` like ``_sweep_mixed_many``.  Results are
    jax arrays (dispatch is async under jit) — ``np.asarray`` to
    materialize.
    """
    missing = [k for k in CFG_FIELDS if k not in cfg]
    if missing:
        raise ValueError(
            f"sweep_aggregates_pallas: cfg is missing field(s) {missing}; "
            f"build it with repro.core.dse_batch._make_cfg_lay")
    missing = [k for k in LAY_FIELDS if k not in lay]
    if missing:
        raise ValueError(
            f"sweep_aggregates_pallas: lay is missing field(s) {missing}; "
            f"build it with repro.core.dse_batch._make_cfg_lay")
    n = int(np.shape(cfg["pe_rows"])[0])
    l = int(np.shape(lay["r"])[1])
    if n < 1 or l < 1:
        raise ValueError(
            f"sweep_aggregates_pallas: need at least one config and one "
            f"layer, got N={n}, L={l}")
    for name in CFG_FIELDS:
        shp = np.shape(cfg[name])
        want_widths = (1, l) if name in MIXED_CFG_FIELDS else (1,)
        if len(shp) != 2 or shp[0] != n or shp[1] not in want_widths:
            raise ValueError(
                f"sweep_aggregates_pallas: cfg[{name!r}] has shape {shp}; "
                f"expected ({n}, w) with w in {want_widths} — pass the "
                f"(N, 1) column form (or (N, L) for per-layer precision "
                f"fields)")
    for name in LAY_FIELDS:
        shp = np.shape(lay[name])
        if shp != (1, l):
            raise ValueError(
                f"sweep_aggregates_pallas: lay[{name!r}] has shape {shp}; "
                f"expected (1, {l})")
    squeeze = bounds is None
    if bounds is None:
        bounds = ((0, l),)
    bounds = tuple((int(s), int(e)) for s, e in bounds)
    for s, e in bounds:
        if not (0 <= s < e <= l):
            raise ValueError(
                f"sweep_aggregates_pallas: segment bounds ({s}, {e}) are "
                f"not a non-empty slice of the {l}-layer axis")
    w = len(bounds)

    interpret = resolve_pallas_interpret(interpret)
    auto_n, auto_l = default_tiling(n, l)
    block_n = auto_n if block_n is None else block_n
    block_l = auto_l if block_l is None else block_l
    if block_n < 1 or block_l < 1:
        raise ValueError(
            f"sweep_aggregates_pallas: block sizes must be >= 1, got "
            f"block_n={block_n}, block_l={block_l}")

    n_pad = _ceil_to(n, block_n)
    l_pad = _ceil_to(l, block_l)
    mixed_wide = tuple(np.shape(cfg[name])[1] == l and l > 1
                       for name in MIXED_CFG_FIELDS)
    layout, size = _packed_layout(n_pad, l_pad, w, mixed_wide)
    with obs_trace.span("kernel.launch", n=n, l=l, w=w,
                        tiles=l_pad // block_l, buffers=1,
                        bytes=4 * size):
        buf = _pack_operands(cfg, lay, n, l, bounds, layout, size)
        fn = _build_packed_call(n, n_pad, l_pad, bounds, block_n, block_l,
                                mixed_wide, squeeze, interpret)
        result = fn(buf)                   # async
    obs_metrics.get_registry().inc("kernel.h2d_buffers")
    return result
