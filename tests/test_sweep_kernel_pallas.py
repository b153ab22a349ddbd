"""Pallas sweep kernel parity + routing (ISSUE 9 tentpole).

The hand-tiled Pallas kernel (``repro.kernels.sweep_kernel``) must be an
invisible substitution for the jitted XLA aggregate path: interpret-mode
results match the exact numpy kernel at ≤1e-6 relative on every
aggregate column — across ragged config tails, multi-tile accumulation
on both grid axes, mixed-precision ``(N, L)`` columns, and multi-segment
(multi-workload) reductions — and the ``use_pallas`` routing flag
threads from the public engines down to ``_run_kernel`` with strict
validation.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.accelerator import AcceleratorConfig, configs_to_soa
from repro.core.dse_batch import (AGGREGATE_OUTPUTS, _make_cfg_lay,
                                  _to_jax_inputs,
                                  _sweep_chunked, _sweep_kernel,
                                  _sweep_mixed, _workload_batch,
                                  mixed_assign_cfg, resolve_use_pallas)
from repro.core.pe import PEType
from repro.core.synthesis import synthesize_soa
from repro.core.workloads import get_workload
from repro.kernels.sweep_kernel import (CFG_FIELDS, LAY_FIELDS,
                                        MIXED_CFG_FIELDS, _build_packed_call,
                                        _build_sweep_call, _ceil_to,
                                        _pack_operands, _packed_layout,
                                        _unpack_operands, default_tiling,
                                        resolve_pallas_interpret,
                                        sweep_aggregates_pallas)

RTOL = 1e-6


def _configs(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    types = tuple(PEType)
    return tuple(
        AcceleratorConfig(
            pe_type=types[int(rng.integers(len(types)))],
            pe_rows=int(rng.integers(4, 33)),
            pe_cols=int(rng.integers(4, 33)),
            glb_kb=int(rng.choice([64, 128, 256, 512])),
            dram_bw_gbps=float(rng.choice([6.4, 12.8, 25.6])))
        for _ in range(n))


def _cfg_lay(n: int, workloads=("vgg16",), seed: int = 0):
    """(cfg, lay, bounds) over the concatenated layer axis."""
    soa = configs_to_soa(_configs(n, seed))
    cols = synthesize_soa(soa)
    wbs = [_workload_batch(get_workload(w)) for w in workloads]
    cfg, _ = _make_cfg_lay(soa, cols, wbs[0])
    lay = {k: np.concatenate([wb.arrays[k][None, :] for wb in wbs],
                             axis=1) for k in wbs[0].arrays}
    bounds, s = [], 0
    for wb in wbs:
        L = len(wb.arrays["macs"])
        bounds.append((s, s + L))
        s += L
    return cfg, lay, tuple(bounds)


def _numpy_segments(cfg, lay, bounds):
    """Exact reference: the numpy kernel per workload segment -> (W, N)."""
    out = {k: [] for k in AGGREGATE_OUTPUTS}
    for s, e in bounds:
        sub_lay = {k: v[:, s:e] for k, v in lay.items()}
        sub_cfg = {k: (v[:, s:e] if v.shape[1] > 1 else v)
                   for k, v in cfg.items()}
        agg = _sweep_kernel(np, sub_cfg, sub_lay, outputs="aggregates")
        for k in AGGREGATE_OUTPUTS:
            out[k].append(np.asarray(agg[k], dtype=np.float64))
    return {k: np.stack(v) for k, v in out.items()}


def _assert_close(got: dict, want: dict):
    for k in AGGREGATE_OUTPUTS:
        g = np.asarray(got[k], dtype=np.float64)
        w = np.asarray(want[k], dtype=np.float64)
        assert g.shape == w.shape, k
        rel = np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-30))
        assert rel <= RTOL, (k, rel)


# ---------------------------------------------------------------------------
# interpret-mode parity vs the exact numpy kernel
# ---------------------------------------------------------------------------

def test_interpret_parity_single_workload():
    cfg, lay, _ = _cfg_lay(83)
    got = sweep_aggregates_pallas(cfg, lay, interpret=True)
    want = {k: v[0] for k, v in
            _numpy_segments(cfg, lay, ((0, lay["r"].shape[1]),)).items()}
    assert all(np.shape(got[k]) == (83,) for k in AGGREGATE_OUTPUTS)
    _assert_close(got, want)


def test_multi_tile_ragged_tail():
    """block_n/block_l far smaller than (N, L): the scratch accumulators
    must carry segment sums across layer tiles and the padded ragged
    tail rows/columns must never contaminate real outputs."""
    cfg, lay, _ = _cfg_lay(53, seed=1)
    L = lay["r"].shape[1]
    got = sweep_aggregates_pallas(cfg, lay, block_n=16, block_l=5,
                                  interpret=True)
    want = {k: v[0] for k, v in
            _numpy_segments(cfg, lay, ((0, L),)).items()}
    _assert_close(got, want)


def test_mixed_precision_columns():
    """(N, L) per-layer act/weight-bit + mac-energy columns (the
    co-exploration genome layout) ride the wide BlockSpec path."""
    rng = np.random.default_rng(7)
    cfg, lay, _ = _cfg_lay(40, seed=2)
    L = lay["r"].shape[1]
    assign = rng.integers(0, len(tuple(PEType)), size=(40, L))
    cfg = mixed_assign_cfg(cfg, assign)
    got = sweep_aggregates_pallas(cfg, lay, block_n=16, block_l=4,
                                  interpret=True)
    want = {k: v[0] for k, v in
            _numpy_segments(cfg, lay, ((0, L),)).items()}
    _assert_close(got, want)


def test_multi_segment_bounds():
    """Two workloads on one concatenated layer axis: per-segment masks
    must gate the Kahan updates even when a layer tile straddles the
    segment boundary."""
    cfg, lay, bounds = _cfg_lay(21, workloads=("vgg16", "resnet34"),
                                seed=3)
    got = sweep_aggregates_pallas(cfg, lay, bounds=bounds, block_n=8,
                                  block_l=8, interpret=True)
    want = _numpy_segments(cfg, lay, bounds)
    assert all(np.shape(got[k]) == (2, 21) for k in AGGREGATE_OUTPUTS)
    _assert_close(got, want)


def test_committed_stream_slice_parity():
    """Rows drawn from the committed benchmark stream (the widened
    chunked-scaling grid of dse_sweep_bench) match at ≤1e-6."""
    from repro.core.accelerator import design_space_soa
    soa = next(iter(design_space_soa(
        chunk_size=2048, glb_kbs=(4, 64, 1024, 4096),
        bws=tuple(np.linspace(2.0, 64.0, 156)))))
    cols = synthesize_soa(soa)
    wb = _workload_batch(get_workload("vgg16"))
    cfg, lay = _make_cfg_lay(soa, cols, wb)
    got = sweep_aggregates_pallas(cfg, lay, interpret=True)
    want = {k: np.asarray(v, dtype=np.float64) for k, v in
            _sweep_kernel(np, cfg, lay, outputs="aggregates").items()}
    _assert_close(got, want)


# ---------------------------------------------------------------------------
# the packed launch: one host buffer, one program, the kernel's bits
# ---------------------------------------------------------------------------

def _pad_cfg(a, n_pad, l_pad):
    """Reference padding of one config operand, field by field."""
    n, width = a.shape
    if n_pad > n:
        a = np.concatenate([a, np.repeat(a[-1:], n_pad - n, axis=0)])
    if width > 1 and l_pad > width:
        a = np.concatenate(
            [a, np.repeat(a[:, :1], l_pad - width, axis=1)], axis=1)
    return a


def _pad_lay(a, l_pad):
    width = a.shape[1]
    if l_pad > width:
        a = np.concatenate(
            [a, np.repeat(a[:, :1], l_pad - width, axis=1)], axis=1)
    return a


def _field_operands(cfg, lay, bounds, n_pad, l_pad):
    """The kernel's operands built one field at a time: cast, pad, then
    the segment mask and the float32 segment MAC sums."""
    jcfg, jlay = _to_jax_inputs(cfg, lay, exact=False)
    ops = [_pad_cfg(np.asarray(jcfg[k]), n_pad, l_pad) for k in CFG_FIELDS]
    ops += [_pad_lay(np.asarray(jlay[k]), l_pad) for k in LAY_FIELDS]
    mask = np.zeros((l_pad, len(bounds)), dtype=np.float32)
    for wi, (s, e) in enumerate(bounds):
        mask[s:e, wi] = 1.0
    macs = np.array([[jlay["macs"][0, s:e].sum(dtype=np.float32)
                      for s, e in bounds]], dtype=np.float32)
    return ops + [mask, macs]


def _packed_case(name):
    rng = np.random.default_rng(13)
    if name == "one-segment-padded-rows":
        cfg, lay, _ = _cfg_lay(1000, seed=6)
        return cfg, lay, None
    if name == "three-segments-wide":
        cfg, lay, bounds = _cfg_lay(
            64, workloads=("vgg16", "resnet34", "resnet50"), seed=7)
        assign = rng.integers(0, len(tuple(PEType)),
                              size=(64, lay["r"].shape[1]))
        return mixed_assign_cfg(cfg, assign), lay, bounds
    if name == "two-layer-tiles":
        cfg, lay, _ = _cfg_lay(40, workloads=("resnet50",) * 4, seed=8)
        return cfg, {k: v[:, :200] for k, v in lay.items()}, None
    cfg, lay, _ = _cfg_lay(1, seed=9)                   # one config
    return cfg, lay, None


@pytest.mark.parametrize("case", ["one-segment-padded-rows",
                                  "three-segments-wide", "two-layer-tiles",
                                  "one-config"])
def test_packed_launch_is_bit_exact(case):
    """The program unpacks, bit for bit, the operands the kernel took one
    field at a time, and returns the raw pallas_call's outputs exactly."""
    import jax
    cfg, lay, bounds = _packed_case(case)
    n, l = cfg["pe_rows"].shape[0], lay["r"].shape[1]
    segs = bounds or ((0, l),)
    w = len(segs)
    block_n, block_l = default_tiling(n, l)
    n_pad, l_pad = _ceil_to(n, block_n), _ceil_to(l, block_l)
    mixed_wide = tuple(cfg[k].shape[1] == l and l > 1
                       for k in MIXED_CFG_FIELDS)
    layout, size = _packed_layout(n_pad, l_pad, w, mixed_wide)
    want_ops = _field_operands(cfg, lay, segs, n_pad, l_pad)

    buf = _pack_operands(cfg, lay, n, l, segs, layout, size)
    got_ops = jax.jit(lambda b: _unpack_operands(b, layout, segs, l_pad))(
        buf)
    assert len(got_ops) == len(want_ops)
    for got, want in zip(got_ops, want_ops):
        got = np.asarray(got)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        assert np.array_equal(got.view(np.int32), want.view(np.int32))

    raw = np.asarray(jax.jit(_build_sweep_call(
        n_pad, l_pad, w, block_n, block_l, mixed_wide, True))(*want_ops))
    got = sweep_aggregates_pallas(cfg, lay, bounds=bounds, interpret=True)
    for idx, k in enumerate(AGGREGATE_OUTPUTS):
        block = raw[:n, idx * w:(idx + 1) * w]
        want = block[:, 0] if bounds is None else block.T
        assert np.array_equal(np.asarray(got[k]), want), k


def test_packed_launch_counts_one_buffer_and_does_not_retrace():
    """One call: one kernel.launch span naming one host buffer and its
    bytes, and the same count on kernel.h2d_buffers.  A second call of
    the same shape reuses the program."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    cfg, lay, bounds = _cfg_lay(21, workloads=("vgg16", "resnet34"), seed=3)
    sweep_aggregates_pallas(cfg, lay, bounds=bounds, interpret=True)
    n, l = 21, lay["r"].shape[1]
    fn = _build_packed_call(n, 24, l, bounds, 24, l, (False,) * 3, False,
                            True)
    traces, misses = fn._cache_size(), _build_packed_call.cache_info().misses
    before = obs_metrics.snapshot().get("kernel.h2d_buffers", 0)
    obs_trace.configure(enabled=True, reset=True)
    try:
        sweep_aggregates_pallas(cfg, lay, bounds=bounds, interpret=True)
        spans = [s.as_dict() for s in obs_trace.get_tracer().spans()]
    finally:
        obs_trace.disable()
        obs_trace.configure(enabled=False, reset=True)
    launch, = [s for s in spans if s["name"] == "kernel.launch"]
    assert launch["attrs"]["buffers"] == 1
    assert launch["attrs"]["bytes"] == 4 * (15 * 24 + 11 * l + 2)
    after = obs_metrics.snapshot()["kernel.h2d_buffers"]
    assert after - before == launch["attrs"]["buffers"]
    assert fn._cache_size() == traces == 1
    assert _build_packed_call.cache_info().misses == misses


# ---------------------------------------------------------------------------
# guards + mode resolution
# ---------------------------------------------------------------------------

def test_validation_guards():
    cfg, lay, _ = _cfg_lay(8)
    bad = dict(cfg)
    del bad["pe_rows"]
    with pytest.raises(ValueError, match="missing field"):
        sweep_aggregates_pallas(bad, lay)
    bad = dict(cfg, pe_rows=cfg["pe_rows"][:, 0])    # (N,) not (N, 1)
    with pytest.raises(ValueError, match="shape"):
        sweep_aggregates_pallas(bad, lay)
    with pytest.raises(ValueError, match="bounds"):
        sweep_aggregates_pallas(cfg, lay, bounds=((0, 0),))
    with pytest.raises(ValueError, match="bounds"):
        sweep_aggregates_pallas(
            cfg, lay, bounds=((0, lay["r"].shape[1] + 1),))
    with pytest.raises(ValueError, match="block sizes"):
        sweep_aggregates_pallas(cfg, lay, block_n=0)


def test_mode_resolution_cpu():
    """On the CPU-only CI host interpret mode auto-resolves on; an
    explicit bool wins."""
    from repro.core.dse_batch import _jax_has_accelerator
    if _jax_has_accelerator():          # pragma: no cover - device CI
        pytest.skip("accelerator attached")
    assert resolve_pallas_interpret(None) is True
    assert resolve_pallas_interpret(False) is False
    assert resolve_pallas_interpret(True) is True


def test_resolve_use_pallas_routing():
    assert resolve_use_pallas(False, "numpy") is False
    assert resolve_use_pallas(None, "numpy") is False
    assert resolve_use_pallas(True, "jax") is True
    with pytest.raises(ValueError, match="numpy"):
        resolve_use_pallas(True, "numpy")
    with pytest.raises(ValueError, match="mesh"):
        resolve_use_pallas(True, "jax", mesh=object())


# ---------------------------------------------------------------------------
# routing through the public engines
# ---------------------------------------------------------------------------

def test_sweep_mixed_use_pallas_matches_xla(jax_usable):
    if not jax_usable:
        pytest.skip("jax unusable")
    from repro.core.pe import mode_compat_matrix
    rng = np.random.default_rng(11)
    wl = get_workload("vgg16")
    soa = configs_to_soa(_configs(24, seed=4))
    # per-layer modes drawn from each config's *compatible* mode set
    compat = mode_compat_matrix()[soa["pe_type_idx"]]     # (N, T)
    assign = np.stack([
        rng.choice(np.nonzero(row)[0], size=len(wl.layers))
        for row in compat])
    base = _sweep_mixed(wl, soa, assign, backend="jax",
                        outputs="aggregates", use_pallas=False)
    pal = _sweep_mixed(wl, soa, assign, backend="jax",
                       outputs="aggregates", use_pallas=True)
    _assert_close({k: pal[k] for k in AGGREGATE_OUTPUTS},
                  {k: np.asarray(base[k], dtype=np.float64)
                   for k in AGGREGATE_OUTPUTS})


def test_chunked_stream_use_pallas(jax_usable):
    if not jax_usable:
        pytest.skip("jax unusable")
    wl = get_workload("vgg16")
    feed = list(_configs(36, seed=5))
    res = _sweep_chunked(wl, [feed], chunk_size=16, backend="jax",
                         use_pallas=True, use_cache=False)
    assert res.timings["use_pallas"] is True
    ref = _sweep_chunked(wl, [feed], chunk_size=16, backend="numpy",
                         overlap=False, use_cache=False)
    assert res.front_size == ref.front_size
    for m in ref.front_metrics:
        np.testing.assert_allclose(
            np.sort(res.front_metrics[m]), np.sort(ref.front_metrics[m]),
            rtol=1e-5)


def test_evaluator_use_pallas_parity(jax_usable):
    if not jax_usable:
        pytest.skip("jax unusable")
    from repro.explore import CoExploreSpace
    from repro.explore.search import random_search
    wl = get_workload("vgg16")
    space = CoExploreSpace(n_layers=len(wl.layers))
    base = random_search(space, wl, 48, seed=9, backend="jax",
                         use_pallas=False)
    pal = random_search(space, wl, 48, seed=9, backend="jax",
                        use_pallas=True)
    assert pal.stats["use_pallas"] is True
    np.testing.assert_allclose(pal.front_objectives,
                               base.front_objectives, rtol=1e-5)


def test_explore_spec_use_pallas_validation():
    from repro.core.dse import ExploreSpec
    with pytest.raises(ValueError, match="numpy"):
        ExploreSpec.single("vgg16", backend="numpy", use_pallas=True)
    with pytest.raises(ValueError, match="prefetch_depth"):
        ExploreSpec.single("vgg16", prefetch_depth=0, chunk_size=8)
    with pytest.raises(ValueError, match="chunk_size"):
        ExploreSpec.single("vgg16", prefetch_depth=4)
    spec = ExploreSpec.single("vgg16", chunk_size=8, prefetch_depth=4)
    assert spec.prefetch_depth == 4 and spec.use_pallas is None
