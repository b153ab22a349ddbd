"""Grouped GEMM rows: a row of ``groups = g`` is g independent copies of
its one-group row, each with its own operands, run one after another.

* ``g = 1`` leaves every conv and FC row of the paper's networks priced
  bit for bit as before groups existed (a frozen copy of the ungrouped
  ``map_layer`` below);
* a g-group row is g one-group rows summed;
* the scalar ``map_layer``, the batched numpy kernel (bit-exact), the
  jitted XLA kernel and the Pallas kernel (1e-6 relative) agree on a
  small MLA + MoE decode step;
* ``mla_moe_decode_step`` at Moonlight-16B-A3B's published widths has
  the rows and MACs its table says.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.core.accelerator import (AcceleratorConfig, configs_to_soa,
                                    design_space)
from repro.core.dataflow import (LayerResult, leakage_mw, map_layer,
                                 run_workload)
from repro.core.dse_batch import (AGGREGATE_OUTPUTS, _make_cfg_lay,
                                  _run_kernel, _sweep_kernel,
                                  _workload_batch)
from repro.core.pe import (PEType, rf_access_energy_pj,
                           sram_access_energy_pj)
from repro.core.synthesis import synthesize, synthesize_soa
from repro.core.workloads import (gemm, get_workload, mla_moe_decode_step)

RTOL = 1e-6

# Moonlight-16B-A3B's config.json (huggingface.co/moonshotai/
# Moonlight-16B-A3B), the keys the decode builder reads
MOONLIGHT = {
    "hidden_size": 2048, "num_hidden_layers": 27,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "intermediate_size": 11264, "n_routed_experts": 64,
    "moe_intermediate_size": 1408, "num_experts_per_tok": 6,
    "n_shared_experts": 2, "num_attention_heads": 16, "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "vocab_size": 163840,
}

TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "intermediate_size": 96, "n_routed_experts": 4,
    "moe_intermediate_size": 32, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "num_attention_heads": 4, "q_lora_rank": None,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "vocab_size": 256,
}


def tiny_step():
    return mla_moe_decode_step(TINY, batch=4, context=32)


# ---------------------------------------------------------------------------
# map_layer as it stood before rows had groups, kept frozen
# ---------------------------------------------------------------------------

def _frozen_map_layer(layer, cfg, clock_ghz, area_mm2, leak_mw):
    s = cfg.spec
    r, e, f_, ss = layer.r, layer.e, layer.f, layer.s
    c, k, n = layer.c, layer.k, layer.batch
    sets_fit = max(1, cfg.pe_rows // r)
    c_simult = min(c, sets_fit)
    k_simult = max(1, sets_fit // c_simult)
    fit_horz = min(e, cfg.pe_cols)
    n_e_groups = math.ceil(e / fit_horz)
    n_c_groups = math.ceil(c / c_simult)
    n_k_groups = math.ceil(k / k_simult)
    passes = n * n_e_groups * n_c_groups * n_k_groups
    compute_cycles = passes * ss * f_
    macs = layer.macs
    utilization = macs / max(1, compute_cycles * cfg.num_pes)
    ab, wb = s.act_bits, s.weight_bits
    ifmap_elems = n * c * layer.h * layer.w
    weight_elems = k * c * r * ss
    ofmap_elems = n * k * e * f_
    ifmap_bytes = ifmap_elems * ab // 8
    weight_bytes = weight_elems * wb // 8
    ofmap_bytes = ofmap_elems * ab // 8
    glb_half = cfg.glb_kb * 1024 // 2
    filt_bytes_one = max(1, c * r * ss * wb // 8)
    k_fit_glb = max(1, glb_half // filt_bytes_one)
    n_k_glb = math.ceil(k / k_fit_glb)
    ifmap_resident = ifmap_bytes <= glb_half
    ifmap_dram = ifmap_bytes * (1 if ifmap_resident else n_k_glb)
    dram_bytes = ifmap_dram + weight_bytes + ofmap_bytes
    dram_elems = ifmap_elems * (1 if ifmap_resident else n_k_glb) \
        + weight_elems + ofmap_elems
    k_res = max(1, cfg.filter_spad // max(1, ss))
    glb_ifmap = ifmap_elems * math.ceil(n_k_groups / k_res)
    w_res = min(n_e_groups, max(1, cfg.filter_spad // max(1, ss)))
    glb_weight = weight_elems * max(1, n_e_groups // w_res)
    spill = 0 if cfg.psum_spad >= f_ else (n_c_groups - 1)
    glb_psum = 2 * ofmap_elems * max(0, spill)
    glb_elems = 2 * dram_elems + glb_ifmap + glb_weight + glb_psum
    glb_bytes = glb_elems * ab // 8
    bw_bytes_per_cycle = cfg.dram_bw_gbps / clock_ghz
    mem_cycles = int(dram_bytes / max(1e-9, bw_bytes_per_cycle))
    total_cycles = max(compute_cycles, mem_cycles)
    spad_bits = s.scratchpad_bits(cfg.ifmap_spad, cfg.filter_spad,
                                  cfg.psum_spad)
    spad_accesses = 3 * macs
    e_spad = spad_accesses * rf_access_energy_pj(spad_bits)
    e_mac = macs * s.mac_energy_pj
    e_glb = glb_elems * sram_access_energy_pj(cfg.glb_bits)
    e_leak = leak_mw * 1e-3 * (total_cycles / (clock_ghz * 1e9)) * 1e12
    energy_pj = e_mac + e_spad + e_glb + e_leak
    return LayerResult(
        name=layer.name, macs=macs, compute_cycles=compute_cycles,
        mem_cycles=mem_cycles, total_cycles=total_cycles,
        utilization=utilization, spad_accesses=spad_accesses,
        glb_bytes=glb_bytes, dram_bytes=dram_bytes, energy_pj=energy_pj)


@pytest.fixture(scope="module")
def paper_grid():
    configs = tuple(design_space())
    assert len(configs) == 720
    soa = configs_to_soa(configs)
    return configs, soa, synthesize_soa(soa)


@pytest.mark.parametrize("name", ["vgg16", "resnet34", "resnet50"])
def test_one_group_rows_are_priced_as_before_groups(name, paper_grid):
    configs, soa, cols = paper_grid
    wl = get_workload(name)
    assert all(l.groups == 1 for l in wl.layers)
    wb = _workload_batch(wl)
    cfg, lay = _make_cfg_lay(soa, cols, wb)
    out = _sweep_kernel(np, cfg, lay, outputs="full")
    for i, c in enumerate(configs):
        clk, area = float(cols["clock_ghz"][i]), float(cols["area_mm2"][i])
        leak = leakage_mw(c)
        for j, layer in enumerate(wl.layers):
            want = _frozen_map_layer(layer, c, clk, area, leak)
            assert map_layer(layer, c, clk, area, leak) == want
            for k in ("compute_cycles", "mem_cycles", "total_cycles",
                      "utilization", "glb_bytes", "dram_bytes",
                      "energy_pj"):
                assert out[k][i, j] == getattr(want, k), (k, i, j)


# ---------------------------------------------------------------------------
# A g-group row is g one-group rows
# ---------------------------------------------------------------------------

def _random_configs(n: int, seed: int):
    rng = np.random.default_rng(seed)
    types = tuple(PEType)
    return tuple(
        AcceleratorConfig(
            pe_type=types[int(rng.integers(len(types)))],
            pe_rows=int(rng.integers(4, 33)),
            pe_cols=int(rng.integers(4, 33)),
            glb_kb=int(rng.choice([4, 16, 64, 512, 4096])),
            dram_bw_gbps=float(rng.uniform(2.0, 64.0)))
        for _ in range(n))


ROWS = [("scores", 16, 576, 4096, 64), ("experts", 6, 2048, 2816, 64),
        ("absorb", 64, 128, 512, 16), ("odd", 5, 300, 70, 7),
        ("context", 3, 40, 24, 3)]


@pytest.mark.parametrize("name,m,k,n,g", ROWS, ids=[r[0] for r in ROWS])
def test_a_grouped_row_is_its_groups_summed(name, m, k, n, g):
    grouped = gemm(name, m, k, n, groups=g)
    one = dataclasses.replace(grouped, groups=1)
    assert grouped.macs == g * one.macs == g * m * k * n
    for c in _random_configs(24, seed=g):
        rep = synthesize(c)
        for mode in (None, PEType.LIGHTPE1):
            if mode is not None and c.pe_type is not PEType.FP32:
                continue
            # leakage 0: the energy is the dynamic energy alone
            got = map_layer(grouped, c, rep.clock_ghz, rep.area_mm2, 0.0,
                            mode=mode)
            copy = map_layer(one, c, rep.clock_ghz, rep.area_mm2, 0.0,
                             mode=mode)
            assert got.macs == g * copy.macs
            assert got.compute_cycles == g * copy.compute_cycles
            assert got.dram_bytes == g * copy.dram_bytes
            assert got.spad_accesses == g * copy.spad_accesses
            assert got.energy_pj == math.fsum([copy.energy_pj] * g)
            # the memory cycles floor the g copies' bytes together
            assert 0 <= got.total_cycles - g * copy.total_cycles < g
            assert got.utilization == pytest.approx(copy.utilization,
                                                    rel=1e-15)


# ---------------------------------------------------------------------------
# Every path prices grouped rows alike, on a small decode step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_batch():
    wl = tiny_step()
    configs = _random_configs(40, seed=3)
    soa = configs_to_soa(configs)
    cols = synthesize_soa(soa)
    cfg, lay = _make_cfg_lay(soa, cols, _workload_batch(wl))
    return wl, configs, cfg, lay


def test_tiny_step_has_every_row_kind():
    wl = tiny_step()
    assert len(wl.layers) == 9 + 12 + 1
    groups = {l.name.split(".")[-1]: l.groups for l in wl.layers}
    assert groups == {"q_proj": 1, "kv_a": 1, "q_absorb": 4, "scores": 4,
                      "context": 4, "v_absorb": 4, "o_proj": 1,
                      "mlp_gate_up": 1, "mlp_down": 1, "router": 1,
                      "shared_gate_up": 1, "shared_down": 1,
                      "experts_gate_up": 4, "experts_down": 4,
                      "lm_head": 1}
    experts = [l for l in wl.layers if l.name.startswith("L1.experts")]
    assert [l.h for l in experts] == [2, 2]     # 4 tokens x 2 / 4 experts


def test_scalar_and_batched_numpy_are_bit_exact(tiny_batch):
    wl, configs, cfg, lay = tiny_batch
    out = _sweep_kernel(np, cfg, lay, outputs="full")
    for i, c in enumerate(configs):
        rep = synthesize(c)
        assert rep.clock_ghz == cfg["clock_ghz"][i, 0]
        res = run_workload(wl, c, report=rep)
        for j, lr in enumerate(res.layers):
            for k in ("compute_cycles", "mem_cycles", "total_cycles",
                      "utilization", "glb_bytes", "dram_bytes",
                      "energy_pj"):
                assert out[k][i, j] == getattr(lr, k), (k, i, lr.name)
            assert out["spad_accesses"][0, j] == lr.spad_accesses
        assert out["latency_s"][i] == res.latency_s
        assert out["energy_j"][i] == res.energy_j
        assert out["perf_per_area"][i] == res.perf_per_area


def _assert_close(got: dict, want: dict):
    for k in AGGREGATE_OUTPUTS:
        g = np.asarray(got[k], dtype=np.float64)
        w = np.asarray(want[k], dtype=np.float64)
        assert g.shape == w.shape, k
        rel = np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-30))
        assert rel <= RTOL, (k, rel)


def test_xla_and_pallas_agree_with_numpy(tiny_batch, jax_usable):
    if not jax_usable:
        pytest.skip("jax unusable on this host")
    from repro.kernels.sweep_kernel import sweep_aggregates_pallas
    _, _, cfg, lay = tiny_batch
    want = _sweep_kernel(np, cfg, lay, outputs="aggregates")
    _assert_close(_run_kernel(cfg, lay, "jax", outputs="aggregates"), want)
    for block_l in (None, 8):       # one layer tile, then three
        got = sweep_aggregates_pallas(cfg, lay, block_l=block_l,
                                      interpret=True)
        _assert_close({k: np.asarray(v) for k, v in got.items()}, want)


def test_float32_path_holds_the_published_widths(jax_usable):
    """At Moonlight's widths the grouped products pass 2**31 (expert
    weights 369 M elements, the LM head 21.5 G MACs): the float32 path
    still agrees with the exact one."""
    if not jax_usable:
        pytest.skip("jax unusable on this host")
    wl = mla_moe_decode_step(MOONLIGHT, batch=64, context=4096)
    soa = configs_to_soa(_random_configs(16, seed=5))
    cfg, lay = _make_cfg_lay(soa, synthesize_soa(soa), _workload_batch(wl))
    want = _sweep_kernel(np, cfg, lay, outputs="aggregates")
    _assert_close(_run_kernel(cfg, lay, "jax", outputs="aggregates"), want)


def test_the_decode_step_streams_through_the_pallas_path(jax_usable):
    """``run(ExploreSpec.single(...))`` streams the step through the
    Pallas kernel; its front is the numpy path's."""
    if not jax_usable:
        pytest.skip("jax unusable on this host")
    from repro.core.dse import ExploreSpec, run
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    wl = tiny_step()
    soa = configs_to_soa(_random_configs(48, seed=9))
    chunks = [{k: v[s:s + 16] for k, v in soa.items()}
              for s in range(0, 48, 16)]
    obs_metrics.reset_metrics()
    obs_trace.configure(enabled=True, reset=True)
    try:
        got = run(ExploreSpec.single(wl, iter(chunks), chunk_size=16,
                                     backend="jax", use_pallas=True))
        spans = [s.as_dict() for s in obs_trace.get_tracer().spans()]
        pairs = obs_metrics.snapshot()["sweep.layer_pairs"]
    finally:
        obs_trace.disable()
        obs_trace.configure(enabled=False, reset=True)
    want = run(ExploreSpec.single(wl, iter(chunks), chunk_size=16,
                                  backend="numpy"))
    assert got.timings["use_pallas"]
    assert got.n_configs == want.n_configs == 48
    order = np.argsort(want.front_metrics["energy_j"])
    got_order = np.argsort(got.front_metrics["energy_j"])
    for k in ("perf_per_area", "energy_j"):
        np.testing.assert_allclose(got.front_metrics[k][got_order],
                                   want.front_metrics[k][order], rtol=RTOL)
    launches = [s for s in spans if s["name"] == "kernel.launch"]
    assert len(launches) == 3
    assert all((s["attrs"]["l"], s["attrs"]["tiles"], s["attrs"]["buffers"],
                s["attrs"]["bytes"]) == (22, 1, 1, 4 * (15 * 16 + 11 * 22 + 1))
               for s in launches)
    assert pairs == 48 * 22


# ---------------------------------------------------------------------------
# The builder at Moonlight-16B-A3B's published widths
# ---------------------------------------------------------------------------

def test_moonlight_decode_step_rows_and_macs():
    b, c = 64, 4096
    wl = mla_moe_decode_step(MOONLIGHT, batch=b, context=c)
    assert len(wl.layers) == 9 + 26 * 12 + 1 == 322
    assert wl.layers[-1].name == "lm_head"
    # (m, k, n, groups) of each row, from the table of the builder's rows
    attention = [(b, 2048, 16 * (128 + 64), 1), (b, 2048, 512 + 64, 1),
                 (b, 128, 512, 16), (16, 576, c, b), (16, c, 512, b),
                 (b, 512, 128, 16), (b, 16 * 128, 2048, 1)]
    dense = [(b, 2048, 2 * 11264, 1), (b, 11264, 2048, 1)]
    moe = [(b, 2048, 64, 1), (b, 2048, 2 * 2 * 1408, 1),
           (b, 2 * 1408, 2048, 1), (b * 6 // 64, 2048, 2 * 1408, 64),
           (b * 6 // 64, 1408, 2048, 64)]
    head = [(b, 2048, 163840, 1)]
    want = attention + dense + 26 * (attention + moe) + head
    got = [(l.h, l.c, l.k, l.groups) for l in wl.layers]
    assert got == want
    assert all((l.w, l.r, l.s, l.stride, l.batch) == (1, 1, 1, 1, 1)
               for l in wl.layers)
    total = sum(m * k * n * g for m, k, n, g in want)
    assert wl.total_macs == total
    assert round(total / 1e9, 2) == 288.27
    attn = sum(m * k * n * g for m, k, n, g in attention[3:5])
    assert round(attn / 1e9, 2) == 4.56


def test_uneven_routing_and_low_rank_queries_are_refused():
    with pytest.raises(ValueError, match="evenly"):
        mla_moe_decode_step(MOONLIGHT, batch=48, context=4096)
    with pytest.raises(ValueError, match="q_lora_rank"):
        mla_moe_decode_step(dict(MOONLIGHT, q_lora_rank=1536), batch=64,
                            context=4096)


def test_the_build_is_a_span():
    from repro.obs import trace as obs_trace
    obs_trace.configure(enabled=True, reset=True)
    try:
        wl = tiny_step()
        (sp,) = [s.as_dict() for s in obs_trace.get_tracer().spans()
                 if s.name == "workload.build"]
    finally:
        obs_trace.disable()
        obs_trace.configure(enabled=False, reset=True)
    assert sp["attrs"]["rows"] == 22
    assert sp["attrs"]["grouped_rows"] == 4 + 4 + 2
    assert sp["attrs"]["macs"] == wl.total_macs
