"""The HLO-text cost model vs XLA's cost_analysis and hand counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo_analysis import analyze_compiled, analyze_hlo_text
from repro.core.tpu_roofline import (Roofline, dense_model_flops,
                                     roofline_from_stats)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_loop_free_matches_cost_analysis():
    def g(a, b):
        return (a @ b).sum()
    co = _compile(g, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((512, 128), jnp.float32))
    mc = analyze_hlo_text(co.as_text())
    xla = co.cost_analysis()["flops"]
    expect = 2 * 256 * 512 * 128
    assert abs(mc.flops - expect) / expect < 0.02
    assert abs(mc.flops - xla) / xla < 0.02


def test_scan_trip_count_correction():
    L = 7

    def f(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, params)
        return y.sum()

    co = _compile(jax.grad(f),
                  jax.ShapeDtypeStruct((L, 64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((8, 64), jnp.float32))
    mc = analyze_hlo_text(co.as_text())
    # fwd dot + 2 bwd dots per layer
    expect = 2 * 8 * 64 * 64 * L * 3
    assert abs(mc.flops - expect) / expect < 0.10, mc.flops
    # XLA counts the body once -> must be way below our corrected count
    assert co.cost_analysis()["flops"] < mc.flops / 2


def test_analyze_compiled_fields():
    def g(a):
        return jnp.tanh(a).sum()
    co = _compile(g, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    st = analyze_compiled(co)
    assert st.flops > 0 and st.bytes_accessed > 0
    assert st.transcendentals >= 128 * 128
    assert st.collectives.total_bytes == 0
    d = st.as_dict()
    assert "collective_bytes_by_kind" in d and "flops" in d


def test_collectives_parsed_under_sharding():
    if jax.device_count() < 2:
        pytest.skip("needs >1 device")


def test_roofline_terms():
    from repro.core.hlo_analysis import CollectiveStats, CompiledStats
    st = CompiledStats(
        flops=197e12, bytes_accessed=819e9, transcendentals=0,
        collectives=CollectiveStats({"all-reduce": 200e9}, {"all-reduce": 4}),
        xla_flops=0, xla_bytes=0, argument_bytes=0, output_bytes=0,
        temp_bytes=0, generated_code_bytes=0)
    r = roofline_from_stats(st, arch="a", shape="s", mesh="m", chips=256,
                            model_flops=197e12 * 256)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 1.0) < 1e-9
    assert abs(r.collective_s - 1.0) < 1e-9
    assert r.bottleneck in ("compute", "memory", "collective")
    assert abs(r.useful_flops_ratio - 1.0) < 1e-9
    assert abs(r.roofline_fraction - 1.0) < 1e-9


def test_model_flops_helpers():
    assert dense_model_flops(1e9, 1e6) == 6e15
