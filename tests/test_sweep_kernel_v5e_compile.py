"""The Pallas sweep kernel compiles for a TPU v5e at the main path's
shapes.

Nothing runs: each test lowers the packed program
``_build_packed_call(..., interpret=False)`` (unpack, the Pallas call,
the output slices) from a ``ShapeDtypeStruct`` of its one packed buffer,
placed on one chip of a described (not attached) ``v5e:2x2`` topology,
and compiles it, which raises whatever the chip's compiler would refuse
(block shapes off the (8, 128) tiling, scoped-VMEM overruns).  The
shapes are those ``chip_smoke.py`` drives:

* vgg16 (16 layers), one workload, per-layer precision columns, one
  64-genome search batch (the serving search);
* resnet50 (54 layers), one workload, a 32,768-config stream chunk;
* the vgg16 + resnet34 + resnet50 suite (107 layers, three segments),
  per-layer precision columns, one 32-genome search batch.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dse_batch import AGGREGATE_OUTPUTS
from repro.core.workloads import get_workload
from repro.kernels.sweep_kernel import (MIXED_CFG_FIELDS, _build_packed_call,
                                        _ceil_to, _packed_layout,
                                        default_tiling)

# name -> (configs per dispatch, workloads, per-layer precision columns)
SHAPES = {
    "vgg16": (64, ("vgg16",), True),
    "resnet50": (32768, ("resnet50",), False),
    "suite": (32, ("vgg16", "resnet34", "resnet50"), True),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of the shared temp directory
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_packed(n, bounds, wide, sharding):
    """Compile the packed program for ``n`` configs over ``bounds``;
    check its kernel and its six output columns."""
    l, w = bounds[-1][1], len(bounds)
    block_n, block_l = default_tiling(n, l)
    n_pad, l_pad = _ceil_to(n, block_n), _ceil_to(l, block_l)
    mixed_wide = (wide,) * len(MIXED_CFG_FIELDS)
    _, size = _packed_layout(n_pad, l_pad, w, mixed_wide)
    fn = _build_packed_call(n, n_pad, l_pad, bounds, block_n, block_l,
                            mixed_wide, w == 1, False)
    buf = jax.ShapeDtypeStruct((size,), jnp.int32, sharding=sharding)
    compiled = fn.lower(buf).compile()
    # the kernel's device op keeps the name profiles know it by
    assert re.search(r"%tpu_custom_call(\.\d+)? = \S+ custom-call\(",
                     compiled.as_text())
    out = compiled.out_info
    assert sorted(out) == sorted(AGGREGATE_OUTPUTS)
    for col in out.values():
        assert col.shape == ((n,) if w == 1 else (w, n))
        assert np.dtype(col.dtype) == np.float32
    return l_pad // block_l


def _bounds(workloads):
    bounds, s = [], 0
    for wl in workloads:
        e = s + len(get_workload(wl).layers)
        bounds.append((s, e))
        s = e
    return tuple(bounds)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_sweep_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    n, workloads, wide = SHAPES[name]
    _compile_packed(n, _bounds(workloads), wide, one_chip)


def test_sweep_kernel_compiles_for_v5e_over_three_layer_tiles(
        one_chip, no_persistent_cache):
    """A Moonlight-16B-A3B decode step (322 grouped GEMM rows) in a
    32,768-config stream chunk: three 128-wide layer tiles."""
    assert _compile_packed(32768, ((0, 322),), False, one_chip) == 3
