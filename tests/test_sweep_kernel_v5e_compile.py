"""The Pallas sweep kernel compiles for a TPU v5e at the main path's
shapes.

Nothing runs: each test lowers ``_build_sweep_call(..., interpret=False)``
from ``ShapeDtypeStruct``s placed on one chip of a described (not
attached) ``v5e:2x2`` topology and compiles it, which raises whatever
the chip's compiler would refuse (block shapes off the (8, 128) tiling,
scoped-VMEM overruns).  The shapes are those ``chip_smoke.py`` drives:

* vgg16 (16 layers), one workload, per-layer precision columns, one
  64-genome search batch (the serving search);
* resnet50 (54 layers), one workload, a 32,768-config stream chunk;
* the vgg16 + resnet34 + resnet50 suite (107 layers, three segments),
  per-layer precision columns, one 32-genome search batch.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dse_batch import _CFG_INT32, _LAY_INT32
from repro.core.workloads import get_workload
from repro.kernels.sweep_kernel import (CFG_FIELDS, LAY_FIELDS,
                                        MIXED_CFG_FIELDS, _build_sweep_call,
                                        _ceil_to, default_tiling)

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


def _operands(n_pad, l_pad, w, wide, sharding):
    def spec(shape, int_fields, name):
        dtype = jnp.int32 if name in int_fields else jnp.float32
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ops = [spec((n_pad, l_pad if wide and name in MIXED_CFG_FIELDS else 1),
                _CFG_INT32, name) for name in CFG_FIELDS]
    ops += [spec((1, l_pad), _LAY_INT32, name) for name in LAY_FIELDS]
    ops.append(jax.ShapeDtypeStruct((l_pad, w), jnp.float32,
                                    sharding=sharding))    # segment mask
    ops.append(jax.ShapeDtypeStruct((1, w), jnp.float32,
                                    sharding=sharding))    # segment MACs
    return ops


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_sweep_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    n, workloads, wide = SHAPES[name]
    l = sum(len(get_workload(wl).layers) for wl in workloads)
    w = len(workloads)
    block_n, block_l = default_tiling(n, l)
    n_pad, l_pad = _ceil_to(n, block_n), _ceil_to(l, block_l)
    fn = _build_sweep_call(n_pad, l_pad, w, block_n, block_l,
                           (wide,) * len(MIXED_CFG_FIELDS), False)
    compiled = fn.lower(*_operands(n_pad, l_pad, w, wide,
                                   one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out, = jax.tree.leaves(compiled.out_info)
    assert out.shape == (n_pad, 6 * w)
    assert np.dtype(out.dtype) == np.float32


def test_sweep_kernel_compiles_for_v5e_over_three_layer_tiles(
        one_chip, no_persistent_cache):
    """A Moonlight-16B-A3B decode step (322 grouped GEMM rows) in a
    32,768-config stream chunk: three 128-wide layer tiles."""
    n, l, w = 32768, 322, 1
    block_n, block_l = default_tiling(n, l)
    n_pad, l_pad = _ceil_to(n, block_n), _ceil_to(l, block_l)
    assert l_pad // block_l == 3
    fn = _build_sweep_call(n_pad, l_pad, w, block_n, block_l,
                           (False,) * len(MIXED_CFG_FIELDS), False)
    compiled = fn.lower(*_operands(n_pad, l_pad, w, False,
                                   one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
