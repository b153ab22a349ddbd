"""Logical sharding rules: divisibility fallbacks + tree construction."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.sharding import (activation_sharding,
                                     default_activation_rules, param_pspec,
                                     shard, tree_pspecs)


class FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.empty(tuple(sizes.values()))


MESH = FakeMesh({"data": 16, "model": 16})


def test_tp_spec_for_attention_proj():
    spec = param_pspec("layers/wq", (48, 8192, 8192), True, MESH)
    assert spec == P(None, "data", "model")


def test_vocab_divisibility_fallback():
    # mamba2 vocab 50280 is not divisible by 16 -> fsdp-shard d instead
    spec = param_pspec("embed", (50280, 768), False, MESH)
    assert spec == P(None, "data")
    spec2 = param_pspec("embed", (163840, 2048), False, MESH)
    assert spec2 == P("model", "data")


def test_expert_parallel_spec():
    spec = param_pspec("layers/w_experts_in", (48, 64, 2048, 1408), True,
                       MESH)
    assert spec == P(None, "model", "data", None)


def test_small_params_replicated():
    assert param_pspec("layers/ln1", (48, 2048), True, MESH) == P(None, None)
    assert param_pspec("final_norm", (2048,), False, MESH) == P(None)


def test_nondivisible_inner_dim_dropped():
    # in_proj inner dim 3352 % 16 != 0 -> only fsdp axis survives
    spec = param_pspec("layers/in_proj", (24, 768, 3352), True, MESH)
    assert spec == P(None, "data", None)


def test_tree_pspecs_structure():
    params = {"embed": jnp.zeros((256, 64)),
              "layers": {"wq": jnp.zeros((2, 64, 64)),
                         "ln1": jnp.zeros((2, 64))}}
    specs = tree_pspecs(params, None)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(params)


def test_activation_sharding_context_noop_outside():
    x = jnp.ones((4, 4))
    # outside the context: identity
    np.testing.assert_array_equal(np.asarray(shard(x, "residual")),
                                  np.asarray(x))


def test_activation_sharding_applies_inside():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_activation_rules(mesh, seq_sharded=True)

    def f(x):
        with activation_sharding(mesh, rules):
            return shard(x, "residual") * 2
    with mesh:
        out = jax.jit(f)(jnp.ones((2, 4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((2, 4, 8)))


def test_default_rules_shapes():
    mesh = make_mesh((1,), ("data",))
    rules = default_activation_rules(mesh, seq_sharded=False)
    assert "residual" in rules and "moe_buffer" in rules
