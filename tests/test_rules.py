"""Sharding-rule tests: dedupe, divisibility fallback, activation ctx."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.sharding import rules as R

jax.config.update("jax_platform_name", "cpu")


class TestSpecToPspec:
    def test_basic_mapping(self):
        ps = R.spec_to_pspec(("embed", "mlp"), R.TRAIN_RULES)
        assert ps == P("data", "model")

    def test_dedupe_moe_stacked(self):
        """(layer, expert, embed, mlp): expert and mlp both -> model;
        first occurrence wins, mlp falls back to replicated."""
        ps = R.spec_to_pspec(("layer", "expert", "embed", "mlp"),
                             R.TRAIN_RULES)
        assert ps == P(None, "model", "data", None)

    def test_divisibility_fallback(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        # fake a 16-way axis via rule check: use size-1 mesh -> divides
        ps = R.spec_to_pspec(("embed", "mlp"), R.TRAIN_RULES,
                             shape=(7, 13), mesh=mesh)
        assert ps == P("data", "model")  # size-1 axes always divide

    def test_divisibility_fallback_nondividing(self):
        class FakeMesh:
            shape = {"data": 4, "model": 4}
        ps = R.spec_to_pspec(("embed", "mlp"), R.TRAIN_RULES,
                             shape=(6, 16), mesh=FakeMesh())
        assert ps == P(None, "model")  # 6 % 4 != 0 -> replicated

    def test_params_pspecs_with_params_tree(self):
        class FakeMesh:
            shape = {"data": 4, "model": 4}
        specs = {"a": ("embed", "mlp"), "b": ("embed",)}
        params = {"a": jax.ShapeDtypeStruct((8, 6), jnp.float32),
                  "b": jax.ShapeDtypeStruct((5,), jnp.float32)}
        out = R.params_pspecs(specs, R.TRAIN_RULES, params, FakeMesh())
        assert out["a"] == P("data", None)   # 6 % 4 -> mlp dropped
        assert out["b"] == P(None)           # 5 % 4 -> embed dropped


class TestActivationContext:
    def test_noop_without_context(self):
        x = jnp.ones((4, 8))
        y = R.act(x, R.BATCH, None)
        assert y is x

    def test_constrains_under_context(self):
        mesh = make_host_mesh()
        with R.activation_sharding(mesh, ("data",)):
            @jax.jit
            def f(x):
                return R.act(x, R.BATCH, None) * 2
            y = f(jnp.ones((4, 8)))
        assert bool((y == 2).all())

    def test_nondividing_dim_replicates(self):
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 4}
        # shape 6 % 4 -> entry must become None: exercise the logic via
        # the internal path (no real device needed since constraint is
        # only applied inside jit; here just check no exception path)
        with R.activation_sharding(None, ("data",)):
            x = jnp.ones((6, 8))
            assert R.act(x, R.BATCH, None) is x

    def test_context_restores(self):
        mesh = make_host_mesh()
        with R.activation_sharding(mesh, ("data",)):
            pass
        x = jnp.ones((4,))
        assert R.act(x, R.BATCH) is x  # context cleared -> no-op


class TestCacheSpecs:
    def test_kv_heads_replicated_when_indivisible(self):
        mesh = make_host_mesh()  # 1 device: everything divides
        specs = {"cache": {"k": jax.ShapeDtypeStruct((4, 2, 64, 8, 16),
                                                     jnp.bfloat16),
                           "pos": jax.ShapeDtypeStruct((), jnp.int32)},
                 "token": jax.ShapeDtypeStruct((2, 1), jnp.int32),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        out = R.serve_input_pspecs(specs, mesh, long_context=False)
        assert out["cache"]["k"][3] in ("model", None)
        assert out["token"] == P(("data",), None)
