"""Each cell's timed program compiled for a described TPU v5e chip at
the cell's own sizes, and held to one chip's HBM: the train steps of
both train cells, and the chat mix's decode and prefill programs with
the packed store's ``nm_spmm`` kernels.  Nothing runs; the topology is
described inside a fixture, never at import."""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding

from chipbench import spec

HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe a chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def used(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("cell", ["granite-train-bdwp28", "qwen3-8b-train-bdwp28"])
def test_train_step_fits(topo, cell):
    from repro.core.sparsity import SparsityConfig
    from repro.optim import sgd
    from repro.train import step as ST
    c = spec.find(spec.benchmark()["workloads"], cell, "workload")
    conf = spec.config(c["config"])
    cfg = spec.family(conf).program_config(conf)
    mix = spec.traffic(c["traffic"])
    sp = SparsityConfig(**mix["sparsity"])
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    bundle = ST.build_lm_train(cfg, mesh, sp, sgd.SGDConfig(), **mix["path"])
    state = jax.eval_shape(partial(ST.init_train_state, cfg=cfg, sp_cfg=sp),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                         state, bundle.state_shardings)
    tok = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32,
                               sharding=NamedSharding(mesh, bundle.input_pspecs["tokens"]))
    compiled = bundle.step_fn.lower(state, {"tokens": tok, "labels": tok}).compile()
    assert used(compiled) < HBM
