"""Mosaic/XLA compiles for a described TPU v5e chip at granite widths.

Interpret mode cannot see what the TPU compiler refuses (block shapes
off the (8, 128) tiling, vector ops Mosaic cannot lower, programs that
do not fit HBM).  These tests compile each Pallas kernel of the chip
path, and the 4-layer granite-moe-1b-a400m BDWP train step, for a
``v5e:2x2`` topology that is described, not attached.  Nothing runs.

The topology is described inside a module fixture (never at import):
only the worker process that runs this file loads the TPU compiler.
Code that asks ``jax.default_backend()`` still sees the CPU here, so
the tests switch the kernels out of interpret mode themselves.
"""

import dataclasses
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_arch
from repro.core.sparsity import SparsityConfig
from repro.kernels import ops
from repro.optim import sgd
from repro.train import step as ST

GRANITE = get_arch("granite-moe-1b-a400m").full
D, F = GRANITE.d_model, GRANITE.moe.d_expert        # 1024, 512
N, M = 2, 8
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe a chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels compiled by Mosaic, not interpreted."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _kernels(text: str) -> set:
    """Names of the Mosaic kernels a compiled module calls."""
    calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    return {re.sub(r"\.\d+$", "", name) for name in calls}


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("idx_bits", [8, 4])
@pytest.mark.parametrize("rows", [4, 8192])
@pytest.mark.parametrize("k,f", [(D, F), (F, D)])
def test_nm_spmm(one_chip, mosaic, idx_bits, rows, k, f):
    """Decode batch (4 slots) and a train batch (8 x 1024 tokens), the
    expert up/gate (1024 -> 512) and down (512 -> 1024) projections."""
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    kc = k // M * N
    kci = kc // 2 if idx_bits == 4 else kc
    _compile(partial(ops.nm_spmm, n=N, m=M, idx_bits=idx_bits),
             s((rows, k), jnp.bfloat16), s((kc, f), jnp.bfloat16),
             s((kci, f), jnp.uint8))


def test_fused_update(one_chip, mosaic):
    """The stacked w_down expert leaf with its FF axis last."""
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    leaf = s((32 * 1024, F), jnp.float32)
    scal = s((), jnp.float32)
    _compile(partial(ops.fused_update, n=N, m=M), leaf, leaf, leaf,
             scal, scal, scal, scal)


@pytest.mark.parametrize("idx_bits", [8, 4])
def test_nm_compact(one_chip, mosaic, idx_bits):
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(partial(ops.nm_compact, n=N, m=M, idx_bits=idx_bits),
             s((4096, 12288), jnp.bfloat16))


def test_grad_compress_and_decompress(one_chip, mosaic):
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    slab = s((2, 1 << 20), jnp.float32)
    _compile(partial(ops.grad_compress, n=N, m=M), slab, slab)
    kc = (1 << 20) // M * N
    _compile(partial(ops.grad_decompress_mean, n=N, m=M),
             s((2, kc), jnp.bfloat16), s((2, kc), jnp.uint8))


def test_nm_spmm_shared(one_chip, mosaic):
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    nf, kc = F // 128, D // M * N
    _compile(ops.nm_spmm_shared, s((256, D), jnp.bfloat16),
             s((nf, kc, 128), jnp.bfloat16), s((nf, kc), jnp.int32))


@pytest.mark.parametrize("kernels", [False, True], ids=["default", "kernels"])
def test_granite_train_step_fits_one_chip(topo, mosaic, kernels):
    """The 4-layer BDWP 2:8 step at published widths, batch 8 x 1024:
    default path (pregen, unpacked operands) and kernel path (packed FF
    through nm_spmm, fused_update) — both fit one chip's HBM, and both
    run attention through the splash kernel."""
    cfg = dataclasses.replace(GRANITE, n_layers=4)
    sp = SparsityConfig(n=N, m=M, method="bdwp")
    opt = sgd.SGDConfig(total_steps=4)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    kw = (dict(pregen_pack=True, use_pallas=True, nm_backend="pallas")
          if kernels else {})
    bundle = ST.build_lm_train(cfg, mesh, sp, opt, **kw)
    state = jax.eval_shape(partial(ST.init_train_state, cfg=cfg, sp_cfg=sp,
                                   pregen_pack=kernels),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        state, bundle.state_shardings)
    tok = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32,
        sharding=NamedSharding(mesh, bundle.input_pspecs["tokens"]))
    compiled = bundle.step_fn.lower(
        state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2 ** 30:.2f} GiB does not fit"
    # global causal attention takes the splash kernels on either path;
    # the default path calls no other Mosaic kernel
    names = _kernels(compiled.as_text())
    splash = {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    if kernels:
        assert splash <= names, names
        assert any(n.startswith("nm_spmm_") for n in names), names
        assert any(n.startswith("fused_update_") for n in names), names
    else:
        assert names == splash, names
