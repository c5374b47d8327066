"""Device scopes of the train step (core/scopes.py) and the benchmark's
table that reads them (chipbench/layer_time.py): the classifier on
hand-written ``op_name`` paths, and the instruction -> layer table of a
tiny MoE LM train step compiled through ``build_lm_train``."""

import re
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding

from repro.core import scopes as S
from repro.launch.hlo_cost import _FREE_OPS, parse_module

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import layer_time as LT  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


@pytest.mark.parametrize("op_name, layer", [
    ("jit(lm_train_step)/jvp(blocks)/while/body/closed_call/ff/dot_general",
     S.FF),
    ("jit(lm_train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "wu/dot_general", S.WU),
    ("jit(lm_train_step)/transpose(jvp(blocks))/while/body/checkpoint/"
     "attention/bp/dot_general", S.BP),
    ("jit(lm_train_step)/transpose(jvp(blocks))/while/body/checkpoint/"
     "attention/transpose(jvp(exp))", S.ATTENTION),
    ("jit(f)/vmap(jvp(moe_dispatch))/top_k", S.MOE_DISPATCH),
    ("jit(lm_train_step)/jvp(blocks)/while/body/dynamic_update_slice",
     S.BLOCKS),
    ("jit(lm_train_step)/update/sort", S.UPDATE),
    ("jit(lm_train_step)/update/while/body/mul", S.UPDATE),
    # a ';' list takes its first path
    ("jit(f)/jvp(blocks)/while/body/moe_dispatch/mul;while/body/ff/dot",
     S.MOE_DISPATCH),
    ("jit(f)/transpose(jvp(blocks))/attention/wu/reshape;attention/reshape",
     S.WU),
    ("jit(lm_train_step)/jvp()/broadcast_in_dim", LT.UNSCOPED),
    ("state['compute']['embed']['embed_table']", LT.UNSCOPED),
    ("jit(buffer)/bpx/ffn_out/wux", LT.UNSCOPED),   # whole words only
    ("", LT.UNSCOPED),
])
def test_layer_of(op_name, layer):
    assert LT.layer_of(op_name) == layer


def test_the_benchmark_reads_the_program_scope_names():
    assert LT.LAYERS == S.LAYERS


def test_layers_of_a_list():
    assert LT.layers_of("a/ff/dot;b/bp/dot;c") == {S.FF, S.BP, LT.UNSCOPED}


HLO = """HloModule jit_t

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(f32[4]{0} %p0, f32[4]{0} %p0), metadata={op_name="jit(t)/blocks/mul"}
}

%fused_b (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %c = f32[4]{0} convert(f32[4]{0} %p0), metadata={op_name="jit(t)/blocks/convert"}
  ROOT %d = f32[4]{0} add(f32[4]{0} %c, f32[4]{0} %c), metadata={op_name="jit(t)/ff/add"}
}

ENTRY %main (x: f32[4]) -> (f32[4], f32[4]) {
  %x = f32[4]{0:T(256)} parameter(0)
  %cp = f32[4]{0:T(256)} copy(f32[4]{0:T(256)} %x)
  %f1 = f32[4]{0:T(256)} fusion(f32[4]{0:T(256)} %cp), kind=kLoop, calls=%fused_a
  %f2 = f32[4]{0} fusion(f32[4]{0} %f1), kind=kLoop, calls=%fused_b, metadata={op_name="jit(t)/wu/add"}
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%f1, %f2)
}
"""


def test_layer_table_by_hand():
    tab = LT.layer_table(HLO)
    assert tab["f1"] == (S.BLOCKS, False)     # from its fused root
    assert tab["f2"] == (S.WU, True)          # own name; spans wu/ff/blocks
    assert tab["cp"] == (S.BLOCKS, False)     # no name: its reader's
    assert tab["t"] == (LT.UNSCOPED, False)    # tuples take no layer
    assert tab["x"] == (LT.UNSCOPED, False)


@pytest.fixture(scope="module")
def step_text():
    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.optim import sgd
    from repro.train import step as ST

    cfg = get_arch("granite-moe-1b-a400m").smoke
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    bundle = ST.build_lm_train(cfg, mesh, sp, sgd.SGDConfig(total_steps=4))
    state = jax.eval_shape(partial(ST.init_train_state, cfg=cfg, sp_cfg=sp),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, bundle.state_shardings)
    tok = jax.ShapeDtypeStruct(
        (2, 64), jnp.int32,
        sharding=NamedSharding(mesh, bundle.input_pspecs["tokens"]))
    return bundle.step_fn.lower(state, {"tokens": tok, "labels": tok}) \
        .compile().as_text()


def test_step_program_is_named(step_text):
    assert step_text.startswith("HloModule jit_lm_train_step,")


def _executed(text):
    """Instructions of the computations that run as such (not the bodies
    of fusions, reducers and comparators), with the kinds fused inside."""
    comps = parse_module(text)
    called = {m for c in comps.values() for o in c.ops
              for m in re.findall(r"(?:calls|to_apply|comparator)=%?([\w.\-]+)",
                                  o.line) if o.kind != "while"}
    for c in comps.values():
        if c.name in called:
            continue
        for o in c.ops:
            inner = comps.get(LT._fused(o))
            yield o, {x.kind for x in inner.ops} if inner else {o.kind}


def test_every_instruction_has_a_layer_and_no_product_is_unscoped(step_text):
    tab = LT.layer_table(step_text)
    products = {}
    for op, kinds in _executed(step_text):
        if op.kind in _FREE_OPS:
            continue
        assert op.name in tab, op.name
        if kinds & {"dot", "convolution"}:
            products[op.name] = tab[op.name][0]
    assert products and LT.UNSCOPED not in products.values()
    count = {k: list(products.values()).count(k) for k in set(products.values())}
    # seven N:M linears a layer (q, k, v, o, expert gate, up, down): each
    # forward product twice (forward and remat recompute), one dx, one dw
    assert count[S.FF] >= 14 and count[S.BP] >= 7 and count[S.WU] >= 7
    assert count[S.ATTENTION] >= 2           # scores and weighted sum
    assert count.get(S.MOE_DISPATCH, 0) >= 1  # the router


def test_backward_attention_and_update_are_scoped(step_text):
    tab = LT.layer_table(step_text)
    layers = {}
    for op, _ in _executed(step_text):
        if op.kind in _FREE_OPS:
            continue
        path = op.op_name.split(";")[0]
        layers.setdefault(tab[op.name][0], []).append(path)
    assert any("transpose(" in p for p in layers[S.ATTENTION])
    for layer in (S.BP, S.WU):
        assert all("transpose(" in p for p in layers[layer] if p)
    assert layers[S.UPDATE]
    assert all("transpose(" not in p for p in layers[S.UPDATE] if p)
