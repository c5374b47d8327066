"""Unified SparseOperand API tests (core/operand.nm_apply).

What must hold:
  * every operand variant consumed through ``nm_apply`` is BITWISE equal
    (forward AND gradients) to the pre-refactor consumption path it
    replaced — in-op masking (nm_linear/nm_conv), pre-generated FF/BP
    operands (nm_linear_pregen/nm_conv_pregen, incl. stacked MoE expert
    leaves), packed serving (nm_linear_packed), shared-mode serving
    (packed_shared_apply);
  * the packed pre-generated train FORWARD consumes ``(vals, idx)``
    directly through kernels/nm_spmm on the pallas backend — no
    scatter-unpack anywhere in the traced forward (either backend), and
    the lowered forward really invokes the kernel;
  * ``pregen_pack=True`` training is bitwise-identical across
    nm_backend="jnp" / "pallas" and the unpacked state (solo device);
  * the operand pytrees flatten in the dict-era leaf order, so PR-3/4
    checkpoints whose compute trees stored operand *dicts* restore
    leaf-for-leaf (bitwise) into PregenOp-typed state — solo and across
    mesh shapes;
  * the old bdwp entry points still work as thin deprecation shims.
"""

import sys

if "jax" not in sys.modules:  # standalone: force before backend init
    from repro.launch.spmd import force_host_devices
    force_host_devices(8)

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import get_arch
from repro.core import bdwp
from repro.core import operand as O
from repro.core.sparsity import (DENSE, SparsityConfig, nm_mask, nm_pack,
                                 nm_unpack_n, pack_idx_u4, sparsify)
from repro.data import synthetic as D
from repro.kernels import ops
from repro.launch.hlo_cost import count_jaxpr_prims, count_mask_ops
from repro.launch.mesh import make_host_mesh  # noqa: F401


def _solo_mesh():
    """A literal 1-device mesh so the solo parity tests stay solo even
    under a forced multi-device backend (the spmd CI job)."""
    from repro.launch import spmd
    return spmd.single_device_mesh()
from repro.models import layers as L
from repro.models import transformer_lm as T
from repro.optim import sgd
from repro.train import step as ST
from repro.train.checkpoint import CheckpointManager

ARCH = get_arch("qwen3-8b")
CFG = ARCH.smoke
OPT = sgd.SGDConfig(lr=0.05, total_steps=16)
BDWP = SparsityConfig(n=2, m=8, method="bdwp")

mesh8_only = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _eq(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


def _tree_eq(ta, tb):
    fa = jax.tree_util.tree_flatten_with_path(ta)[0]
    fb = jax.tree.leaves(tb)
    assert len(fa) == len(fb)
    for (path, a), b in zip(fa, fb):
        _eq(a, b, "/".join(str(getattr(k, "key", k)) for k in path))


def _legacy(fn, *args, **kw):
    """Call a deprecated bdwp entry point without warning noise."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*args, **kw)


def _pregen_arrays(key, k=16, f=16, sp=BDWP, stack=()):
    """(x, w, vals, idx, ff_dense, bp) fixture for pregen parity tests."""
    kw, kx = jax.random.split(jax.random.PRNGKey(key))
    w = jax.random.normal(kw, (*stack, k, f), jnp.float32)
    ff_mask = nm_mask(w, sp.n, sp.m, axis=w.ndim - 2)
    bp_mask = nm_mask(w, sp.n, sp.m, axis=w.ndim - 1)
    ff = jnp.where(ff_mask, w, 0.0).astype(jnp.bfloat16)
    bp = jnp.where(bp_mask, w, 0.0).astype(jnp.bfloat16)
    vals, idx = nm_pack(ff, sp.n, sp.m, axis=w.ndim - 2)
    x = jax.random.normal(kx, (*stack, 4, k), jnp.bfloat16)
    return x, w, vals, idx, ff, bp


class TestOperandPytree:
    def test_flatten_roundtrip_preserves_type_and_cfg(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(0)
        for op in (O.DenseOp(w), O.MaskedOp(w, BDWP),
                   O.PregenOp(bp=bp, ff=ff, mask=None, cfg=BDWP),
                   O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP),
                   O.PackedOp(vals, idx, BDWP), O.SharedOp(vals, idx[:, 0])):
            leaves, tdef = jax.tree_util.tree_flatten(op)
            back = jax.tree_util.tree_unflatten(tdef, leaves)
            assert type(back) is type(op)
            assert back.fields == op.fields
            assert back.cfg == op.cfg
            for fld in op.fields:
                _eq(back[fld], op[fld])

    def test_flatten_order_matches_dict_era(self):
        """PregenOp leaves flatten in the sorted-key order the operand
        DICTS had — the invariant that makes old checkpoints restore
        leaf-for-leaf (dicts flatten in sorted key order)."""
        x, w, vals, idx, ff, bp = _pregen_arrays(1)
        mask = nm_mask(w, 2, 8, axis=0)
        op = O.PregenOp(bp=bp, ff=ff, mask=mask, cfg=BDWP)
        as_dict = {"bp": bp, "ff": ff, "mask": mask}
        for a, b in zip(jax.tree.leaves(op), jax.tree.leaves(as_dict)):
            _eq(a, b)
        op_p = O.PregenOp(bp=bp, vals=vals, idx=idx, mask=mask, cfg=BDWP)
        dict_p = {"bp": bp, "vals": vals, "idx": idx, "mask": mask}
        for a, b in zip(jax.tree.leaves(op_p), jax.tree.leaves(dict_p)):
            _eq(a, b)

    def test_dict_like_accessors(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(2)
        op = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
        assert "vals" in op and "ff" not in op
        assert set(op) == {"bp", "idx", "vals"}
        _eq(op["bp"], bp)
        assert op.get("mask") is None
        assert op.is_packed
        with pytest.raises(KeyError):
            op["ff"]

    def test_tree_map_and_eval_shape(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(3)
        op = O.PregenOp(bp=bp, ff=ff, cfg=BDWP)
        z = jax.tree.map(jnp.zeros_like, op)
        assert isinstance(z, O.PregenOp) and float(z.bp.sum()) == 0.0
        ab = jax.eval_shape(lambda o: o, op)
        assert isinstance(ab, O.PregenOp)
        assert ab.bp.shape == bp.shape

    def test_packed_op_dense_shape(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(4)
        assert O.PackedOp(vals, idx, BDWP).shape == w.shape

    def test_as_operand_dispatch(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(5)
        op = O.as_operand(w, "blocks/ffn/w_gate/w", BDWP)
        assert isinstance(op, O.MaskedOp) and op.cfg == BDWP
        op = O.as_operand(w, "router/w", BDWP)  # excluded -> dense cfg
        assert isinstance(op, O.MaskedOp) and op.cfg.is_dense
        op = O.as_operand({"bp": bp, "ff": ff}, "p/w", BDWP)
        assert isinstance(op, O.PregenOp) and not op.is_packed
        op = O.as_operand({"vals": vals, "idx": idx}, "p/w", BDWP)
        assert isinstance(op, O.PackedOp)
        op = O.as_operand({"vals": vals, "idx": idx[:, 0]}, "p/w", BDWP)
        assert isinstance(op, O.SharedOp)
        assert O.as_operand(op, "p/w", BDWP) is op


class TestNmApplyParity:
    """nm_apply vs each pre-refactor consumption path — bitwise."""

    @pytest.mark.parametrize("method",
                             ["dense", "srste", "sdgp", "sdwp", "bdwp"])
    def test_masked_linear_all_methods(self, method):
        sp = SparsityConfig(n=2, m=8, method=method)
        x, w, *_ = _pregen_arrays(10, sp=sp)

        def new(x, w):
            return O.nm_apply(O.MaskedOp(w, sp), x).astype(jnp.float32).sum()

        def old(x, w):
            return _legacy(bdwp.nm_linear, x, w, sp).astype(
                jnp.float32).sum()

        _eq(O.nm_apply(O.MaskedOp(w, sp), x), _legacy(bdwp.nm_linear, x, w, sp))
        ga = jax.grad(new, argnums=(0, 1))(x, w)
        gb = jax.grad(old, argnums=(0, 1))(x, w)
        for a, b in zip(ga, gb):
            _eq(a, b)

    def test_pregen_linear(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(11)
        op = O.PregenOp(bp=bp, ff=ff, cfg=BDWP)
        _eq(O.nm_apply(op, x), _legacy(bdwp.nm_linear_pregen, x, ff, bp))

        def new(x, ff, bp):
            return O.nm_apply(O.PregenOp(bp=bp, ff=ff, cfg=BDWP),
                              x).astype(jnp.float32).sum()

        def old(x, ff, bp):
            return _legacy(bdwp.nm_linear_pregen, x, ff, bp).astype(
                jnp.float32).sum()

        ga = jax.grad(new, argnums=(0, 1, 2))(x, ff, bp)
        gb = jax.grad(old, argnums=(0, 1, 2))(x, ff, bp)
        for a, b in zip(ga, gb):
            _eq(a, b)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_pregen_packed_matches_unpacked(self, backend):
        """Packed (vals, idx) consumption — through the kernel on the
        pallas backend, select-decompressed on jnp — is bitwise the
        unpacked pregen path: same forward, same dx, same dense WU
        gradient on the bp cotangent, zero cotangent on vals."""
        x, w, vals, idx, ff, bp = _pregen_arrays(12)
        op = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
        y = O.nm_apply(op, x, backend=backend)
        _eq(y, _legacy(bdwp.nm_linear_pregen, x, ff, bp), backend)

        def new(x, vals, bp):
            o = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
            return O.nm_apply(o, x, backend=backend).astype(
                jnp.float32).sum()

        def old(x, ff, bp):
            return _legacy(bdwp.nm_linear_pregen, x, ff, bp).astype(
                jnp.float32).sum()

        dx_n, dv_n, dbp_n = jax.grad(new, argnums=(0, 1, 2))(x, vals, bp)
        dx_o, dff_o, dbp_o = jax.grad(old, argnums=(0, 1, 2))(x, ff, bp)
        _eq(dx_n, dx_o)
        _eq(dbp_n, dbp_o)  # the dense straight-through WU gradient
        assert float(jnp.abs(dv_n).sum()) == 0.0
        assert float(jnp.abs(dff_o).sum()) == 0.0

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_pregen_packed_stacked_expert_leaf(self, backend):
        """Stacked (E, K, F) MoE leaves ride the same packed consumption
        (the kernel vmaps over the expert axis) — bitwise vs the vmapped
        unpacked path, gradients included."""
        x, w, vals, idx, ff, bp = _pregen_arrays(13, stack=(3,))
        op = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
        y = O.nm_apply(op, x, backend=backend, stacked=True)
        ref = jax.vmap(O.pregen_linear)(x, ff, bp)
        _eq(y, ref, backend)

        def new(x, vals, bp):
            o = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
            return O.nm_apply(o, x, backend=backend,
                              stacked=True).astype(jnp.float32).sum()

        def old(x, ff, bp):
            return jax.vmap(O.pregen_linear)(x, ff, bp).astype(
                jnp.float32).sum()

        dx_n, dv_n, dbp_n = jax.grad(new, argnums=(0, 1, 2))(x, vals, bp)
        dx_o, _, dbp_o = jax.grad(old, argnums=(0, 1, 2))(x, ff, bp)
        _eq(dx_n, dx_o)
        _eq(dbp_n, dbp_o)
        assert float(jnp.abs(dv_n).sum()) == 0.0

    def test_masked_stacked_expert_leaf(self):
        sp = SparsityConfig(n=2, m=4, method="bdwp")
        x, w, *_ = _pregen_arrays(14, sp=sp, stack=(3,))
        y = O.nm_apply(O.MaskedOp(w, sp), x, stacked=True)
        ref = jax.vmap(lambda xe, we: _legacy(bdwp.nm_linear, xe, we, sp))(
            x, w)
        _eq(y, ref)

    def test_masked_and_pregen_conv(self):
        sp = SparsityConfig(n=2, m=8, method="bdwp")
        kw, kx = jax.random.split(jax.random.PRNGKey(15))
        w = jax.random.normal(kw, (3, 3, 16, 16), jnp.float32)
        x = jax.random.normal(kx, (2, 8, 8, 16), jnp.bfloat16)
        _eq(O.nm_apply(O.MaskedOp(w, sp), x, stride=2),
            _legacy(bdwp.nm_conv, x, w, sp, 2))
        ff = jnp.where(nm_mask(w, 2, 8, axis=2), w, 0.0).astype(jnp.bfloat16)
        bp = jnp.where(nm_mask(w, 2, 8, axis=3), w, 0.0).astype(jnp.bfloat16)
        op = O.PregenOp(bp=bp, ff=ff, cfg=sp)
        _eq(O.nm_apply(op, x), _legacy(bdwp.nm_conv_pregen, x, ff, bp))
        # packed conv leaves decompress (scatter-free) then convolve
        vals, idx = nm_pack(ff, 2, 8, axis=2)
        op_p = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=sp)
        for backend in ("jnp", "pallas"):
            _eq(O.nm_apply(op_p, x, backend=backend),
                _legacy(bdwp.nm_conv_pregen, x, ff, bp), backend)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_packed_serve_operand(self, use_pallas):
        x, w, vals, idx, ff, bp = _pregen_arrays(16)
        op = O.PackedOp(vals, idx, BDWP)
        backend = "pallas" if use_pallas else "jnp"
        _eq(O.nm_apply(op, x, backend=backend),
            _legacy(bdwp.nm_linear_packed, x, vals, idx, BDWP,
                    use_pallas=use_pallas))

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_packed_serve_stacked_leaf(self, backend):
        """Layer-stacked (L, Kc, F) PackedOp leaves (pack_tree_element
        packs stacked dict sites per layer) consume outside the scan
        too: the kernel vmaps over the stack axis, bitwise the per-layer
        2-D consumption."""
        x, w, vals, idx, ff, bp = _pregen_arrays(25, stack=(3,))
        op = O.PackedOp(vals, idx, BDWP)
        y = O.nm_apply(op, x, backend=backend)
        ref = jnp.stack([
            O.nm_apply(O.PackedOp(vals[i], idx[i], BDWP), x[i],
                       backend=backend)
            for i in range(vals.shape[0])])
        _eq(y, ref, backend)

    def test_shared_serve_operand(self):
        x = jax.random.normal(jax.random.PRNGKey(17), (4, 32), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(18), (32, 64))
        vals, rows = bdwp.shared_ff_pack(w, BDWP)
        op = O.SharedOp(vals, rows)
        _eq(O.nm_apply(op, x),
            _legacy(bdwp.packed_shared_apply, {"vals": vals, "idx": rows}, x))

    def test_dense_apply_routes_every_leaf_format(self):
        """layers.dense_apply accepts arrays, PregenOp leaves, PackedOp
        leaves and the legacy dict formats — one nm_apply seam."""
        x, w, vals, idx, ff, bp = _pregen_arrays(19)
        b = jnp.ones((w.shape[-1],), jnp.float32)
        name = "blocks/ffn/w_gate/w"
        y_arr = L.dense_apply({"w": w, "b": b}, x, name, BDWP)
        _eq(y_arr, _legacy(bdwp.nm_linear, x, w, BDWP)
            + b.astype(jnp.bfloat16))
        op = O.PregenOp(bp=bp, ff=ff, cfg=BDWP)
        y_op = L.dense_apply({"w": op}, x, name, BDWP)
        y_dict = L.dense_apply({"w": {"bp": bp, "ff": ff}}, x, name, BDWP)
        _eq(y_op, y_dict)
        y_pk = L.dense_apply({"w": O.PackedOp(vals, idx, BDWP)}, x, name,
                             BDWP)
        y_pk_dict = L.dense_apply({"vals": vals, "idx": idx}, x, name, BDWP)
        _eq(y_pk, y_pk_dict)


class TestU4Operand:
    """u4-packed index planes through the one nm_apply seam: the fused
    decode kernel (and its jnp fallback) consuming two offsets per byte
    must be BITWISE the byte-wide path it halves the index traffic of."""

    def _u4(self, key, stack=()):
        x, w, vals, idx, ff, bp = _pregen_arrays(key, stack=stack)
        idx4 = pack_idx_u4(idx, axis=w.ndim - 2)
        return x, w, vals, idx, idx4, ff, bp

    def test_pytree_aux_roundtrip_preserves_idx_bits(self):
        x, w, vals, idx, idx4, ff, bp = self._u4(30)
        for op in (O.PackedOp(vals, idx4, BDWP, idx_bits=4),
                   O.PregenOp(bp=bp, vals=vals, idx=idx4, cfg=BDWP,
                              idx_bits=4)):
            leaves, tdef = jax.tree_util.tree_flatten(op)
            back = jax.tree_util.tree_unflatten(tdef, leaves)
            assert type(back) is type(op) and back.idx_bits == 4
            for fld in op.fields:
                _eq(back[fld], op[fld])
        # distinct aux: a u4 and a u8 operand must never share a jit
        # cache entry (the kernel decodes them differently)
        t4 = jax.tree_util.tree_structure(O.PackedOp(vals, idx4, BDWP, 4))
        t8 = jax.tree_util.tree_structure(O.PackedOp(vals, idx, BDWP, 8))
        assert t4 != t8

    def test_idx_bits_validated(self):
        x, w, vals, idx, idx4, ff, bp = self._u4(31)
        with pytest.raises(ValueError):
            O.PackedOp(vals, idx4, BDWP, idx_bits=6)
        with pytest.raises(ValueError):
            O.PregenOp(bp=bp, vals=vals, idx=idx4, cfg=BDWP, idx_bits=2)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_packed_serve_u4_bitwise_vs_u8(self, backend):
        """The fused u4 decode — in-kernel nibble expansion on pallas,
        select-decompress on jnp — is bitwise the byte-wide kernel AND
        the unpacked masked matmul oracle."""
        x, w, vals, idx, idx4, ff, bp = self._u4(32)
        y4 = O.nm_apply(O.PackedOp(vals, idx4, BDWP, idx_bits=4), x,
                        backend=backend)
        y8 = O.nm_apply(O.PackedOp(vals, idx, BDWP), x, backend=backend)
        _eq(y4, y8, backend)
        _eq(y4, jnp.matmul(x, ff), backend)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_packed_serve_u4_stacked_leaf(self, backend):
        """Layer-stacked (L, Kc/2, F) u4 planes vmapping over the stack
        axis — bitwise the per-layer 2-D consumption."""
        x, w, vals, idx, idx4, ff, bp = self._u4(33, stack=(3,))
        op = O.PackedOp(vals, idx4, BDWP, idx_bits=4)
        y = O.nm_apply(op, x, backend=backend)
        ref = jnp.stack([
            O.nm_apply(O.PackedOp(vals[i], idx4[i], BDWP, idx_bits=4),
                       x[i], backend=backend)
            for i in range(vals.shape[0])])
        _eq(y, ref, backend)
        _eq(y, O.nm_apply(O.PackedOp(vals, idx, BDWP), x, backend=backend),
            backend)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_pregen_train_forward_u4_bitwise(self, backend):
        """The packed pregen TRAIN forward with a u4 plane: forward, dx
        and the dense bp cotangent all bitwise the u8 path; vals and the
        index plane stay gradient-free."""
        x, w, vals, idx, idx4, ff, bp = self._u4(34)

        def loss(x, vals, bp, idx_p, bits):
            o = O.PregenOp(bp=bp, vals=vals, idx=idx_p, cfg=BDWP,
                           idx_bits=bits)
            return O.nm_apply(o, x, backend=backend).astype(
                jnp.float32).sum()

        y4 = O.nm_apply(O.PregenOp(bp=bp, vals=vals, idx=idx4, cfg=BDWP,
                                   idx_bits=4), x, backend=backend)
        y8 = O.nm_apply(O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP),
                        x, backend=backend)
        _eq(y4, y8, backend)
        g4 = jax.grad(loss, argnums=(0, 1, 2))(x, vals, bp, idx4, 4)
        g8 = jax.grad(loss, argnums=(0, 1, 2))(x, vals, bp, idx, 8)
        for a, b in zip(g4, g8):
            _eq(a, b, backend)
        assert float(jnp.abs(g4[1]).sum()) == 0.0

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_pregen_u4_stacked_moe_leaf(self, backend):
        x, w, vals, idx, idx4, ff, bp = self._u4(35, stack=(3,))
        op4 = O.PregenOp(bp=bp, vals=vals, idx=idx4, cfg=BDWP, idx_bits=4)
        op8 = O.PregenOp(bp=bp, vals=vals, idx=idx, cfg=BDWP)
        _eq(O.nm_apply(op4, x, backend=backend, stacked=True),
            O.nm_apply(op8, x, backend=backend, stacked=True), backend)

    def test_odd_compact_tile_falls_back_bitwise(self):
        """A (K·N/M) compact axis the kernel tiling can't halve (odd
        per-block count) routes to the jnp oracle inside ops.nm_spmm —
        still bitwise the u8 consumption.  Impossible for even n (2:8
        tiles always halve), so force it with 3:6 at K=6 -> Kc=3 and a
        padded final nibble in the u4 plane."""
        sp = SparsityConfig(n=3, m=6, method="bdwp")
        kw, kx = jax.random.split(jax.random.PRNGKey(36))
        w = jax.random.normal(kw, (6, 16), jnp.float32)
        ff = jnp.where(nm_mask(w, sp.n, sp.m, axis=0), w, 0.0).astype(
            jnp.bfloat16)
        vals, idx = nm_pack(ff, sp.n, sp.m, axis=0)
        idx4 = pack_idx_u4(idx, axis=0)
        assert idx4.shape[0] == 2  # ceil(3/2): the plane really padded
        x = jax.random.normal(kx, (4, 6), jnp.bfloat16)
        for backend in ("jnp", "pallas"):
            y4 = O.nm_apply(O.PackedOp(vals, idx4, sp, idx_bits=4), x,
                            backend=backend)
            _eq(y4, O.nm_apply(O.PackedOp(vals, idx, sp), x,
                               backend=backend), backend)


class TestPackedTrainForward:
    """The ROADMAP item: pregen_pack=True training consumes (vals, idx)
    directly through kernels/nm_spmm inside the train-step forward."""

    def _fwd(self, backend, pack=True):
        state = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                    pregen_pack=pack)
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}

        def forward_loss(compute, batch):
            with O.backend_scope(backend):
                hidden, _, aux = T.forward(compute, batch["tokens"], CFG,
                                           BDWP)
                return T.lm_loss(compute, hidden, batch["labels"], CFG) \
                    + 0.01 * aux

        structs = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
            (state["compute"], batch))
        return forward_loss, structs, state

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_no_scatter_unpack_in_forward(self, backend):
        """Neither backend scatters packed operands back to dense in the
        traced forward (the jnp fallback decompresses with selects; the
        pallas backend never leaves the kernel) — backward included."""
        forward_loss, (cstructs, bstructs), state = self._fwd(backend)
        jaxpr = jax.make_jaxpr(forward_loss)(cstructs, bstructs)
        assert count_jaxpr_prims(jaxpr.jaxpr,
                                 names=("scatter", "scatter-add")) == 0
        # the mask-once selection lives in the OPTIMIZER, not here
        assert count_jaxpr_prims(jaxpr.jaxpr, names=("top_k", "sort")) == 0

        # backward included: packing must add ZERO scatters over the
        # unpacked pregen baseline (the embed-table / loss-gather
        # cotangents legitimately scatter in both)
        def grad_scatters(pack):
            fwd, (cs, bs), st = self._fwd(backend, pack=pack)
            diff, meta = ST.split_compute(st["compute"])
            dstructs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in diff]
            gaxpr = jax.make_jaxpr(jax.grad(
                lambda d, b: fwd(ST.merge_compute(d, meta), b)
            ))(dstructs, bs)
            return count_jaxpr_prims(gaxpr.jaxpr,
                                     names=("scatter", "scatter-add"))

        assert grad_scatters(pack=True) == grad_scatters(pack=False)

    def test_pallas_forward_invokes_nm_spmm(self):
        """Every packed FF consumption in the pallas-backend forward is
        a pallas_call (the nm_spmm kernel); the jnp backend has none."""
        fwd_p, (cs, bs), state = self._fwd("pallas")
        n_sites = sum(isinstance(leaf, O.PregenOp) and leaf.is_packed
                      for leaf in jax.tree.leaves(
                          state["compute"],
                          is_leaf=lambda x: isinstance(x, O.PregenOp)))
        assert n_sites > 0
        jp = jax.make_jaxpr(fwd_p)(cs, bs)
        assert count_jaxpr_prims(jp.jaxpr, names=("pallas_call",)) >= n_sites
        fwd_j, (cs, bs), _ = self._fwd("jnp")
        jj = jax.make_jaxpr(fwd_j)(cs, bs)
        assert count_jaxpr_prims(jj.jaxpr, names=("pallas_call",)) == 0

    def _run(self, backend, pack=True, steps=3):
        mesh = _solo_mesh()
        bundle = ST.build_lm_train(CFG, mesh, BDWP, OPT, donate=False,
                                   pregen_pack=pack, nm_backend=backend)
        state = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                    pregen_pack=pack)
        state = jax.device_put(state, bundle.state_shardings)
        stream = D.lm_stream(CFG.vocab, 2, 32, seed=0)
        losses = []
        for i, (_, batch) in enumerate(stream):
            if i >= steps:
                break
            state, metrics = bundle.step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        return state, losses

    def test_packed_train_bitwise_across_backends_and_vs_unpacked(self):
        """Solo device: pregen_pack training is bitwise identical on the
        jnp and pallas backends, and to the unpacked pregen state — the
        kernel consumption changed WHERE the FF operand decompresses
        (VMEM), not WHAT is computed."""
        s_j, l_j = self._run("jnp")
        s_p, l_p = self._run("pallas")
        s_u, l_u = self._run("jnp", pack=False)
        assert l_j == l_p == l_u
        for a, b in zip(jax.tree.leaves(s_j["master"]),
                        jax.tree.leaves(s_p["master"])):
            _eq(a, b)
        for a, b in zip(jax.tree.leaves(s_j["master"]),
                        jax.tree.leaves(s_u["master"])):
            _eq(a, b)

    def test_mask_once_invariant_survives_pallas_backend(self):
        mesh = _solo_mesh()
        bundle = ST.build_lm_train(CFG, mesh, BDWP, OPT, donate=False,
                                   pregen_pack=True, nm_backend="pallas")
        state = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                    pregen_pack=True)
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}
        structs = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), (state, batch))
        n_sites = sum(
            bdwp.pregen_site(n, sgd._logical_shape(n, w.shape)[0], BDWP)
            for n, w in zip(sgd._names_of(state["master"]),
                            jax.tree.leaves(state["master"])))
        assert count_mask_ops(bundle.step_fn, structs[0],
                              structs[1]) == n_sites


class TestDeprecationShims:
    def test_shims_warn_and_compute(self):
        bdwp.reset_deprecation_warnings()  # shims warn only once/process
        x, w, vals, idx, ff, bp = _pregen_arrays(20)
        calls = [
            (lambda: bdwp.nm_linear(x, w, BDWP),
             lambda: O.nm_apply(O.MaskedOp(w, BDWP), x)),
            (lambda: bdwp.nm_linear_pregen(x, ff, bp),
             lambda: O.nm_apply(O.PregenOp(bp=bp, ff=ff, cfg=BDWP), x)),
            (lambda: bdwp.nm_linear_packed(x, vals, idx, BDWP),
             lambda: O.nm_apply(O.PackedOp(vals, idx, BDWP), x,
                                backend="jnp")),
        ]
        for old_fn, new_fn in calls:
            with pytest.warns(DeprecationWarning):
                y_old = old_fn()
            _eq(y_old, new_fn())

    def test_conv_shims_warn_and_compute(self):
        bdwp.reset_deprecation_warnings()
        kw, kx = jax.random.split(jax.random.PRNGKey(21))
        w = jax.random.normal(kw, (3, 3, 16, 16), jnp.float32)
        x = jax.random.normal(kx, (2, 8, 8, 16), jnp.bfloat16)
        with pytest.warns(DeprecationWarning):
            y = bdwp.nm_conv(x, w, BDWP)
        _eq(y, O.nm_apply(O.MaskedOp(w, BDWP), x))
        ff = jnp.where(nm_mask(w, 2, 8, axis=2), w, 0.0).astype(jnp.bfloat16)
        bp = jnp.where(nm_mask(w, 2, 8, axis=3), w, 0.0).astype(jnp.bfloat16)
        with pytest.warns(DeprecationWarning):
            y = bdwp.nm_conv_pregen(x, ff, bp)
        _eq(y, O.nm_apply(O.PregenOp(bp=bp, ff=ff, cfg=BDWP), x))

    def test_shims_warn_once_per_process(self):
        """A per-step training loop through a shim must not spam one
        DeprecationWarning per call — only the first call warns."""
        bdwp.reset_deprecation_warnings()
        x, w, *_ = _pregen_arrays(23)
        with pytest.warns(DeprecationWarning):
            bdwp.nm_linear(x, w, BDWP)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            bdwp.nm_linear(x, w, BDWP)  # silent or this raises

    def test_is_pregen_covers_both_forms(self):
        x, w, vals, idx, ff, bp = _pregen_arrays(22)
        assert bdwp.is_pregen(O.PregenOp(bp=bp, ff=ff, cfg=BDWP))
        assert bdwp.is_pregen({"bp": bp, "ff": ff})
        assert not bdwp.is_pregen({"w": w})
        assert not bdwp.is_pregen(w)

    def test_shared_decompress_is_the_one_implementation(self):
        """The dedicated helper is bitwise nm_unpack_n (scatter formul.),
        and so is the kernel's in-VMEM tile decompress (read out through
        an identity activation)."""
        from repro.kernels import decompress_nm
        from repro.kernels.ops import nm_spmm

        x, w, vals, idx, ff, bp = _pregen_arrays(23)
        _eq(decompress_nm(vals, idx, 2, 8, axis=-2),
            nm_unpack_n(vals, idx, 2, 8, axis=-2))
        eye = jnp.eye(vals.shape[0] * 4, dtype=vals.dtype)
        _eq(nm_spmm(eye, vals, idx, 2, 8).astype(vals.dtype),
            nm_unpack_n(vals, idx, 2, 8, axis=0))
        # stacked leaves decompress along the same axis, batched
        xs, ws, vs, is_, ffs, bps = _pregen_arrays(24, stack=(3,))
        _eq(decompress_nm(vs, is_, 2, 8, axis=-2),
            nm_unpack_n(vs, is_, 2, 8, axis=-2))


def _to_dict_era(compute):
    """Convert PregenOp compute leaves back to the PR-3/4 dict layout."""
    def walk(node):
        if isinstance(node, O.PregenOp):
            return {f: node[f] for f in node.fields}
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(compute)


class TestCheckpointForwardCompat:
    """PR-3/PR-4-era checkpoints stored the compute tree as operand
    DICTS; they must restore bitwise into SparseOperand-typed state."""

    @pytest.mark.parametrize("pack", [False, True])
    def test_dict_leaf_checkpoint_restores_into_operands(self, tmp_path,
                                                         pack):
        state = ST.init_train_state(jax.random.PRNGKey(7), CFG, sp_cfg=BDWP,
                                    pregen_pack=pack)
        old_state = dict(state, compute=_to_dict_era(state["compute"]))
        assert (jax.tree_util.tree_structure(old_state["compute"])
                != jax.tree_util.tree_structure(state["compute"]))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, old_state, blocking=True)

        like = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                   pregen_pack=pack)
        restored = ST.restore_with_pregen(mgr, like, sp_cfg=BDWP,
                                          pregen_pack=pack)
        _tree_eq(restored, state)
        # ...and the restored compute leaves really are operands
        sites = [leaf for leaf in jax.tree.leaves(
            restored["compute"],
            is_leaf=lambda x: isinstance(x, O.PregenOp))
            if isinstance(leaf, O.PregenOp)]
        assert sites and all(s.is_packed == pack for s in sites)
        # the restored state steps
        mesh = _solo_mesh()
        bundle = ST.build_lm_train(CFG, mesh, BDWP, OPT, donate=False,
                                   pregen_pack=pack)
        restored = jax.device_put(restored, bundle.state_shardings)
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}
        _, metrics = bundle.step_fn(restored, batch)
        assert np.isfinite(float(metrics["loss"]))


@mesh8_only
class TestOperandSPMD:
    """The unified API on a forced 8-device mesh: packed consumption
    under GSPMD, and dict-era checkpoint restore across mesh shapes."""

    @pytest.fixture(scope="class")
    def mesh8(self):
        from repro.launch import spmd
        return spmd.make_spmd_mesh("pod,data,model")

    def _run(self, mesh, backend, pack=True, steps=2):
        from jax.sharding import NamedSharding

        bundle = ST.build_lm_train(CFG, mesh, BDWP, OPT, donate=False,
                                   pregen_pack=pack, nm_backend=backend)
        state = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                    pregen_pack=pack)
        state = jax.device_put(state, bundle.state_shardings)
        sh = {k: NamedSharding(mesh, ps)
              for k, ps in bundle.input_pspecs.items()}
        stream = D.lm_stream(CFG.vocab, 4, 32, shardings=sh, seed=0)
        losses = []
        for i, (_, b) in enumerate(stream):
            if i >= steps:
                break
            state, metrics = bundle.step_fn(state, b)
            losses.append(float(metrics["loss"]))
        return state, losses

    def test_sharded_packed_train_jnp_bitwise_vs_unpacked(self, mesh8):
        """On one mesh the packed and unpacked pregen states must stay
        bitwise equal (pack/decompress is exact under SPMD too)."""
        s_p, l_p = self._run(mesh8, "jnp", pack=True)
        s_u, l_u = self._run(mesh8, "jnp", pack=False)
        assert l_p == l_u
        for a, b in zip(jax.tree.leaves(s_p["master"]),
                        jax.tree.leaves(s_u["master"])):
            _eq(a, b)

    def test_sharded_packed_train_pallas_backend_runs_and_tracks(self, mesh8):
        """The kernel-consuming forward partitions under GSPMD (the
        kernel's fp32 K-block accumulation may legally re-order vs the
        fused dot, so cross-backend equality is tolerance, not bitwise,
        on a sharded mesh)."""
        _, l_p = self._run(mesh8, "pallas")
        _, l_j = self._run(mesh8, "jnp")
        np.testing.assert_allclose(l_p, l_j, rtol=2e-3)

    def test_dict_era_checkpoint_restores_across_meshes(self, tmp_path,
                                                        mesh8):
        """A dict-leaf (PR-3/4) checkpoint saved unsharded restores onto
        the 8-device mesh — elastic resharding straight into operand-
        typed state, bitwise."""
        state = ST.init_train_state(jax.random.PRNGKey(9), CFG, sp_cfg=BDWP,
                                    pregen_pack=True)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, dict(state, compute=_to_dict_era(state["compute"])),
                 blocking=True)
        bundle = ST.build_lm_train(CFG, mesh8, BDWP, OPT, donate=False,
                                   pregen_pack=True)
        like = ST.init_train_state(jax.random.PRNGKey(0), CFG, sp_cfg=BDWP,
                                   pregen_pack=True)
        restored = ST.restore_with_pregen(
            mgr, like, shardings=bundle.state_shardings, sp_cfg=BDWP,
            pregen_pack=True)
        _tree_eq(restored, state)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 32), jnp.int32)}
        _, metrics = bundle.step_fn(restored, batch)
        assert np.isfinite(float(metrics["loss"]))
