"""Property suite for the gradient compressor (hypothesis).

The cross-pod sync trusts three exact identities, pinned here:

  * the vectorized jnp fast path (kernels/ops._jnp_grad_compress /
    _jnp_grad_decompress_mean) is BITWISE-identical to the readable
    ref.py oracles — including argmax-vs-top_k tie breaking, the
    compare-swap index ordering, and the scatter-free residual;
  * error feedback telescopes exactly: decode(payload) + new_err
    reconstructs g + err bit-for-bit in f32 (optim/compress leans on
    this to skip decoding the own pod's payload);
  * one transposable mask legally serves W and Wᵀ: N-per-group holds
    along BOTH orientations (Hubara et al., arXiv 2102.08124), which is
    what lets a single stored mask feed FF and BP packed operands.

Plus the refusal properties: bucket plans may never split an M-group,
and the MVUE estimator (arXiv 2203.10991) is exact when a group has
≤ n nonzeros.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import require_or_skip

require_or_skip("hypothesis")  # bare env: skip; CI (REQUIRE_HYPOTHESIS): fail
from hypothesis import given, settings, strategies as st

from repro.core import sparsity as S
from repro.kernels import ops, ref
from repro.optim import compress as C

jax.config.update("jax_platform_name", "cpu")

NM = st.sampled_from([(1, 4), (2, 4), (2, 8), (1, 8), (4, 8), (2, 16)])


def _grads(shape, seed, ties=False):
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, shape, jnp.float32)
    if ties:
        # quantize to a handful of magnitudes: most groups now contain
        # duplicated |g|, exercising the tie-break rule on every call
        g = jnp.round(g * 2) / 2
    return g


class TestFastPathBitwise:
    @settings(max_examples=25, deadline=None)
    @given(nm=NM, seed=st.integers(0, 2**16), rows=st.sampled_from([1, 3]),
           groups=st.integers(1, 24), ties=st.booleans())
    def test_compress_matches_oracle(self, nm, seed, rows, groups, ties):
        n, m = nm
        g = _grads((rows, groups * m), seed, ties)
        err = _grads((rows, groups * m), seed + 1) * 0.1
        v, i, e = ops.grad_compress(g, err, n, m, use_pallas=False)
        rv, ri, re_ = ref.ref_grad_compress(g, err, n, m)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(re_))

    @settings(max_examples=20, deadline=None)
    @given(nm=NM, seed=st.integers(0, 2**16), pods=st.sampled_from([1, 2, 4]),
           groups=st.integers(1, 16))
    def test_decompress_mean_matches_oracle(self, nm, seed, pods, groups):
        n, m = nm
        g = _grads((pods, groups * m), seed)
        v, i, _ = ops.grad_compress(g, jnp.zeros_like(g), n, m,
                                    use_pallas=False)
        out = ops.grad_decompress_mean(v, i, n, m, use_pallas=False)
        rout = ref.ref_grad_decompress_mean(v, i, n, m)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(rout))

    def test_all_zero_and_all_tied_groups(self):
        # degenerate tie patterns: every lane identical, and all-zero
        g = jnp.concatenate([jnp.zeros((2, 16)), jnp.ones((2, 16))], axis=1)
        err = jnp.zeros_like(g)
        v, i, e = ops.grad_compress(g, err, 2, 8, use_pallas=False)
        rv, ri, re_ = ref.ref_grad_compress(g, err, 2, 8)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(re_))
        # lower index wins every tie: the all-ones groups keep lanes 0, 1
        kept = np.asarray(i)[:, 4:].reshape(2, 2, 2)
        np.testing.assert_array_equal(kept, np.broadcast_to([0, 1], kept.shape))


class TestTelescoping:
    @settings(max_examples=25, deadline=None)
    @given(nm=NM, seed=st.integers(0, 2**16), groups=st.integers(1, 24),
           ties=st.booleans(), steps=st.integers(1, 4))
    def test_decode_plus_residual_is_exact(self, nm, seed, groups, ties, steps):
        """decode(payload) + new_err == g + err bitwise, every step.

        The sync's own-pod decode skip rewrites decode(own) as
        t - new_err; that rewrite is sound iff this holds exactly."""
        n, m = nm
        err = jnp.zeros((1, groups * m), jnp.float32)
        for s in range(steps):
            g = _grads((1, groups * m), seed + s, ties)
            t = g + err
            v, i, err = ops.grad_compress(g, err, n, m, use_pallas=False)
            dec = ops.grad_decompress_mean(v, i, n, m, use_pallas=False)
            np.testing.assert_array_equal(
                np.asarray(dec) + np.asarray(err)[0], np.asarray(t)[0])

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16), groups=st.integers(1, 8))
    def test_pallas_interpret_roundtrip_bitwise(self, seed, groups):
        """Packed roundtrip through the Pallas kernels (interpret mode on
        CPU) is bitwise the jnp reference path — payload, index AND
        residual, so either backend may feed the sync."""
        n, m = 2, 8
        g = _grads((1, groups * m), seed, ties=True)
        err = _grads((1, groups * m), seed + 1) * 0.1
        v, i, e = ops.grad_compress(g, err, n, m, use_pallas=True)
        jv, ji, je = ops.grad_compress(g, err, n, m, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(jv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ji))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(je))
        d = ops.grad_decompress_mean(v, i, n, m, use_pallas=True)
        jd = ops.grad_decompress_mean(jv, ji, n, m, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(jd))


class TestBf16Round:
    """``ref.bf16_round`` — the one rounding the oracle, the jnp path and
    the kernels share — is the plain f32 -> bf16 -> f32 convert."""

    @settings(max_examples=50, deadline=None)
    @given(bits=st.lists(st.integers(0, 2**32 - 1), min_size=1,
                         max_size=64))
    def test_bitwise_equal_to_convert_on_finite(self, bits):
        x = np.asarray(bits, np.uint32).view(np.float32)
        x = x[np.isfinite(x)]
        want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                          .astype(jnp.float32)).view(np.uint32)
        got = np.asarray(ref.bf16_round(jnp.asarray(x))).view(np.uint32)
        np.testing.assert_array_equal(got, want)

    def test_ties_overflow_inf_and_nan(self):
        one_ulp = 2.0 ** -7  # bf16 spacing at 1.0
        big = float(jnp.finfo(jnp.bfloat16).max)
        x = jnp.asarray([1 + one_ulp / 2, 1 + 1.5 * one_ulp,  # ties to even
                         big * (1 + 3 * 2.0 ** -9), -np.inf, np.inf],
                        jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(ref.bf16_round(x)),
            [1.0, 1 + 2 * one_ulp, np.inf, -np.inf, np.inf])
        # NaN stays NaN with its sign, whatever its payload (an all-ones
        # mantissa must not carry into the sign or exponent bits)
        nans = np.asarray([0x7FFFFFFF, 0xFFFFFFFF, 0x7F800001, 0x7FC00000],
                          np.uint32).view(np.float32)
        got = np.asarray(ref.bf16_round(jnp.asarray(nans)))
        assert np.isnan(got).all()
        np.testing.assert_array_equal(np.signbit(got), np.signbit(nans))


class TestTransposableMask:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**16),
           nm=st.sampled_from([(1, 4), (2, 4), (2, 8)]),
           r=st.sampled_from([8, 16, 32]))
    def test_n_per_group_both_orientations(self, seed, nm, r):
        n, m = nm
        w = _grads((r, r), seed)
        mask = S.nm_mask_transposable(w, n, m)
        mk = np.asarray(mask)
        rows = mk.reshape(r, r // m, m).sum(-1)
        cols = mk.T.reshape(r, r // m, m).sum(-1)
        assert (rows <= n).all(), "row orientation violates N:M"
        assert (cols <= n).all(), "column orientation violates N:M"

    def test_one_mask_serves_w_and_wt(self):
        w = _grads((16, 16), 7)
        mask = S.nm_mask_transposable(w, 2, 8)
        # FF consumes W under mask, BP consumes Wᵀ under maskᵀ: both are
        # valid N:M operands from the SAME stored mask
        for mat, mk in ((w, mask), (w.T, mask.T)):
            v, i = S.nm_pack_from_mask(jnp.where(mk, mat, 0.0), mk, 2, 8,
                                       axis=-1)
            assert v.shape == (16, 16 // 8 * 2)
            groups = np.asarray(mk).reshape(16, 2, 8).sum(-1)
            assert (groups <= 2).all()


class TestBucketIntegrity:
    @settings(max_examples=30, deadline=None)
    @given(m=st.sampled_from([4, 8, 16]), total_groups=st.integers(1, 64),
           bucket_groups=st.integers(1, 16))
    def test_aligned_plans_cover_exactly(self, m, total_groups, bucket_groups):
        total = total_groups * m
        buckets = C.plan_buckets(total, bucket_groups * m, m)
        assert buckets[0][0] == 0 and buckets[-1][1] == total
        for (s0, e0), (s1, e1) in zip(buckets, buckets[1:]):
            assert e0 == s1
        assert all(s % m == 0 and e % m == 0 for s, e in buckets)

    @settings(max_examples=30, deadline=None)
    @given(m=st.sampled_from([4, 8, 16]), off=st.integers(1, 15))
    def test_group_splitting_refused(self, m, off):
        bad = (off if off % m else off + 1)
        with pytest.raises(ValueError):
            C.plan_buckets(16 * m, bad, m)
        with pytest.raises(ValueError):
            C.GradCompressConfig(m=m, bucket_elems=bad)
        with pytest.raises(ValueError):
            C.plan_buckets(16 * m + bad, 4 * m, m)


class TestMvue:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**16), groups=st.integers(1, 12),
           nm=st.sampled_from([(2, 8), (2, 4), (1, 8)]))
    def test_exact_when_group_has_le_n_nonzeros(self, seed, nm, groups):
        """≤ n nonzeros per group: every nonzero gets p=1, no rescaling,
        no sampling noise — the estimate IS the input (arXiv 2203.10991's
        exactness regime).  bf16-representable inputs keep it bitwise."""
        n, m = nm
        key = jax.random.PRNGKey(seed)
        lanes = jax.random.randint(key, (groups, n), 0, m)
        t = np.zeros((groups, m), np.float32)
        vals = np.asarray(
            jax.random.randint(jax.random.PRNGKey(seed + 1),
                               (groups, n), -8, 9), np.float32)
        for gi in range(groups):
            for j in range(n):
                t[gi, int(lanes[gi, j])] = vals[gi, j]  # dups just overwrite
        flat = jnp.asarray(t.reshape(1, groups * m))
        v, i = C.mvue_compress(flat, n, m, jax.random.PRNGKey(seed + 2))
        dec = ops.grad_decompress_mean(
            v.reshape(1, -1), i.reshape(1, -1), n, m, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(dec), t.reshape(-1))

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), groups=st.integers(1, 12))
    def test_payload_is_nm_shaped(self, seed, groups):
        n, m = 2, 8
        flat = _grads((1, groups * m), seed)
        v, i = C.mvue_compress(flat, n, m, jax.random.PRNGKey(seed))
        assert v.shape == (1, groups * n) and i.shape == (1, groups * n)
        ii = np.asarray(i).reshape(groups, n)
        assert (ii < m).all()
        assert (np.diff(ii, axis=-1) > 0).all(), "indices ascending per group"
