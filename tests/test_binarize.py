"""Unit + property tests for the paper's §II approximation procedures."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import binarize as bz

jax.config.update("jax_platform_name", "cpu")


def _rand_w(key, K, N, scale=1.0):
    return jax.random.normal(key, (K, N)) * scale


class TestAlgorithm1:
    def test_first_tensor_is_sign(self):
        """B_1 = sign(W) — the paper's rationale for Algorithm 1 step 3."""
        W = _rand_w(jax.random.PRNGKey(0), 32, 8)
        a = bz.algorithm1(W, M=3)
        np.testing.assert_array_equal(
            np.asarray(a.B[0]), np.where(np.asarray(W) >= 0, 1, -1)
        )

    def test_residual_decreases_with_M(self):
        """More binary tensors -> better approximation (paper §II-A)."""
        W = _rand_w(jax.random.PRNGKey(1), 64, 16)
        errs = [float(bz.residual_error(W, bz.algorithm1(W, M=m))) for m in (1, 2, 3, 4)]
        assert all(errs[i + 1] < errs[i] for i in range(len(errs) - 1)), errs

    def test_alpha_is_least_squares_optimal(self):
        """Paper Eq. 5: alpha from solve() beats the greedy estimates."""
        W = _rand_w(jax.random.PRNGKey(2), 48, 4)
        B, alpha_hat = bz._greedy_binarize(W, 3, 48)
        greedy = bz.BinApprox(B=B, alpha=alpha_hat[:, None, :] if alpha_hat.ndim == 2 else alpha_hat, group_size=48)
        # reshape greedy alphas [M, G=1, N]
        greedy = bz.BinApprox(B=B, alpha=alpha_hat.reshape(3, 1, 4), group_size=48)
        ls = bz.algorithm1(W, M=3)
        assert float(bz.residual_error(W, ls)) <= float(bz.residual_error(W, greedy)) + 1e-5

    def test_exact_recovery_when_W_is_binary_combination(self):
        """If W = a1*B1 + a2*B2 exactly, M=2 recovers it to fp precision."""
        key = jax.random.PRNGKey(3)
        k1, k2 = jax.random.split(key)
        B1 = jnp.where(jax.random.bernoulli(k1, 0.5, (40, 8)), 1.0, -1.0)
        B2 = jnp.where(jax.random.bernoulli(k2, 0.5, (40, 8)), 1.0, -1.0)
        W = 0.7 * B1 + 0.2 * B2
        a = bz.algorithm2(W, M=2, K_iters=50)
        assert float(bz.residual_error(W, a)) < 1e-8


class TestAlgorithm2:
    @pytest.mark.parametrize("M", [1, 2, 3, 4])
    def test_alg2_never_worse_than_alg1(self, M):
        """The paper's central §II claim."""
        for seed in range(5):
            W = _rand_w(jax.random.PRNGKey(seed), 72, 12)
            e1 = float(bz.residual_error(W, bz.algorithm1(W, M=M)))
            e2 = float(bz.residual_error(W, bz.algorithm2(W, M=M, K_iters=100)))
            assert e2 <= e1 + 1e-5, (seed, M, e1, e2)

    def test_alg2_monotone_in_M(self):
        """Monotone accuracy increase with M — what Alg-1 lacks (Table II)."""
        W = _rand_w(jax.random.PRNGKey(7), 96, 16)
        errs = [
            float(bz.residual_error(W, bz.algorithm2(W, M=m, K_iters=100)))
            for m in (1, 2, 3, 4, 5)
        ]
        assert all(errs[i + 1] <= errs[i] + 1e-6 for i in range(len(errs) - 1)), errs

    def test_alg2_jits(self):
        W = _rand_w(jax.random.PRNGKey(8), 32, 8)
        f = jax.jit(lambda w: bz.reconstruct(bz.algorithm2(w, M=2, K_iters=10)))
        out = f(W)
        assert out.shape == W.shape and bool(jnp.all(jnp.isfinite(out)))

    def test_groupwise_alpha_improves_residual(self):
        """Beyond-paper: finer alpha groups fit at least as well."""
        W = _rand_w(jax.random.PRNGKey(9), 64, 8)
        e_filter = float(bz.residual_error(W, bz.algorithm2(W, M=2, K_iters=30)))
        e_group = float(
            bz.residual_error(W, bz.algorithm2(W, M=2, K_iters=30, group_size=16))
        )
        assert e_group <= e_filter + 1e-5


class TestPacking:
    @pytest.mark.parametrize("K,N,M", [(8, 4, 1), (64, 16, 3), (128, 8, 4)])
    def test_pack_unpack_roundtrip(self, K, N, M):
        key = jax.random.PRNGKey(K + N + M)
        B = jnp.where(jax.random.bernoulli(key, 0.5, (M, K, N)), 1, -1).astype(jnp.int8)
        np.testing.assert_array_equal(
            np.asarray(bz.unpack_bits(bz.pack_bits(B), K)), np.asarray(B)
        )

    def test_packed_size_is_one_sixteenth_of_bf16(self):
        B = jnp.ones((2, 128, 64), jnp.int8)
        packed = bz.pack_bits(B)
        assert packed.size == B.size // 8  # 1 byte per 8 weights
        # vs bf16 dense: 2 bytes/weight for M=2 levels -> 16x per level pair
        assert (128 * 64 * 2) / (packed.size / 2) == 16.0


class TestCompressionFactor:
    def test_eq6_examples_from_paper(self):
        """Paper: cf -> 16, 10.7, 8 for M = 2, 3, 4 at bits_w=32."""
        for M, expect in [(2, 16.0), (3, 32 / 3), (4, 8.0)]:
            cf = bz.compression_factor(100000, M)
            assert abs(cf - expect) < 0.05, (M, cf, expect)

    def test_table2_cnn_a_values(self):
        """Table II CNN-A: cf = 15.8, 10.6, 7.9 for M = 2, 3, 4.

        CNN-A's mean filter size gives cf slightly under the asymptote; with
        a representative N_c (the 4x4x5 conv filter = 80 coeffs, plus bias)
        Eq. 6 lands in the Table II ballpark.
        """
        cf2 = bz.compression_factor(80, 2, bits_w=32, bits_alpha=8)
        assert 14.5 < cf2 < 16.0, cf2


class TestSTE:
    def test_fake_quant_gradient_is_straight_through(self):
        W = _rand_w(jax.random.PRNGKey(11), 24, 8)
        x = jax.random.normal(jax.random.PRNGKey(12), (4, 24))

        def loss(w):
            return jnp.sum(x @ bz.fake_quant(w, M=2, K_iters=5))

        g = jax.grad(loss)(W)
        # STE: dL/dW == x^T @ ones — as if binarization were identity
        expect = x.T @ jnp.ones((4, 8))
        np.testing.assert_allclose(np.asarray(g), np.asarray(expect), rtol=1e-5)

    def test_fake_quant_forward_is_reconstruction(self):
        W = _rand_w(jax.random.PRNGKey(13), 24, 8)
        got = bz.fake_quant(W, M=3, K_iters=20)
        expect = bz.reconstruct(bz.algorithm2(W, M=3, K_iters=20))
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-6)

    @pytest.mark.parametrize("m_active", [1, 2, 3])
    def test_fake_quant_m_active_keeps_leading_levels(self, m_active):
        """§IV-D: m_active of M levels reconstructs from the first m_active
        levels of the M-level approximation, as the packed path applies."""
        W = _rand_w(jax.random.PRNGKey(14), 24, 8)
        a = bz.algorithm2(W, M=3, K_iters=20)
        expect = bz.reconstruct(a._replace(B=a.B[:m_active],
                                           alpha=a.alpha[:m_active]))
        got = bz.fake_quant(W, M=3, K_iters=20, m_active=m_active)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@settings(max_examples=25, deadline=None)
@given(
    K=st.sampled_from([8, 16, 32, 64]),
    N=st.integers(1, 12),
    M=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_residual_bounded_by_alg1(K, N, M, seed):
    """Property: Alg-2 residual <= Alg-1 residual for any shape/seed."""
    W = jax.random.normal(jax.random.PRNGKey(seed), (K, N))
    e1 = float(bz.residual_error(W, bz.algorithm1(W, M=M)))
    e2 = float(bz.residual_error(W, bz.algorithm2(W, M=M, K_iters=25)))
    assert e2 <= e1 + 1e-4 * max(e1, 1.0)


@settings(max_examples=20, deadline=None)
@given(
    K=st.sampled_from([8, 24, 40]),
    N=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_reconstruction_values_in_omega(K, N, seed):
    """Every reconstructed weight lies in the 2^M-element set omega (Eq. 3)."""
    M = 3
    W = jax.random.normal(jax.random.PRNGKey(seed), (K, N))
    a = bz.algorithm2(W, M=M, K_iters=25)
    W_hat = np.asarray(bz.reconstruct(a))
    alpha = np.asarray(a.alpha)[:, 0, :]  # [M, N]
    for n in range(N):
        omega = set()
        for signs in np.ndindex(*([2] * M)):
            s = sum((1 if b else -1) * alpha[m, n] for m, b in enumerate(signs))
            omega.add(round(float(s), 4))
        col = {round(float(v), 4) for v in W_hat[:, n]}
        assert col <= omega, (n, col - omega)
