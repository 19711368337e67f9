"""The reference's Algorithm 2, its tie margins and the push of a seeded
draw into general position, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference

EPS = 1e-4


def draw(seed, K=288, N=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (K, N), jnp.float32)


def test_margin_finds_a_planted_tie():
    W = draw(0).at[3, 5].set(1e-9)     # level 0 takes sign(W): a tie
    margin, direction = reference.binarize(W, 2, 8)[2]
    assert float(margin[3, 5]) < 1e-6
    assert float(jnp.min(reference.binarize(draw(0), 2, 8)[2][0])) > 0
    assert set(np.unique(np.asarray(direction))) <= {-1.0, 1.0}


@pytest.mark.parametrize("seed", [1, 2])
def test_general_position_leaves_no_weight_near_a_tie(seed):
    W = draw(seed).at[3, 5].set(0.0)      # sign(0): a tie at level 0
    W2, left = reference.general_position(W, 2, 8, EPS, 3)
    assert int(left) == 0
    margin = reference.binarize(W2, 2, 8)[2][0]
    assert float(jnp.min(margin)) >= EPS
    moved = np.asarray(W2 != W)
    assert 0 < moved.sum() < 0.01 * W.size
    scale = np.asarray(jnp.mean(jnp.abs(W), axis=0))
    assert np.all(np.abs(np.asarray(W2 - W)) <= 3 * EPS * scale + 1e-9)


def test_reference_signs_match_the_program_in_general_position():
    """A second witness: the program's own binarizer takes the same signs
    as the reference on a draw in general position, and its alphas agree
    to float32 rounding."""
    from repro.core.binarize import algorithm2

    W, _ = reference.general_position(draw(3), 2, 8, EPS, 3)
    B, alpha, _ = reference.binarize(W, 2, 8)
    got = algorithm2(W, 2, K_iters=8)
    np.testing.assert_array_equal(np.asarray(got.B, np.float32),
                                  np.asarray(B))
    np.testing.assert_allclose(np.asarray(got.alpha[:, 0]),
                               np.asarray(alpha), rtol=1e-5)
