"""Multi-level binary weight approximation (BinArray, §II).

Implements:
  * Algorithm 1 (Guo et al., CVPR'17 "Network Sketching", as restated in the
    paper): greedy residual binarization followed by one least-squares solve
    for the scaling factors alpha.
  * Algorithm 2 (the paper's contribution): alternate between re-deriving the
    binary tensors B from the current *optimal* alpha and re-solving the
    least-squares system, until B is stable or K iterations.
  * Group-wise approximation: the paper binarizes per filter (= per output
    channel).  We generalize to groups along the reduction axis (group_size),
    which subsumes the paper's scheme (group_size == K) and allows finer
    accuracy control ("beyond paper", DESIGN.md §7).
  * Bit-packing of the ±1 tensors into uint8 (8 weights/byte) for the
    memory-roofline win on TPU, plus unpacking.
  * Compression-factor computation (paper Eq. 6).

Conventions
-----------
Weight matrices are stored as ``W[K, N]`` (reduction dim first, like
``x @ W``).  The paper's "filter" == one output channel == one column of W.
Binary tensors are ``B[M, K, N]`` (int8, values in {-1, +1}) and scales are
``alpha[M, G, N]`` where ``G = K // group_size`` (G == 1 reproduces the paper
exactly).  All functions are jit-able and differentiable where meaningful.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class BinApprox(NamedTuple):
    """Multi-level binary approximation of a weight matrix W[K, N]."""

    B: jax.Array          # [M, K, N] int8, values in {-1, +1}
    alpha: jax.Array      # [M, G, N] float32, per-(level, group, out-channel) scale
    group_size: int       # reduction-dim group size; K // group_size == G

    @property
    def M(self) -> int:
        return self.B.shape[0]

    @property
    def K(self) -> int:
        return self.B.shape[1]

    @property
    def N(self) -> int:
        return self.B.shape[2]


def _expand_alpha(alpha: jax.Array, K: int, group_size: int) -> jax.Array:
    """alpha[M, G, N] -> per-element scale [M, K, N] by repeating over groups."""
    return jnp.repeat(alpha, group_size, axis=1, total_repeat_length=K)


def reconstruct(approx: BinApprox) -> jax.Array:
    """W_hat = sum_m alpha_m * B_m   (paper Eq. 1), float32 [K, N]."""
    a = _expand_alpha(approx.alpha, approx.K, approx.group_size)
    return jnp.sum(a * approx.B.astype(jnp.float32), axis=0)


def residual_error(W: jax.Array, approx: BinApprox) -> jax.Array:
    """||W - W_hat||^2 (paper Eq. 4 objective), scalar."""
    return jnp.sum((W.astype(jnp.float32) - reconstruct(approx)) ** 2)


# ---------------------------------------------------------------------------
# Least-squares solve for alpha given B (paper Eq. 5)
# ---------------------------------------------------------------------------

def solve_alpha(W: jax.Array, B: jax.Array, group_size: int) -> jax.Array:
    """Optimal alpha for given binary tensors (paper Eq. 5), per group & column.

    For each (group g, column n) solves the M-dim normal equations
        (B_g^T B_g) alpha = B_g^T w_g
    where B_g is the [group_size, M] slice.  Singular Gram matrices (duplicate
    binary tensors) are handled with a pseudo-inverse-style ridge.
    """
    M, K, N = B.shape
    G = K // group_size
    Bf = B.astype(jnp.float32).reshape(M, G, group_size, N)
    Wf = W.astype(jnp.float32).reshape(G, group_size, N)
    # Gram[G, N, M, M] and rhs[G, N, M], at full fp32 precision: on a TPU the
    # default would round W to bf16, and the packed weights a program is
    # compiled with would then depend on the backend that binarized them.
    hi = jax.lax.Precision.HIGHEST
    gram = jnp.einsum("mgkn,lgkn->gnml", Bf, Bf, precision=hi)
    rhs = jnp.einsum("mgkn,gkn->gnm", Bf, Wf, precision=hi)
    # Ridge for rank-deficient B (e.g. B_m == B_l): tiny relative jitter.
    eye = jnp.eye(M, dtype=jnp.float32)
    jitter = 1e-6 * jnp.maximum(jnp.trace(gram, axis1=-2, axis2=-1), 1.0)
    gram = gram + eye * jitter[..., None, None]
    alpha = jnp.linalg.solve(gram, rhs[..., None])[..., 0]  # [G, N, M]
    return jnp.transpose(alpha, (2, 0, 1))  # [M, G, N]


# ---------------------------------------------------------------------------
# Algorithm 1 (Guo et al. / paper Algorithm 1)
# ---------------------------------------------------------------------------

def _greedy_binarize(W: jax.Array, M: int, group_size: int) -> tuple[jax.Array, jax.Array]:
    """Steps 1-5 of Algorithm 1: greedy residual binarization.

    Returns (B[M,K,N] int8, alpha_hat[M,G,N]) where alpha_hat are the greedy
    mean-|residual| estimates (paper step 4).
    """
    K, N = W.shape
    G = K // group_size

    def body(carry, _):
        dW = carry
        Bm = jnp.where(dW >= 0, 1.0, -1.0)
        # mean(|dW|) per (group, column) — paper: mean(dW ⊙ B_m) over the filter
        a = jnp.mean(
            jnp.abs(dW).reshape(G, group_size, N), axis=1
        )  # [G, N]
        dW = dW - Bm * jnp.repeat(a, group_size, axis=0, total_repeat_length=K)
        return dW, (Bm.astype(jnp.int8), a)

    _, (B, alpha_hat) = jax.lax.scan(body, W.astype(jnp.float32), None, length=M)
    return B, alpha_hat


def algorithm1(W: jax.Array, M: int, *, group_size: int | None = None) -> BinApprox:
    """Paper Algorithm 1: greedy B, then one LS solve for alpha (Eq. 5)."""
    K, N = W.shape
    group_size = K if group_size is None else group_size
    if K % group_size:
        raise ValueError(f"group_size {group_size} must divide K={K}")
    B, _ = _greedy_binarize(W, M, group_size)
    alpha = solve_alpha(W, B, group_size)
    return BinApprox(B=B, alpha=alpha, group_size=group_size)


# ---------------------------------------------------------------------------
# Algorithm 2 (the paper's contribution)
# ---------------------------------------------------------------------------

def algorithm2(
    W: jax.Array,
    M: int,
    *,
    K_iters: int = 100,
    group_size: int | None = None,
) -> BinApprox:
    """Paper Algorithm 2: alternate B-refinement and LS alpha until stable.

    Lines 3-11 of the paper: starting from Algorithm 1's (B, alpha), re-derive
    each B_m as sign of the residual under the *optimal* alpha (not the greedy
    estimate), then re-solve Eq. 5; stop when B is unchanged or after K_iters.
    Implemented with lax.while_loop so it jit-compiles; the early-exit
    condition (B == B_old) is honored exactly.
    """
    Kdim, N = W.shape
    group_size = Kdim if group_size is None else group_size
    if Kdim % group_size:
        raise ValueError(f"group_size {group_size} must divide K={Kdim}")
    init = algorithm1(W, M, group_size=group_size)
    Wf = W.astype(jnp.float32)

    def refine_B(alpha: jax.Array) -> jax.Array:
        """Lines 6-9: greedy sign pass using the current optimal alpha."""
        def body(carry, am):
            dW = carry
            Bm = jnp.where(dW >= 0, 1.0, -1.0)
            dW = dW - Bm * jnp.repeat(
                am, group_size, axis=0, total_repeat_length=Kdim
            )
            return dW, Bm.astype(jnp.int8)

        _, B = jax.lax.scan(body, Wf, alpha)  # alpha scanned over M
        return B

    def cond(state):
        it, B, B_old, _ = state
        changed = jnp.any(B != B_old)
        return jnp.logical_and(it < K_iters, changed)

    def body(state):
        it, B, _, alpha = state
        B_new = refine_B(alpha)
        alpha_new = solve_alpha(W, B_new, group_size)
        return (it + 1, B_new, B, alpha_new)

    # Seed B_old with ~B so the loop runs at least once.
    state0 = (jnp.int32(0), init.B, -init.B, init.alpha)
    _, B, _, alpha = jax.lax.while_loop(cond, body, state0)
    return BinApprox(B=B, alpha=alpha, group_size=group_size)


# ---------------------------------------------------------------------------
# Generic tensor entry points (conv kernels, stacked layers, ...)
# ---------------------------------------------------------------------------

def approximate_tensor(
    W: jax.Array,
    M: int,
    *,
    algorithm: int = 2,
    K_iters: int = 100,
    group_size: int | None = None,
    reduce_axes: tuple[int, ...] | None = None,
) -> tuple[BinApprox, tuple[int, ...]]:
    """Binarize an arbitrary-rank weight tensor.

    ``reduce_axes`` are the contraction axes (flattened into K); the remaining
    axes are output channels (flattened into N).  Returns the approximation of
    the [K, N] matrix plus the permutation used, so callers can reshape back.
    Conv kernels HWIO use reduce_axes=(0,1,2); the paper's per-filter scheme
    falls out as group_size=None (= whole filter).
    """
    if reduce_axes is None:
        reduce_axes = tuple(range(W.ndim - 1))
    out_axes = tuple(i for i in range(W.ndim) if i not in reduce_axes)
    perm = reduce_axes + out_axes
    Wm = jnp.transpose(W, perm)
    K = int(np.prod([W.shape[i] for i in reduce_axes]))
    N = int(np.prod([W.shape[i] for i in out_axes])) if out_axes else 1
    Wm = Wm.reshape(K, N)
    fn = algorithm2 if algorithm == 2 else algorithm1
    kwargs = {"group_size": group_size}
    if algorithm == 2:
        kwargs["K_iters"] = K_iters
    return fn(Wm, M, **kwargs), perm


# ---------------------------------------------------------------------------
# Bit packing (TPU adaptation: 1-bit weights in HBM)
# ---------------------------------------------------------------------------

def pack_bits(B: jax.Array) -> jax.Array:
    """Pack ±1 int8 [M, K, N] -> uint8 [M, K//8, N]; bit j of byte k is B[8k+j].

    +1 -> bit 1, -1 -> bit 0.  K must be a multiple of 8 (pad upstream).
    """
    M, K, N = B.shape
    if K % 8:
        raise ValueError(f"K={K} must be a multiple of 8 for packing")
    bits = (B > 0).astype(jnp.uint8).reshape(M, K // 8, 8, N)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1)
    return jnp.sum(bits << shifts, axis=2).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, K: int) -> jax.Array:
    """uint8 [M, K//8, N] -> ±1 int8 [M, K, N] (inverse of pack_bits)."""
    M, K8, N = packed.shape
    if K8 * 8 != K:
        raise ValueError(f"packed K//8={K8} inconsistent with K={K}")
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1)
    bits = (packed[:, :, None, :] >> shifts) & jnp.uint8(1)
    return (bits.astype(jnp.int8) * 2 - 1).reshape(M, K, N)


class PackedBinApprox(NamedTuple):
    """Deployment form: bit-packed binary tensors + scales."""

    B_packed: jax.Array   # [M, K//8, N] uint8
    alpha: jax.Array      # [M, G, N] float32 (or bf16)
    K: int
    group_size: int


def pack(approx: BinApprox) -> PackedBinApprox:
    return PackedBinApprox(
        B_packed=pack_bits(approx.B),
        alpha=approx.alpha,
        K=approx.K,
        group_size=approx.group_size,
    )


def unpack(packed: PackedBinApprox) -> BinApprox:
    return BinApprox(
        B=unpack_bits(packed.B_packed, packed.K),
        alpha=packed.alpha,
        group_size=packed.group_size,
    )


# ---------------------------------------------------------------------------
# Compression factor (paper Eq. 6)
# ---------------------------------------------------------------------------

def compression_factor(
    N_c: int, M: int, *, bits_w: int = 32, bits_alpha: int = 8, n_bias: int = 1
) -> float:
    """(N_c + 1)·bits_w / (M·(N_c + bits_alpha))  — paper Eq. 6 exactly."""
    return ((N_c + n_bias) * bits_w) / (M * (N_c + bits_alpha))


# ---------------------------------------------------------------------------
# Straight-through estimator (paper §V-B1 retraining)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def ste_binarize(W: jax.Array, W_hat: jax.Array) -> jax.Array:
    """Forward: the binary reconstruction W_hat; backward: identity to W.

    This is the straight-through estimation of BinaryNet ([5] in the paper)
    used for the paper's one-epoch retraining: gradients flow to the latent
    real-valued weights as if the binarization were the identity.
    """
    del W
    return W_hat


def _ste_fwd(W, W_hat):
    return W_hat, None


def _ste_bwd(_, g):
    return g, jnp.zeros_like(g)


ste_binarize.defvjp(_ste_fwd, _ste_bwd)


def fake_quant(
    W: jax.Array,
    M: int,
    *,
    algorithm: int = 2,
    K_iters: int = 8,
    group_size: int | None = None,
    m_active: int | None = None,
) -> jax.Array:
    """QAT forward: W -> STE(binary reconstruction of W).  Differentiable.

    ``m_active`` reconstructs from the first ``m_active`` of the M levels
    only — the §IV-D runtime switch, as the packed deployment applies it."""
    approx = (algorithm2 if algorithm == 2 else algorithm1)(
        W, M, group_size=group_size,
        **({"K_iters": K_iters} if algorithm == 2 else {}),
    )
    if m_active is not None:
        approx = approx._replace(B=approx.B[:m_active],
                                 alpha=approx.alpha[:m_active])
    # The binarization itself (incl. the Alg-2 while_loop) is not part of the
    # gradient path — STE routes dL/dW_hat straight to the latent weights.
    return ste_binarize(W, jax.lax.stop_gradient(reconstruct(approx)))
