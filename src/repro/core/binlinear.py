"""Quantizable linear layer — the paper's technique as a first-class module.

Three execution modes, selected by ``QuantConfig.mode``:

  * ``dense``      — ordinary ``x @ W`` (the fp baseline the paper compares to).
  * ``fake_quant`` — QAT/retraining: forward uses the binary reconstruction
                     W_hat = sum_m alpha_m B_m with a straight-through gradient
                     to the latent fp weights (paper §V-B1 retraining).
  * ``binary``     — deployment: weights stored bit-packed (uint8), the matmul
                     is  y = sum_{m<m_active} alpha_m (x @ B_m)  (paper Eq. 8),
                     executed either by the Pallas kernel (TPU) or the jnp
                     reference path (CPU / dry-run lowering).

``m_active`` implements the paper's runtime accuracy↔throughput switch
(§IV-D): a BinArray built with M levels can serve with any m_active <= M.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import binarize as bz


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    mode: str = "dense"             # dense | fake_quant | binary
    M: int = 2                      # number of binary levels (paper M)
    algorithm: int = 2              # 1 = Guo et al., 2 = paper's Algorithm 2
    K_iters: int = 8                # Alg-2 refinement budget inside jit
    group_size: int | None = None   # None = per-output-channel (paper)
    m_active: int | None = None     # runtime levels used (<= M); None = all
    m_schedule: tuple[int, ...] | None = None  # per-layer §IV-D schedule:
                                    # entry i is m_active for decoder layer i
                                    # (models.common.layer_quant_cfg resolves
                                    # it; forces unrolled layer walks)
    use_pallas: bool = False        # route binary mode through Pallas kernel
    interpret: bool = False         # Pallas interpret mode (CPU validation)
    fuse_conv: bool = False         # binary convs: fused implicit-GEMM kernel
                                    # (patches in VMEM, AMU epilogue) instead
                                    # of HBM im2col + matmul; needs use_pallas
    conv_batch_tile: int | None = None   # fused conv kernels: images folded
                                    # per program (NB); None = auto pick_tile
                                    # co-pick with the row tile
    conv_vmem_budget: int | None = None  # per-program VMEM budget override
                                    # for the (NB, BU) pick (bytes; None =
                                    # kernels' DEFAULT_VMEM_BUDGET)

    def replace(self, **kw: Any) -> "QuantConfig":
        return dataclasses.replace(self, **kw)


DENSE = QuantConfig(mode="dense")


def init_linear(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: float | None = None):
    """LeCun-normal weight init; returns {'w': [K, N]}."""
    s = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    return {"w": (jax.random.normal(key, (in_dim, out_dim)) * s).astype(dtype)}


def binarize_params(params: dict, qc: QuantConfig) -> dict:
    """Offline conversion: fp weights -> packed binary deployment params.

    Returns {'B_packed': uint8 [M, ceil(K/8), N], 'alpha': [M, G, N]}
    (+ bias kept).  K is padded to a multiple of 8 if needed (padded rows
    multiply zero-padded activations).  Only array leaves — the static K /
    group_size are re-derived from shapes at apply time, so the packed tree
    is jit/eval_shape/checkpoint-safe.
    """
    W = params["w"]
    K, N = W.shape
    approx, _ = bz.approximate_tensor(
        W.astype(jnp.float32), qc.M, algorithm=qc.algorithm,
        K_iters=qc.K_iters, group_size=qc.group_size,
    )
    B, alpha = approx.B, approx.alpha
    pad = (-K) % 8
    if pad:
        B = jnp.concatenate([B, jnp.ones((qc.M, pad, N), jnp.int8)], axis=1)
    packed = bz.pack_bits(B)
    out = {"B_packed": packed, "alpha": alpha}
    if "b" in params:
        out["b"] = params["b"]
    return out


def apply_linear(params: dict, x: jax.Array, qc: QuantConfig = DENSE) -> jax.Array:
    """y = quantized-linear(x).  x: [..., K] -> [..., N].

    The execution path is keyed on the params' form: packed trees
    ('B_packed' present) always take the binary path; fp trees follow
    qc.mode (dense | fake_quant).
    """
    if "B_packed" in params:
        y = _apply_binary(params, x, qc)
    elif qc.mode == "fake_quant":
        W = params["w"].astype(jnp.float32)
        W_hat = bz.fake_quant(
            W, qc.M, algorithm=qc.algorithm, K_iters=qc.K_iters,
            group_size=qc.group_size, m_active=qc.m_active,
        )
        y = x @ W_hat.astype(x.dtype)
    else:
        y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def _apply_binary(params: dict, x: jax.Array, qc: QuantConfig) -> jax.Array:
    """Deployment path over packed weights (paper Eq. 8).  The static K and
    group_size are re-derived from shapes: K = x's trailing dim, group_size
    = K // G (binarization guarantees exact division)."""
    K = x.shape[-1]
    G = params["alpha"].shape[1]
    group_size = K // G
    m_active = qc.m_active or params["alpha"].shape[0]
    if qc.use_pallas:
        from repro.kernels import ops as kops

        return kops.binary_matmul(
            x, params["B_packed"], params["alpha"],
            K=K, group_size=group_size,
            m_active=m_active, interpret=qc.interpret,
        )
    from repro.kernels import ref as kref

    y = kref.binary_matmul_ref(
        x, params["B_packed"], params["alpha"],
        K=K, group_size=group_size, m_active=m_active,
    )
    return y.astype(x.dtype)  # fp32 accumulate, caller dtype out
