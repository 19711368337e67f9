"""Binary-approximated convolution — the paper's §III mapped to JAX.

A conv with binary-approximated filters is an im2col (patch extraction, the
AGU's job on the FPGA) followed by the binary dot product (the PA's job):

    O[b, u, v, d] = sum_m alpha_{m,d} * sum_{i} patch[b, u, v, i] * B_{m,i,d}

The fused ReLU+max-pool epilogue reproduces the AMU (paper Eq. 13).  The
dense (fp) path is the baseline the paper compares against.

Two execution strategies for the binary deployment path:

  * explicit im2col (``conv2d``): materializes the [B, U, V, kh*kw*C] patch
    tensor, then runs the binary matmul — simple, but the patch tensor is a
    kh·kw× HBM blow-up of the activation stream.
  * fused implicit GEMM (``conv2d_relu_pool`` with ``QuantConfig.fuse_conv``
    and ``use_pallas``): kernels/binary_conv.py extracts patches tile-by-tile
    in VMEM, runs the per-level bit-unpack + MXU matmul, and applies the AMU
    epilogue (bias + max-pool + ReLU) before write-back — the im2col tensor
    never exists in HBM and the output stream is already pooled.

``QuantConfig.m_active`` (paper §IV-D) selects how many of the packed levels
both paths apply at runtime — the serving-time accuracy↔throughput switch.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core import binarize as bz
from repro.core.binlinear import QuantConfig, DENSE


def same_pads(size: int, k: int, stride: int) -> tuple[int, int]:
    """XLA-convention SAME padding (lo, hi) for one spatial dim.

    out = ceil(size/stride); total = (out-1)*stride + k - size, split with the
    extra element on the *high* side — asymmetric for even kernels, matching
    ``jax.lax.conv_general_dilated(padding="SAME")`` (e.g. CNN-A's 4x4 conv2).
    """
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1,
           padding: str = "VALID") -> jax.Array:
    """x: [B, H, W, C] -> patches [B, U, V, kh*kw*C] (row-major, like the
    paper's feature-buffer layout)."""
    B, H, W, C = x.shape
    if padding == "SAME":
        x = jnp.pad(x, ((0, 0), same_pads(H, kh, stride),
                        same_pads(W, kw, stride), (0, 0)))
        H, W = x.shape[1], x.shape[2]
    U = (H - kh) // stride + 1
    V = (W - kw) // stride + 1
    patches = jnp.stack(
        [x[:, u0: u0 + H - kh + 1: stride, v0: v0 + W - kw + 1: stride, :]
         for u0 in range(kh) for v0 in range(kw)], axis=3,
    )  # [B, U, V, kh*kw, C]
    return patches.reshape(B, U, V, kh * kw * C)


def conv2d(params: dict, x: jax.Array, *, stride: int = 1,
           padding: str = "VALID", quant: QuantConfig = DENSE) -> jax.Array:
    """Conv via im2col + (binary|dense) matmul.  params['w']: [kh,kw,C,D]."""
    if quant.mode == "binary":
        kh, kw = params["kh"], params["kw"]
    else:
        kh, kw, C, D = params["w"].shape
    patches = im2col(x, kh, kw, stride, padding)
    B, U, V, K = patches.shape
    flat = patches.reshape(B * U * V, K)
    if quant.mode == "dense":
        y = flat @ params["w"].reshape(K, -1).astype(flat.dtype)
    elif quant.mode == "fake_quant":
        W = params["w"].reshape(K, -1).astype(jnp.float32)
        W_hat = bz.fake_quant(W, quant.M, algorithm=quant.algorithm,
                              K_iters=quant.K_iters, group_size=quant.group_size,
                              m_active=quant.m_active)
        y = flat @ W_hat.astype(flat.dtype)
    elif quant.mode == "binary":
        Kf = flat.shape[-1]
        gs = Kf // params["alpha"].shape[1]
        if quant.use_pallas:
            from repro.kernels import ops as kops

            y = kops.binary_matmul(flat, params["B_packed"], params["alpha"],
                                   K=Kf, group_size=gs,
                                   m_active=quant.m_active,
                                   interpret=quant.interpret)
        else:
            from repro.kernels import ref as kref

            y = kref.binary_matmul_ref(flat, params["B_packed"], params["alpha"],
                                       K=Kf, group_size=gs,
                                       m_active=quant.m_active)
    else:
        raise ValueError(quant.mode)
    D_out = y.shape[-1]
    y = y.reshape(B, U, V, D_out)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def binarize_conv_params(params: dict, quant: QuantConfig) -> dict:
    """Offline: fp conv filters -> packed binary form (per-filter alpha).

    Emits both packings: the flat ``B_packed [M, ceil(K/8), D]`` stream
    (im2col + matmul path) and the per-tap ``B_tap_packed
    [M, kh*kw, ceil(C/8), D]`` layout the fused conv kernel consumes (each
    spatial tap's C-slice byte-aligned; see kernels/binary_conv.py).
    """
    kh, kw, C, D = params["w"].shape
    K = kh * kw * C
    W = params["w"].reshape(K, D).astype(jnp.float32)
    approx, _ = bz.approximate_tensor(
        W, quant.M, algorithm=quant.algorithm, K_iters=quant.K_iters,
        group_size=quant.group_size)
    from repro.kernels import binary_conv as bck

    B = approx.B
    tap_packed = bck.pack_taps(B, kh, kw, C)
    pad = (-K) % 8
    if pad:
        B = jnp.concatenate([B, jnp.ones((quant.M, pad, D), jnp.int8)], axis=1)
    out = {"B_packed": bz.pack_bits(B), "B_tap_packed": tap_packed,
           "alpha": approx.alpha,
           "kh": kh, "kw": kw}  # kh/kw: static ints (example-path only)
    if "b" in params:
        out["b"] = params["b"]
    return out


def binarize_dwconv_params(params: dict, quant: QuantConfig) -> dict:
    """Offline: fp depth-wise filters -> packed binary form (channel-wise).

    params['w']: [kh, kw, 1, C] (HWIO depth-wise layout).  The paper (§V-A3)
    approximates depth-wise layers channel-wise with D_arch = 1: each channel
    is one "filter" of kh·kw taps, so the approximation runs on the
    [kh*kw, C] matrix with per-channel alpha.  Emits the channel-packed
    ``B_tap_packed [M, kh*kw, ceil(C/8)]`` layout the fused dw kernel
    consumes (kernels/binary_dwconv.py) plus ``alpha [M, C]``.
    """
    kh, kw, one, C = params["w"].shape
    assert one == 1, f"expected HWIO depth-wise filters [kh,kw,1,C], got {params['w'].shape}"
    W = params["w"].reshape(kh * kw, C).astype(jnp.float32)
    approx, _ = bz.approximate_tensor(
        W, quant.M, algorithm=quant.algorithm, K_iters=quant.K_iters,
        group_size=None)  # per-column == per-channel (G = 1)
    from repro.kernels import binary_dwconv as bdw

    out = {"B_tap_packed": bdw.pack_dw_taps(approx.B),
           "alpha": approx.alpha[:, 0, :],     # [M, 1, C] -> [M, C]
           "kh": kh, "kw": kw}
    if "b" in params:
        out["b"] = params["b"]
    return out


def ensure_tap_packed(params: dict, C: int) -> dict:
    """One-time weight-layout upgrade for legacy packed conv trees.

    Packed trees that predate the fused kernel carry only the flat
    ``B_packed`` stream; the fused kernel consumes the per-tap
    ``B_tap_packed`` layout.  Call this once at load time (``C`` is the
    layer's input channel count — it cannot be recovered from the packed
    bytes alone because each tap pads to a byte boundary); hitting the
    conversion inside a traced forward instead re-runs the repack every
    call and raises a ``DeprecationWarning`` each time (see
    :func:`conv2d_relu_pool`).  The deploy compiler (repro.deploy.compile)
    calls this on legacy trees so a compiled program always carries
    ``B_tap_packed``.
    """
    if "B_tap_packed" in params or "B_packed" not in params:
        return params
    from repro.kernels import binary_conv as bck

    out = dict(params)
    out["B_tap_packed"] = bck.repack_taps(
        params["B_packed"], params["kh"], params["kw"], C)
    return out


def relu_maxpool(x: jax.Array, pool: int) -> jax.Array:
    """AMU: max-pool (downsampling only, paper §III-B) then ReLU == fused."""
    B, H, W, C = x.shape
    assert H % pool == 0 and W % pool == 0, "downsampling only (paper §III-B)"
    y = x.reshape(B, H // pool, pool, W // pool, pool, C).max(axis=(2, 4))
    return jnp.maximum(y, 0.0)


def conv2d_relu_pool(params: dict, x: jax.Array, *, stride: int = 1,
                     padding: str = "VALID", pool: int = 1,
                     quant: QuantConfig = DENSE) -> jax.Array:
    """Conv + bias + max-pool + ReLU — the paper's full PE→PA→AMU pipeline.

    With packed-binary params and ``quant.fuse_conv`` + ``quant.use_pallas``,
    routes to the fused implicit-GEMM Pallas kernel (kernels/binary_conv.py):
    patches are extracted in VMEM, the AMU epilogue runs before write-back,
    and the [B·U·V, kh·kw·C] im2col tensor never exists in HBM.  Any other
    configuration (dense / fake-quant / unfused binary / pool not dividing
    the conv output) falls back to ``conv2d`` + ``relu_maxpool`` —
    numerically equivalent, just unfused.
    """
    binary = "B_packed" in params or "B_tap_packed" in params
    if binary and quant.fuse_conv and quant.use_pallas:
        kh, kw = params["kh"], params["kw"]
        B, H, W, C = x.shape
        if padding == "SAME":
            (pt, pb), (pl_, pr) = same_pads(H, kh, stride), same_pads(W, kw, stride)
            Hp, Wp = H + pt + pb, W + pl_ + pr
        else:
            Hp, Wp = H, W
        U = (Hp - kh) // stride + 1
        V = (Wp - kw) // stride + 1
        if U % pool == 0 and V % pool == 0:
            tap = params.get("B_tap_packed")
            if tap is None:  # packed trees from before the fused kernel landed
                warnings.warn(
                    "conv params carry only the flat B_packed layout; the "
                    "per-call repack_taps path is deprecated and re-runs the "
                    "repack inside the traced forward on EVERY call.  Convert "
                    "the tree once at load time with "
                    "binconv.ensure_tap_packed(params, C), or compile it into "
                    "a BinArrayProgram (repro.deploy.compile) — both emit "
                    "B_tap_packed directly.",
                    DeprecationWarning, stacklevel=2)
                from repro.kernels import binary_conv as bck

                tap = bck.repack_taps(params["B_packed"], kh, kw, C)
            D = params["alpha"].shape[-1]
            bias = params.get("b")
            if bias is None:
                bias = jnp.zeros((D,), jnp.float32)
            from repro.kernels import ops as kops

            y = kops.binary_conv2d(
                x, tap, params["alpha"], bias, kh=kh, kw=kw, stride=stride,
                padding=padding, pool=pool, m_active=quant.m_active,
                nb=quant.conv_batch_tile,
                vmem_budget=quant.conv_vmem_budget,
                interpret=quant.interpret)
            return y.astype(x.dtype)
    y = conv2d(params, x, stride=stride, padding=padding, quant=quant)
    return relu_maxpool(y, pool)


def _dwconv_fp(w: jax.Array, x: jax.Array, stride: int) -> jax.Array:
    """fp depth-wise conv, SAME padding.  w: [kh, kw, 1, C] (HWIO groups)."""
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1])


def depthwise_relu(params: dict, x: jax.Array, *, stride: int = 1,
                   quant: QuantConfig = DENSE) -> jax.Array:
    """Depth-wise conv + bias + ReLU — the paper's §V-A3 channel-wise stage.

    Path selection mirrors :func:`conv2d_relu_pool`:

      * packed-binary params ('B_tap_packed' [M, kh*kw, ceil(C/8)]) with
        ``quant.fuse_conv`` + ``quant.use_pallas``: the fused Pallas kernel
        (kernels/binary_dwconv.py) — the activations make one HBM round
        trip, the weights stream bit-packed, and **no fp ``lax.conv``
        runs** (the full-binary MobileNet requirement);
      * packed-binary params otherwise: the jnp oracle
        (kernels/ref.py binary_dwconv_relu_ref) — numerically the same
        reconstruction, HBM-bound;
      * fp params in ``fake_quant`` mode: STE-binarized W_hat (channel-wise,
        group_size = whole filter) through fp conv — the retraining path
        the packed deployment must match;
      * fp params otherwise: plain dense conv (the fp baseline).

    Depth-wise layers always use SAME padding (MobileNet's only variant).
    """
    if "B_tap_packed" in params:
        kh, kw = params["kh"], params["kw"]
        C = x.shape[-1]
        bias = params.get("b")
        if bias is None:
            bias = jnp.zeros((C,), jnp.float32)
        if quant.fuse_conv and quant.use_pallas:
            from repro.kernels import ops as kops

            y = kops.binary_dwconv2d(
                x, params["B_tap_packed"], params["alpha"], bias,
                kh=kh, kw=kw, stride=stride, padding="SAME",
                m_active=quant.m_active, nb=quant.conv_batch_tile,
                vmem_budget=quant.conv_vmem_budget,
                interpret=quant.interpret)
        else:
            from repro.kernels import ref as kref

            y = kref.binary_dwconv_relu_ref(
                x, params["B_tap_packed"], params["alpha"], kh=kh, kw=kw,
                stride=stride, padding="SAME", m_active=quant.m_active,
                bias=bias)
        return y.astype(x.dtype)
    w = params["w"]
    if quant.mode == "fake_quant":
        kh, kw, one, C = w.shape
        W_hat = bz.fake_quant(
            w.reshape(kh * kw, C).astype(jnp.float32), quant.M,
            algorithm=quant.algorithm, K_iters=quant.K_iters,
            group_size=None,  # channel-wise, like binarize_dwconv_params
            m_active=quant.m_active)
        w = W_hat.reshape(kh, kw, one, C).astype(x.dtype)
    y = _dwconv_fp(w, x, stride)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return jax.nn.relu(y)
