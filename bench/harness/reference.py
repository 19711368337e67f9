"""The plain float32 reference of a configuration, in straightforward JAX.

It imports nothing of the program under test and takes nothing it made: it
binarizes the seeded float weights itself, by the paper's Algorithm 2, and
runs the forward pass as plain convolutions and matrix products over the
reconstructed weights ``W_hat = sum_{m < m_active} alpha_m B_m`` (the fake
quantization the deployment approximates, paper Eq. 1 and section IV-D).

Algorithm 2 (arXiv:2012.03481, section II), per output channel of the
``[K, N]`` weight matrix (``K`` = kh * kw * C_in, the filter; N = C_out):

1. Greedy start (Algorithm 1): ``B_m = sign(R_m)`` with ``R_0 = W`` and
   ``R_{m+1} = R_m - mean|R_m| * B_m``; then the least-squares alphas.
2. Repeat up to ``K_iters`` times, until B stops changing: re-derive each
   ``B_m`` greedily from the residual under the current alphas, then solve
   the least-squares alphas again (paper Eq. 5).

The Gram matrix of the least-squares solve gets a ridge of ``1e-6`` times
its trace (at least ``1e-6``), so that two equal binary tensors leave it
solvable; sign(0) is +1.  Both follow the published description of the
repository's binarizer, and change the alphas by about 1e-6 of their size.

Two precisions can be named: ``binarize`` for the least-squares solves of
Algorithm 2, and ``forward`` for the convolutions and matrix products of
the forward pass.  ``"highest"`` is float32 (on a TPU, six bf16 passes),
what the configuration states.  The control is one step below it, XLA's
``Precision.HIGH``, three bf16 passes: ``"high"`` asks XLA for it, and
``"bf16x3"`` computes the same sum explicitly (each operand split into a
bf16 high and low part, the three larger cross products accumulated in
float32), so that it means the same on a backend that ignores ``HIGH``, as
the CPU does.

``tie_margins`` replays Algorithm 2 and reports, for every weight, how
near the closest of its sign decisions came to a tie.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _split(a):
    # reduce_precision keeps the rounding to bf16 that a convert to bf16 and
    # back would be simplified away by
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _contract(fn, a, b, precision: str):
    """``fn(a, b, precision=..., preferred_element_type=...)`` at the named
    precision, where ``fn`` is bilinear in its two operands."""
    if precision in ("highest", "high"):
        level = {"highest": HIGHEST, "high": jax.lax.Precision.HIGH}
        return fn(a, b, precision=level[precision],
                  preferred_element_type=jnp.float32)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)

    def part(x, y):
        return fn(x, y, precision=jax.lax.Precision.DEFAULT,
                  preferred_element_type=jnp.float32)

    return part(ah, bh) + (part(ah, bl) + part(al, bh))


def _einsum(spec):
    return lambda a, b, **kw: jnp.einsum(spec, a, b, **kw)


def _solve_alpha(W, B, precision):
    """Least-squares alphas [M, N] for binary tensors B [M, K, N]."""
    M = B.shape[0]
    Bf = B.astype(jnp.float32)
    gram = _contract(_einsum("mkn,lkn->nml"), Bf, Bf, precision)
    rhs = _contract(_einsum("mkn,kn->nm"), Bf, W, precision)
    ridge = 1e-6 * jnp.maximum(jnp.trace(gram, axis1=-2, axis2=-1), 1.0)
    gram = gram + jnp.eye(M, dtype=jnp.float32) * ridge[:, None, None]
    return jnp.linalg.solve(gram, rhs[..., None])[..., 0].T


def _note(R, scale, near):
    """Fold the sign decisions ``sign(R)`` into ``near``: per weight, the
    smallest |residual| / channel scale seen so far and the sign taken
    there."""
    margin, direction = near
    r = jnp.abs(R) / scale
    closer = r < margin
    return (jnp.where(closer, r, margin),
            jnp.where(closer, jnp.where(R >= 0, 1.0, -1.0), direction))


def _greedy_signs(W, alphas, scale, near):
    """B_m = sign of the residual left by levels < m, under ``alphas``."""
    R, signs = W, []
    for a in alphas:
        near = _note(R, scale, near)
        Bm = jnp.where(R >= 0, 1.0, -1.0)
        R = R - Bm * a[None, :]
        signs.append(Bm)
    return jnp.stack(signs), near


def binarize(W, M: int, K_iters: int, precision: str = "highest"):
    """Algorithm 2 on W [K, N], one group per output channel.

    Returns (B [M, K, N] of +-1 in float32, alpha [M, N], (margin,
    direction)).  ``margin`` [K, N] is, per weight, the smallest |residual|
    at which one of its sign decisions was taken, over its channel's mean
    |W|: how near it came to a tie.  ``direction`` is the sign taken there.
    """
    scale = jnp.maximum(jnp.mean(jnp.abs(W), axis=0), 1e-30)[None, :]
    near = (jnp.full(W.shape, jnp.inf, jnp.float32),
            jnp.ones(W.shape, jnp.float32))
    R, signs = W, []
    for _ in range(M):
        near = _note(R, scale, near)
        Bm = jnp.where(R >= 0, 1.0, -1.0)
        R = R - Bm * jnp.mean(jnp.abs(R), axis=0)[None, :]
        signs.append(Bm)
    B = jnp.stack(signs)
    alpha = _solve_alpha(W, B, precision)

    def cond(state):
        it, B, B_old, _, _ = state
        return jnp.logical_and(it < K_iters, jnp.any(B != B_old))

    def body(state):
        it, B, _, alpha, near = state
        B_new, near = _greedy_signs(W, alpha, scale, near)
        return it + 1, B_new, B, _solve_alpha(W, B_new, precision), near

    _, B, _, alpha, near = jax.lax.while_loop(
        cond, body, (jnp.int32(0), B, -B, alpha, near))
    return B, alpha, near


def general_position(W, M: int, K_iters: int, eps: float, rounds: int):
    """W [K, N] with every weight whose Algorithm 2 sign decision came
    within ``eps`` of a tie (``binarize``'s margin) pushed away from it, to
    a margin of ``2 * eps``, until none is left within ``eps`` or after
    ``rounds`` pushes.  Returns the weights and how many are still within
    ``eps``."""
    def near(w):
        margin, direction = binarize(w, M, K_iters)[2]
        return margin, direction, jnp.sum(margin < eps)

    def cond(state):
        i, _, _, _, left = state
        return jnp.logical_and(i < rounds, left > 0)

    def body(state):
        i, w, margin, direction, _ = state
        step = jnp.where(margin < eps, direction * (2 * eps - margin), 0.0)
        w = w + step * jnp.mean(jnp.abs(w), axis=0)[None, :]
        return (i + 1, w) + near(w)

    _, W, _, _, left = jax.lax.while_loop(
        cond, body, (jnp.int32(0), W) + near(W))
    return W, left


def reconstruct(W, config: dict, m_active: int, precision: str):
    """W_hat from the first ``m_active`` of the configuration's M levels."""
    if config.get("group_size") is not None or config["algorithm"] != 2:
        raise ValueError("the reference implements Algorithm 2 with one "
                         "group per output channel")
    B, alpha, _ = binarize(W, config["M"], config["K_iters"], precision)
    return jnp.sum(alpha[:m_active, None, :] * B[:m_active], axis=0)


def quantized_weights(params: dict, config: dict, m_active: int,
                      precision: str) -> dict:
    """Every layer's W_hat, in the layout its forward op takes."""
    out = {}
    for spec in config["layers"]:
        w = params[spec["name"]]["w"]
        if spec["kind"] == "linear":
            w_hat = reconstruct(w, config, m_active, precision)
        else:
            kh, kw, cin, cout = w.shape
            w_hat = reconstruct(w.reshape(kh * kw * cin, cout), config,
                                m_active, precision).reshape(w.shape)
        out[spec["name"]] = {"w": w_hat, "b": params[spec["name"]]["b"]}
    return out


def _conv(x, w, stride, padding, groups, precision):
    def fn(a, b, **kw):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, **kw)

    return _contract(fn, x, w, precision)


def forward(qparams: dict, x, config: dict, precision: str):
    """Logits of images x [B, H, W, C] through the reconstructed weights."""
    y = x
    for spec in config["layers"]:
        p = qparams[spec["name"]]
        pre = spec.get("pre", "none")
        if pre == "flatten":
            y = y.reshape(y.shape[0], -1)
        elif pre == "gap":
            y = jnp.mean(y, axis=(1, 2))
        kind = spec["kind"]
        if kind == "linear":
            y = _contract(_einsum("bk,kn->bn"), y, p["w"], precision) + p["b"]
        else:
            groups = y.shape[-1] if kind == "dwconv" else 1
            y = _conv(y, p["w"], spec.get("stride", 1),
                      spec.get("padding", "VALID"), groups, precision)
            y = y + p["b"]
            pool = spec.get("pool", 1)
            if pool > 1:
                n, h, w, c = y.shape
                y = y.reshape(n, h // pool, pool, w // pool, pool, c)
                y = y.max(axis=(2, 4))
        if spec.get("relu", True):
            y = jnp.maximum(y, 0.0)
    return y


@functools.cache
def compiled(config_json: str, m_active: int, forward_precision: str,
             binarize_precision: str = "highest"):
    """(jitted W_hat maker, jitted forward) for one configuration; keyed by
    the configuration's JSON text so equal configurations share them."""
    config = json.loads(config_json)
    weights = jax.jit(lambda params: quantized_weights(
        params, config, m_active, binarize_precision))
    fwd = jax.jit(lambda q, x: forward(q, x, config, forward_precision))
    return weights, fwd


def logits(params: dict, images, config: dict, m_active: int,
           forward_precision: str = "highest",
           binarize_precision: str = "highest", block: int = 64):
    """Reference logits of host images [N, H, W, C], ``block`` at a time, as
    a host array."""
    weights, fwd = compiled(json.dumps(config, sort_keys=True), m_active,
                            forward_precision, binarize_precision)
    q = weights(params)
    out = []
    for i in range(0, len(images), block):
        xb = images[i:i + block]
        n = len(xb)
        if n < block:   # one compiled shape: pad the last block
            xb = np.concatenate([xb, np.zeros((block - n,) + xb.shape[1:],
                                              xb.dtype)])
        out.append(np.asarray(fwd(q, jnp.asarray(xb)))[:n])
    return np.concatenate(out)
