"""Pallas TPU kernel: fused binary depth-wise convolution (paper §V-A3).

MobileNet's depth-wise 3×3 layers are memory-bound — each output channel
reads one input channel through a kh·kw window, so there is no reduction for
the MXU to amortize and the paper maps them to a *channel-wise* binary
approximation with D_arch = 1 (a single filter per PA).  Running them as fp
``lax.conv`` breaks the binary deployment story end to end: the activations
stream through HBM twice (conv out, then ReLU) and the weights stay fp32.
This kernel keeps the whole dw stage on-chip:

  1. unpack the bit-packed per-tap filters and fold the per-(level, channel)
     alpha into one *effective* tap weight per (tap, channel) in VMEM —
     the depth-wise conv is linear in the weights, so
     ``sum_m alpha[m,c]·B[m,t,c]`` collapses the level loop into the
     reconstruction W_hat the paper's Eq. 1 defines (HBM traffic stays the
     packed bits + alpha; m_active < M truncates the sum, §IV-D);
  2. accumulate the kh·kw taps channel-wise on the VPU, one output row at
     a time (no matmul — there is nothing to contract);
  3. bias + ReLU epilogue before the only HBM write-back.

``B_tap_packed`` weight layout (channel-wise, byte-aligned per tap)
-------------------------------------------------------------------
    B_tap_packed [M, kh·kw, ceil(C/8)]   uint8

``B_tap_packed[m, t, c8]`` holds channels ``8*c8 .. 8*c8+7`` of the level-m
±1 depth-wise weights at spatial tap ``t = i*kw + j``, LSB-first like the
conv kernel: bit j == 1 iff the weight for channel ``8*c8 + j`` is +1.
The C axis is padded to a byte boundary with +1 bits, sliced off after
unpacking.  ``pack_dw_taps`` builds the layout from ±1 tensors;
``binconv.binarize_dwconv_params`` emits it plus the channel-wise
``alpha [M, C]``.  The jnp oracle (kernels/ref.py binary_dwconv_relu_ref)
unpacks the same bytes and runs fp ``lax.conv`` on the reconstruction,
which is what keeps the packing and the kernel cross-checked.

VMEM blocking
-------------
Grid: ``(ceil(B/NB), ceil(U/BU))`` — joint (batch-tile, row-tile) blocking
like kernels/binary_conv.py; the channel axis stays whole (dw feature maps
are large exactly when C is small, and C·4 bytes per pixel is the whole
working set — there is no D blow-up).  NB images per program amortize the
per-program bit-unpack + alpha-fold NB× (there is no MXU row dimension to
fill here — the tap accumulation runs on the VPU — so the batch tile is
purely an unpack/dispatch amortization; ragged batches ride on zero-padded
images sliced off after the call).  Row tiles use the same halo-slab scheme
as the conv kernel: tile ``t`` reads the input rows
``[t·BU·stride, t·BU·stride + (BU-1)·stride + kh)`` via a ``pl.Element``
element-offset index map, with the wrapper zero-padding the row axis so
ragged last tiles stay in bounds and folding ``stride`` columns into the
lane axis so every tap is a contiguous load.  ``pick_tile_dw`` co-picks
(NB, BU) from the conv kernel's default budget (``DEFAULT_VMEM_BUDGET``,
counted as tiled, double-buffered VMEM): row-tiled maps keep NB=1,
whole-image maps grow NB until the budget binds (``pick_bu_dw`` is the
BU-only special case).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import binarize as bz
from repro.kernels import binary_matmul as bmk
from repro.kernels.binary_conv import (DEFAULT_VMEM_BUDGET, _note_plan_pick,
                                       fold_columns, folded_width, slab_rows)


def pack_dw_taps(B: jax.Array) -> jax.Array:
    """±1 int8 [M, kh*kw, C] -> channel-packed [M, kh*kw, ceil(C/8)] uint8.

    The C axis is padded to a byte boundary with +1 bits; the kernel and the
    oracle slice them off after unpacking, so their value never matters.
    """
    M, T, C = B.shape
    c_pad = (-C) % 8
    if c_pad:
        B = jnp.concatenate([B, jnp.ones((M, T, c_pad), jnp.int8)], axis=2)
    Cp = C + c_pad
    return bz.pack_bits(B.reshape(M * T, Cp, 1)).reshape(M, T, Cp // 8)


def unpack_dw_taps(packed: jax.Array, C: int) -> jax.Array:
    """uint8 [M, kh*kw, ceil(C/8)] -> ±1 int8 [M, kh*kw, C] (inverse)."""
    M, T, c8 = packed.shape
    B = bz.unpack_bits(packed.reshape(M * T, c8, 1), c8 * 8)
    return B.reshape(M, T, c8 * 8)[:, :, :C]


def tile_vmem_bytes_dw(W: int, C: int, kh: int, kw: int, *, bu: int,
                       stride: int = 1, m: int = 1, nb: int = 1) -> int:
    """VMEM footprint of one ``nb``-image, ``bu``-row dw program as Mosaic
    lays it out: (sublane, 128-lane) tiled buffers, the pipelined blocks
    counted twice (double buffering)."""
    tb = bmk.vmem_tile_bytes
    V = (W - kw) // stride + 1
    slab = slab_rows(bu, kh, stride=stride)
    c8 = -(-C // 8)
    T = kh * kw
    blocks = (tb((nb * slab, *folded_width(W, C, stride)))
              + tb((m, T, c8), 1) + tb((m, C))
              + tb((1, C)) + tb((nb * bu, V, C)))
    # int32 bits (8 lanes per byte before the merge), ±1 levels, folded taps
    weights = 2 * tb((m * T * c8, 8)) + tb((m * T, C)) + tb((T, C))
    values = 3 * tb((V, C))                 # one output row: tap, acc, out
    return 2 * blocks + weights + values


def dw_block_shapes(Hp: int, Wp: int, C: int, kh: int, kw: int, *,
                    bu: int, nb: int, stride: int = 1, m: int = 1,
                    B: int | None = None) -> dict:
    """The exact BlockSpec geometry ``binary_dwconv2d_pallas`` builds for a
    (clamped) tile plan — same contract as
    ``binary_conv.conv_block_shapes``, consumed by ``repro.analysis``."""
    U = (Hp - kh) // stride + 1
    V = (Wp - kw) // stride + 1
    T = kh * kw
    c8 = -(-C // 8)
    nt = -(-U // bu)
    adv = bu * stride
    slab = slab_rows(bu, kh, stride=stride)
    rows_needed = (nt - 1) * adv + slab
    row_pad = max(rows_needed - Hp, 0)
    b = B if B is not None else nb
    Bp = b + (-b) % nb
    Wf, Cf = folded_width(Wp, C, stride)
    blocks = {
        "x": ((nb, slab, Wf, Cf), (Bp, Hp + row_pad, Wf, Cf), "float32"),
        "B_tap_packed": ((m, T, c8), (m, T, c8), "uint8"),
        "alpha": ((m, C), (m, C), "float32"),
        "bias": ((1, C), (1, C), "float32"),
        "out": ((nb, bu, V, C), (Bp, nt * bu, V, C), "float32"),
    }
    return {"blocks": blocks, "grid": (Bp // nb, nt),
            "padded_rows": Hp + row_pad, "slab": slab, "adv": adv, "nt": nt}


def pick_bu_dw(H: int, W: int, C: int, kh: int, kw: int,
               budget_bytes: int = DEFAULT_VMEM_BUDGET, *,
               stride: int = 1, m: int = 1, nb: int = 1) -> int:
    """Largest dw row tile (output rows per program) fitting the budget at a
    fixed batch tile ``nb``."""
    _note_plan_pick()
    U = (H - kh) // stride + 1
    for bu in range(max(U, 1), 1, -1):
        if tile_vmem_bytes_dw(W, C, kh, kw, bu=bu, stride=stride,
                              m=m, nb=nb) <= budget_bytes:
            return bu
    return 1


def pick_tile_dw(B: int, H: int, W: int, C: int, kh: int, kw: int,
                 budget_bytes: int = DEFAULT_VMEM_BUDGET, *,
                 stride: int = 1, m: int = 1,
                 nb_cap: int = 8) -> tuple[int, int]:
    """Co-pick the (NB, BU) tile for the fused dw kernel.

    Row-tiled maps (whole image over budget) keep NB=1; whole-image maps
    grow NB while the working set fits the budget, capped at ``nb_cap``
    (the VPU has no 128-row dimension to fill — past a handful of images
    the unpack/dispatch amortization has flattened out).
    """
    _note_plan_pick()
    U = (H - kh) // stride + 1
    bu = pick_bu_dw(H, W, C, kh, kw, budget_bytes, stride=stride, m=m)
    if bu < max(U, 1) or B <= 1:
        return 1, bu
    nb = 1
    while nb < min(B, nb_cap) and tile_vmem_bytes_dw(
            W, C, kh, kw, bu=bu, stride=stride, m=m,
            nb=nb + 1) <= budget_bytes:
        nb += 1
    return nb, bu


def _dw_kernel(x_ref, bp_ref, alpha_ref, bias_ref, o_ref, *,
               kh: int, kw: int, C: int, stride: int, nb: int,
               u_tile: int, V: int, m_active: int, relu: bool):
    """One (NB images, BU rows) tile: fold levels, tap-accumulate, epilogue."""
    T, c8 = bp_ref.shape[1], bp_ref.shape[2]
    # fold the level sum into one effective fp tap weight per (tap, channel):
    # W_hat[t, c] = sum_{m < m_active} alpha[m, c] * B[m, t, c]  (Eq. 1)
    # Channels sit on lanes, so the unpack spreads each byte's 8 bits over 8
    # consecutive lanes; the bit arithmetic runs in 32-bit integers.
    shape = (m_active, T, c8, 8)
    shifts = jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    packed = jnp.broadcast_to(
        bp_ref[...].astype(jnp.int32)[:, :, :, None], shape)
    bits = (packed >> shifts) & 1
    w = (bits * 2 - 1).astype(jnp.float32).reshape(m_active, T, c8 * 8)
    w = w[:, :, :C]                                  # [m, T, C] ±1
    eff = jnp.sum(w * alpha_ref[...][:, None, :], axis=0)     # [T, C]
    # channel-wise tap accumulation on the VPU (no contraction to feed MXU),
    # one output row per step; each tap is one contiguous load of the
    # column-folded slab
    bias = bias_ref[...]                             # [1, C]

    def row(r, carry):
        n, u = r // u_tile, r % u_tile
        acc = jnp.zeros((V, C), jnp.float32)
        for i in range(kh):
            for j in range(kw):
                xs = x_ref[n, u * stride + i, pl.ds(j // stride, V),
                           (j % stride) * C:(j % stride + 1) * C]
                t = i * kw + j
                acc = acc + xs.astype(jnp.float32) * eff[t:t + 1, :]
        y = acc + bias
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[n, u] = y
        return carry

    jax.lax.fori_loop(0, nb * u_tile, row, 0)


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "m_active", "relu", "bu", "nb",
                     "vmem_budget", "interpret"),
)
def binary_dwconv2d_pallas(
    x: jax.Array,
    B_tap_packed: jax.Array,
    alpha: jax.Array,
    bias: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    m_active: int | None = None,
    relu: bool = True,
    bu: int | None = None,
    nb: int | None = None,
    vmem_budget: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused binary depth-wise conv + bias + ReLU.  fp32 output.

    x:            [B, Hp, Wp, C]  (already padded for SAME by the caller)
    B_tap_packed: [M, kh*kw, ceil(C/8)] uint8  (see pack_dw_taps)
    alpha:        [M, C] float   (channel-wise, paper §V-A3 / D_arch=1)
    bias:         [C] float
    returns       [B, U, V, C] float32, U = (Hp-kh)//stride + 1.

    ``nb``/``bu`` fix the batch/row tile; leaving both None co-picks them
    via :func:`pick_tile_dw` (giving ``bu`` alone keeps per-image blocking).
    Every (nb, bu) tiling is bit-identical.
    """
    B, Hp, Wp, C = x.shape
    M, T, c8 = B_tap_packed.shape
    assert T == kh * kw, (T, kh, kw)
    assert c8 * 8 >= C, (c8, C)
    assert alpha.shape == (M, C), (alpha.shape, M, C)
    m_active = min(m_active or M, M)
    U = (Hp - kh) // stride + 1
    V = (Wp - kw) // stride + 1

    budget = vmem_budget or DEFAULT_VMEM_BUDGET
    if nb is None and bu is None:
        nb, bu = pick_tile_dw(B, Hp, Wp, C, kh, kw, budget,
                              stride=stride, m=m_active)
    elif nb is None:
        nb = 1  # explicit BU: per-image row tiling (the pre-batch semantics)
    elif bu is None:
        bu = pick_bu_dw(Hp, Wp, C, kh, kw, budget, stride=stride,
                        m=m_active, nb=max(1, min(nb, B)))
    nb = max(1, min(nb, B))
    bu = max(1, min(bu, U))
    nt = -(-U // bu)
    adv = bu * stride
    slab = slab_rows(bu, kh, stride=stride)
    rows_needed = (nt - 1) * adv + slab
    b_rem = (-B) % nb                       # ragged batch / ragged last row
    row_pad = max(rows_needed - Hp, 0)      # tile: zero pad, sliced off below
    if b_rem or row_pad:
        x = jnp.pad(x, ((0, b_rem), (0, row_pad), (0, 0), (0, 0)))
    x = fold_columns(x, stride)
    Bp = B + b_rem

    bp = B_tap_packed[:m_active]
    alpha = alpha[:m_active].astype(jnp.float32)
    bias2 = bias.astype(jnp.float32).reshape(1, C)

    grid = (Bp // nb, nt)
    footprint = tile_vmem_bytes_dw(Wp, C, kh, kw, bu=bu, stride=stride,
                                   m=m_active, nb=nb)
    out = pl.pallas_call(
        functools.partial(
            _dw_kernel, kh=kh, kw=kw, C=C, stride=stride, nb=nb,
            u_tile=bu, V=V, m_active=m_active, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec(tuple(map(pl.Element, (nb, slab, *x.shape[2:]))),
                         lambda b, t: (b * nb, t * adv, 0, 0)),
            pl.BlockSpec((m_active, T, c8), lambda b, t: (0, 0, 0)),
            pl.BlockSpec((m_active, C), lambda b, t: (0, 0)),
            pl.BlockSpec((1, C), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nb, bu, V, C), lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, nt * bu, V, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=bmk.vmem_limit_bytes(footprint)),
        interpret=interpret,
        name="binary_dwconv2d_pallas",
    )(x, bp, alpha, bias2)
    return out[:B, :U]
