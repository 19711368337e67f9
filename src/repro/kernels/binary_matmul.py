"""Pallas TPU kernel: multi-level binary matmul (the BinArray SA, re-thought).

The FPGA systolic array computes, per output channel d and level m,
    p_{d,m} = sum_i b_{i,m} * x_i            (PE: sign-change + accumulate)
    o_d     = sum_m alpha_{d,m} * p_{d,m}    (PA: one DSP, time-multiplexed)

On TPU the MXU *is* the systolic array.  What we keep from the paper is the
storage format — M× 1-bit weights + per-(level, group) scales — and the
computation order: per K-tile, unpack the packed bits to ±1 in VMEM, run one
MXU matmul per level, and apply the alpha scaling as a VPU epilogue while
accumulating in fp32 (the MULW=28 accumulator analogue, strictly wider).

Packed weight layout (``B_packed``, produced by ``core.binarize.pack_bits``)
----------------------------------------------------------------------------
``B_packed[m, k8, n]`` is a uint8 holding reduction rows ``8*k8 .. 8*k8+7``
of level m's ±1 matrix for output channel n, **LSB-first**:

    bit j of B_packed[m, k8, n]  ==  1  iff  B_m[8*k8 + j, n] == +1
    (so +1 -> bit 1, -1 -> bit 0;  row index = 8*k8 + j, j = 0..7)

K is padded up to a byte boundary *upstream* (``core.binarize.pack``/
``binlinear.binarize_params`` append +1 rows); the padding rows are
harmless because the matching x columns are zero.  Scales live separately
as ``alpha[M, G, N]`` fp32 with ``G = K / group_size`` groups along the
reduction axis (G == 1 is the paper's per-output-channel scheme).

VMEM blocking (BlockSpec, all multiples of MXU-friendly sizes)
--------------------------------------------------------------
    x        [T, K]            -> blocks [BT, BK]
    B_packed [M, K/8, N] uint8 -> blocks [m_active, BK/8, BN]
    alpha    [M, G, N]         -> blocks [m_active, BN]  (group axis squeezed,
                                  G = K/group_size)
    out      [T, N] f32        -> blocks [BT, BN]

Grid: (T/BT, N/BN, K/BK) with the K dimension innermost ("arbitrary"
sequential), accumulating into the output block; alpha's group index is
derived from the K block index (requires group_size % BK == 0 or BK == K —
otherwise ops.py falls back to the single-K-block mode where the whole
padded K is one block and alpha is folded into the unpacked weights per
row).  ``tile_vmem_bytes_mm`` counts the VMEM footprint as Mosaic lays it
out: every block padded to (sublane, 128-lane) tiles and double-buffered by
the pipeline, plus the unpacked weight tile and the accumulator — about
0.9 MiB at BT=BN=128, BK=256, M=2, far inside the 16 MiB scoped limit
(``SCOPED_VMEM_LIMIT``) every call passes explicitly.

Bit unpacking runs in 32-bit integers (``unpack_signs``) and every dot runs
at full fp32 contraction precision (``dot_f32``); the conv and depth-wise
kernels share both helpers.

The per-level unpack costs BK/8 * BN uint8 VMEM loads per (BK x BN) tile —
1/16 the bytes of a bf16 weight tile, which is exactly the paper's
compression-factor win (Eq. 6) applied to the HBM->VMEM stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The scoped-VMEM limit the TPU compiler enforces on a kernel unless the call
# raises it (16 MiB on v5e).  Every pallas_call here passes its limit
# explicitly through ``vmem_limit_bytes``: this floor, raised to the tile
# plan's footprint plus ``VMEM_HEADROOM`` (room for Mosaic's own temporaries)
# when a plan needs more.
SCOPED_VMEM_LIMIT = 16 * 1024 * 1024
VMEM_HEADROOM = 4 * 1024 * 1024


def vmem_limit_bytes(footprint: int) -> int:
    """The scoped-VMEM limit a kernel call asks the compiler for."""
    return max(SCOPED_VMEM_LIMIT, footprint + VMEM_HEADROOM)


def vmem_tile_bytes(shape, itemsize: int = 4) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies under Mosaic's register
    tiling: the last dim pads to 128 lanes and the second-to-last to the
    dtype's sublane count (32 / itemsize: f32 8, uint8 32)."""
    shape = tuple(int(d) for d in shape)
    if len(shape) < 2:
        shape = (1,) * (2 - len(shape)) + shape
    *lead, sub, lane = shape
    n = 1
    for d in lead:
        n *= d
    sub_tile = 32 // itemsize
    return (n * -(-sub // sub_tile) * sub_tile * -(-lane // 128) * 128
            * itemsize)


def tile_vmem_bytes_mm(bt: int, bn: int, bk: int, *, m: int = 1) -> int:
    """Tiled VMEM footprint of one matmul program: the double-buffered x,
    packed-weight, alpha and output blocks, plus one level's unpacked ±1
    weight tile (int32 bits and fp32 signs) and the fp32 accumulator.
    Shared by the deploy compiler's LayerStats and repro.analysis."""
    blocks = (vmem_tile_bytes((bt, bk)) + m * vmem_tile_bytes((bk // 8, bn), 1)
              + vmem_tile_bytes((m, bn)) + vmem_tile_bytes((bt, bn)))
    return 2 * blocks + 2 * vmem_tile_bytes((bk, bn)) + vmem_tile_bytes((bt, bn))


def matmul_block_shapes(T: int, K: int, N: int, *, bt: int, bn: int, bk: int,
                        m: int = 1, G: int = 1,
                        group_size: int | None = None) -> tuple[dict, int]:
    """The exact BlockSpec geometry ``binary_matmul_pallas`` builds for a
    block plan, plus the *effective* bk (the kernel silently overrides bk to
    the whole padded K when grouped alpha boundaries cannot align with the
    K tiles).  Returns ``({operand: (block_shape, padded_array_shape,
    dtype)}, effective_bk)`` — consumed by ``repro.analysis``."""
    K8 = -(-K // 8)
    group_size = group_size or (K // max(G, 1))
    full_groups = G > 1 and group_size % bk != 0
    if full_groups:
        bk = K8 * 8
    K_pad = K8 * 8
    Kp = K_pad + (-K_pad) % bk
    Tp = T + (-T) % bt
    Np = N + (-N) % bn
    # the kernel's alpha view: the whole [m, G, BN] in single-K-block grouped
    # mode, else this K block's group row with the group axis squeezed
    alpha = (((m, G, bn), (m, G, Np)) if full_groups
             else ((m, bn), (m, Np)))
    blocks = {
        "x": ((bt, bk), (Tp, Kp), "float32"),
        "B_packed": ((m, bk // 8, bn), (m, Kp // 8, Np), "uint8"),
        "alpha": (*alpha, "float32"),
        "out": ((bt, bn), (Tp, Np), "float32"),
    }
    return blocks, bk


def unpack_signs(packed: jax.Array) -> jax.Array:
    """Kernel-side unpack: uint8 ``[..., k8, n]`` (LSB-first along the
    reduction axis) -> fp32 ±1 ``[..., 8*k8, n]``.

    The bit arithmetic runs in 32-bit integers: Mosaic builds no 8-bit iota,
    and the ``[k8, 8, n] -> [8*k8, n]`` merge is a tile-aligned shape cast
    only for 32-bit element types (8 sublanes per tile)."""
    *lead, k8, n = packed.shape
    p = packed.astype(jnp.int32)[..., :, None, :]
    shape = (*lead, k8, 8, n)
    shifts = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    bits = (jnp.broadcast_to(p, shape) >> shifts) & 1
    return (bits * 2 - 1).astype(jnp.float32).reshape(*lead, k8 * 8, n)


def dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at full fp32 contraction precision — on the MXU the default
    for fp32 operands may round them to bf16."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel(x_ref, bp_ref, alpha_ref, o_ref, *, m_active: int, n_k_blocks: int,
            full_groups_size: int = 0):
    """One (BT, BN) output tile; invoked n_k_blocks times along the K grid.

    ``full_groups_size > 0`` selects the single-K-block grouped-alpha mode:
    the whole (padded) K lives in one block and alpha arrives as [M, G, BN],
    applied per K row by folding it into the unpacked ±1 weights.  This is
    the legal path for group sizes that are not multiples of 8 (no packed
    K-tile boundary can align with the group boundaries then).  Otherwise
    alpha arrives as this K block's group row, ``[M, BN]``.
    """
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...].astype(jnp.float32)           # [BT, BK]
    acc = jnp.zeros(o_ref.shape, jnp.float32)     # [BT, BN]
    for m in range(m_active):                     # static unroll over levels
        bpm = unpack_signs(bp_ref[m])             # [BK, BN] ±1
        if full_groups_size:
            a = alpha_ref[m]                      # [G, BN]
            G, bn = a.shape
            a_exp = jnp.broadcast_to(
                a[:, None, :], (G, full_groups_size, bn)
            ).reshape(G * full_groups_size, bn)
            kp = bpm.shape[0]
            if kp > G * full_groups_size:         # 8-padding rows (x is zero)
                a_exp = jnp.pad(a_exp, ((0, kp - G * full_groups_size), (0, 0)))
            acc = acc + dot_f32(xb, bpm * a_exp)
        else:
            acc = acc + alpha_ref[m:m + 1, :] * dot_f32(xb, bpm)
    o_ref[...] = o_ref[...] + acc


@functools.partial(
    jax.jit,
    static_argnames=("K", "group_size", "m_active", "bt", "bn", "bk", "interpret"),
)
def binary_matmul_pallas(
    x: jax.Array,
    B_packed: jax.Array,
    alpha: jax.Array,
    *,
    K: int,
    group_size: int,
    m_active: int | None = None,
    bt: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """y[T, N] = sum_m alpha_m ⊙ (x @ B_m) over bit-packed B.  fp32 output.

    Pads T/N/K to block multiples; K-padding is safe because padded x columns
    are zero.  Grouped alpha (G > 1) wants ``group_size % bk == 0`` (group
    boundaries align with K tiles); when group_size is not a multiple of 8
    that is impossible and the kernel switches to a single-K-block mode that
    folds alpha into the unpacked weights per row.  The ops.py wrapper picks
    a legal bk automatically.
    """
    T, Kx = x.shape
    M, K8, N = B_packed.shape
    assert Kx == K, (Kx, K)
    m_active = min(m_active or M, M)  # can't apply more levels than packed
    G = alpha.shape[1]
    assert G * group_size == K, (G, group_size, K)
    # Grouped alpha needs K-tile boundaries aligned to group boundaries; when
    # that's impossible (group_size not a multiple of bk) the whole K must fit
    # in a single block and alpha is folded in per K row inside the kernel.
    full_groups = G > 1 and group_size % bk != 0
    if full_groups:
        bk = K8 * 8                      # single K block, multiple of 8

    K_pad = K8 * 8
    # pad x's K to K_pad (packed buffer is already padded)
    if K_pad != K:
        x = jnp.pad(x, ((0, 0), (0, K_pad - K)))
    # pad K_pad to a multiple of bk
    k_rem = (-K_pad) % bk
    if k_rem:
        x = jnp.pad(x, ((0, 0), (0, k_rem)))
        B_packed = jnp.pad(B_packed, ((0, 0), (0, k_rem // 8), (0, 0)))
    Kp = K_pad + k_rem
    t_rem = (-T) % bt
    if t_rem:
        x = jnp.pad(x, ((0, t_rem), (0, 0)))
    n_rem = (-N) % bn
    if n_rem:
        B_packed = jnp.pad(B_packed, ((0, 0), (0, 0), (0, n_rem)))
        alpha = jnp.pad(alpha, ((0, 0), (0, 0), (0, n_rem)))
    Tp, Np = T + t_rem, N + n_rem

    B_packed = B_packed[:m_active]
    alpha = alpha[:m_active].astype(jnp.float32)
    n_k_blocks = Kp // bk
    grid = (Tp // bt, Np // bn, n_k_blocks)

    if full_groups:
        alpha_spec = pl.BlockSpec((m_active, G, bn), lambda t, n, k: (0, 0, n))
    else:
        # the group axis is squeezed: K block k reads group (k * bk) //
        # group_size, and the kernel sees a lane-dense [m, BN] block
        alpha_spec = pl.BlockSpec(
            (m_active, None, bn),
            lambda t, n, k: (0, (k * bk) // group_size if G > 1 else 0, n))
    footprint = tile_vmem_bytes_mm(bt, bn, bk, m=m_active)
    out = pl.pallas_call(
        functools.partial(_kernel, m_active=m_active, n_k_blocks=n_k_blocks,
                          full_groups_size=group_size if full_groups else 0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk), lambda t, n, k: (t, k)),
            pl.BlockSpec((m_active, bk // 8, bn), lambda t, n, k: (0, k, n)),
            alpha_spec,
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda t, n, k: (t, n)),
        out_shape=jax.ShapeDtypeStruct((Tp, Np), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(footprint)),
        interpret=interpret,
    )(x, B_packed, alpha)
    return out[:T, :N]
