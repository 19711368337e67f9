"""Pallas TPU kernel: fused implicit-GEMM binary convolution (AGU+PA+AMU).

The im2col path (core/binconv.py) materializes a ``[B·U·V, kh·kw·C]`` patch
tensor in HBM — a kh·kw× blow-up of the activation stream — before the binary
matmul ever runs, forfeiting the memory-stream win the paper's compression
(Eq. 6) buys.  On the FPGA the AGU streams patches out of the feature buffer;
here the kernel does the same job in VMEM:

  1. AGU:  assemble the patch tile for one input row-slab directly from the
     input block, kh·kw ref loads per conv row, into a VMEM scratch — the
     im2col tensor never exists in HBM.
  2. PE/PA: unpack the bit-packed filters of all active levels to ±1, fold
     the per-(level, group) alpha in per K row, and run ONE level-concatenated
     MXU contraction ``[rows, m·K] @ [m·K, bd]`` (see below).
  3. AMU:  bias + 2D max-pool + ReLU epilogue (paper Eq. 13, pool then ReLU
     == ReLU then pool by commutativity) before the HBM write-back, so the
     output stream is already pooled (pool² fewer bytes).

``B_tap_packed`` weight layout (byte-aligned per spatial tap)
-------------------------------------------------------------
The flat ``B_packed [M, ceil(K/8), D]`` byte stream (K = kh·kw·C row-major
over (tap_i, tap_j, c)) crosses spatial-tap boundaries whenever C % 8 != 0,
which would force the kernel to do cross-byte bit arithmetic per tap.  The
conv kernel therefore consumes a per-tap repacking

    B_tap_packed [M, kh·kw, ceil(C/8), D]   uint8

where ``B_tap_packed[m, t, c8, d]`` holds input channels ``8*c8 .. 8*c8+7``
of filter d's level-m ±1 weights at spatial tap ``t = i*kw + j`` (row-major
over the kh×kw window), **LSB-first** like the matmul kernel: bit j == 1
iff the ±1 weight for channel ``8*c8 + j`` is +1.  Each tap's C-slice is
padded to its own byte boundary with +1 bits; the kernel slices the padded
channels off right after unpacking (``w[:, :C, :]``), so their value never
matters.  Overhead: at most 7 bits per (level, tap, filter).
``pack_taps`` builds the layout from ±1 tensors, ``repack_taps`` converts a
flat ``B_packed`` (one-time upgrade — see ``binconv.ensure_tap_packed``),
and ``binconv.binarize_conv_params`` emits it directly — the tests' jnp
oracle (kernels/ref.py) consumes the *flat* layout, which is what keeps the
two packings cross-checked.

Level-concatenated GEMM (one MXU contraction per program)
---------------------------------------------------------
The paper's Eq. 8 sum ``y = Σ_m alpha_m ⊙ (patches @ B_m)`` is linear in
the per-level products, so the kernel folds alpha into each level's ±1 tile
and stacks the levels along the contraction axis:

    W_cat [m·K, bd]   = concat_m (B_m ⊙ alpha_m)      (level-major rows)
    P_cat [rows, m·K] = concat_m patches              (m copies, VMEM-only)
    acc              = P_cat @ W_cat                  (one dot_general)

Each program issues a single big MXU contraction instead of m small ones:
the bit-unpack + alpha-fold runs once per program (not once per level-matmul
pipeline stage) and the MXU sees an m× longer reduction, which matters
exactly on the small late-layer feature maps where ``rows`` is short.
(The fully-collapsed alternative — pre-summing the alpha-folded levels into
one fp W_hat [K, bd] like the dw kernel's ``eff`` tap fold — would halve
the per-program MACs and drop the P_cat copy, but gives up the per-level
product structure of the paper's Eq. 8 inside the contraction; the
level-concat layout keeps each alpha_m·B_m product an explicit row block
of the GEMM while still amortizing the unpack.  ``tile_vmem_bytes``
charges the P_cat copy, so the (NB, BU) pick already accounts for it.)

VMEM blocking: (batch-tile, D-tile, U row-tile) grid with halo slabs
--------------------------------------------------------------------
Grid: ``(ceil(B/NB), D/BD, ceil(Uo/BU))`` where ``Uo = U // pool`` is the
pooled output height.  One program computes a ``NB × BU × Vo × BD`` pooled
output tile (``Vo = V // pool``; the V axis is never tiled — feature maps
are at most a few hundred columns wide, and the MXU wants the full row
dimension anyway).  D is tiled MXU-style (BD = 128 by default, shrunk for
small D).

**NB — batch tile.**  NB images are folded into the implicit-GEMM row
dimension: the patch tile becomes ``[NB·u_tile·V, K]`` so the MXU row dim
sees NB·u_tile·V rows instead of u_tile·V.  A 7×7 point-wise layer alone
feeds the 128-row MXU only 49 rows (38% row occupancy) and re-runs the
weight unpack for every one of the B·nt programs that share a weight tile;
folding NB images amortizes the unpack NB× and lets NB·49 approach a
multiple of 128 (NB=5 → 245/256 = 96% occupancy).  Ragged batches
(B % NB != 0) ride on zero-padded images sliced off after the call.

**BU — row tile.**  The input block for row-tile ``t`` is a **slab** of

    slab_rows = (BU·pool − 1)·stride + kh            rows, starting at
    row0      = t · BU·pool·stride                   (element offset)

so consecutive slabs overlap by the ``kh − stride`` halo rows the conv
window needs across the tile boundary.  Overlapping blocks cannot be
expressed in Pallas' default *Blocked* indexing (offsets are
``index·block_shape``), so the x spec gives every block dim as
``pl.Element`` (element indexing is all dims or none): the index map
returns element offsets directly, and the halo rows ride in via ``t·adv``
with ``adv = BU·pool·stride < slab_rows``.  The wrapper zero-pads the row
axis so every slab (including the ragged last tile when ``Uo % BU != 0``)
is fully in bounds; the zero rows only ever feed output rows that are
sliced off after the call.  It also folds every ``stride`` input columns
into the lane axis (:func:`fold_columns`), so each tap of a strided conv
is a contiguous ref load (Mosaic lowers no strided value slice).

alpha/bias/weights are broadcast along the batch-tile and row-tile grid
dims, x along the D grid dim; the row-tile dim is innermost so a weight
tile stays resident while the x slabs stream through it.  Per-program VMEM
(``tile_vmem_bytes`` computes the same quantities, each buffer padded to
(8, 128) tiles, the pipelined blocks counted twice for double buffering):

    x slab        NB·slab_rows·Wf·stride·C·4     (fp32 input rows + halo)
    weight tile   m·kh·kw·ceil(C/8)·BD           (bit-packed)
    P_cat         rows_pad·m·K·4                 (scratch: implicit im2col,
                                                  m level copies, rows
                                                  padded to 128)
    acc           rows_pad·BD·4                  (scratch: GEMM result)
    W_cat         unpack bits + signs, alpha-folded ±1 weights
    out tile      NB·BU·Vo·BD·4                  (pooled HBM write)

``pick_tile`` co-picks (NB, BU) from a VMEM budget (default
``DEFAULT_VMEM_BUDGET``: the compiler's 16 MiB default scoped-VMEM limit
less headroom for Mosaic's temporaries; every call still sets its limit
explicitly, ``binary_matmul.vmem_limit_bytes``): big early layers keep
NB=1 and row-tile BU down until the slab fits; small late layers keep
whole-image BU = Uo and pick the NB
minimizing the whole batch's padded MXU rows (ragged-batch zero images
charged) within the budget.  ``pick_bu`` is the BU-only special case (NB
fixed).  Whole-image per-image blocking is
recovered as NB=1, BU=Uo and remains bit-exact with every other tiling.
``benchmarks/kernel_bench.py`` prints the analytic per-tile VMEM bytes, HBM
bytes, and MXU row occupancy for the paper's layer shapes from these
quantities.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import binarize as bz
from repro.kernels import binary_matmul as bmk
from repro.kernels.binary_matmul import dot_f32, unpack_signs

# VMEM budget for auto-picked tiles: the compiler's default scoped-VMEM limit
# less the headroom kept for Mosaic's own temporaries.  ``tile_vmem_bytes``
# counts the tiled, double-buffered footprint, so a plan within this budget
# compiles without raising the limit.
DEFAULT_VMEM_BUDGET = bmk.SCOPED_VMEM_LIMIT - bmk.VMEM_HEADROOM

# MXU systolic-array row dimension: the GEMM row count a program feeds is
# padded to a multiple of this, so occupancy = rows / roundup(rows, 128).
MXU_ROWS = 128

# ---------------------------------------------------------------------------
# Plan-pick accounting: every tile/block auto-pick bumps this counter, so the
# deploy tier can *prove* that a compiled BinArrayProgram runs zero scheduling
# decisions inside the jitted execute trace (repro/deploy — plans are frozen
# at compile time).  The legacy per-call paths (binconv.conv2d_relu_pool etc.)
# still auto-pick on every trace, which is exactly what the counter exposes.
# ---------------------------------------------------------------------------

_plan_picks = [0]


def _note_plan_pick() -> None:
    _plan_picks[0] += 1


def plan_pick_count() -> int:
    """Process-wide count of tile/block auto-picks (any kernel)."""
    return _plan_picks[0]


def reset_plan_pick_count() -> None:
    _plan_picks[0] = 0


def pack_taps(B: jax.Array, kh: int, kw: int, C: int) -> jax.Array:
    """±1 int8 [M, kh*kw*C, D] -> per-tap packed [M, kh*kw, ceil(C/8), D].

    Each spatial tap's C-slice is padded to a byte boundary with +1 bits;
    the kernel slices them off after unpacking, so their value never matters.
    """
    M, K, D = B.shape
    B = B.reshape(M, kh * kw, C, D)
    c_pad = (-C) % 8
    if c_pad:
        B = jnp.concatenate(
            [B, jnp.ones((M, kh * kw, c_pad, D), jnp.int8)], axis=2)
    Cp = C + c_pad
    return bz.pack_bits(B.reshape(M * kh * kw, Cp, D)).reshape(
        M, kh * kw, Cp // 8, D)


def repack_taps(B_packed: jax.Array, kh: int, kw: int, C: int) -> jax.Array:
    """Flat [M, ceil(K/8), D] uint8 -> per-tap [M, kh*kw, ceil(C/8), D] uint8
    (K = kh*kw*C row-major over (tap_i, tap_j, c)).

    One-time weight-layout upgrade for packed trees that predate the fused
    kernel — convert the tree once at load time via
    ``binconv.ensure_tap_packed`` (``binarize_conv_params`` emits
    B_tap_packed directly); hitting this from a traced forward re-runs the
    repack every call and warns (core/binconv.py).
    """
    M, K8, D = B_packed.shape
    K = kh * kw * C
    B = bz.unpack_bits(B_packed, K8 * 8)[:, :K, :]       # [M, K, D] ±1
    return pack_taps(B, kh, kw, C)


# ---------------------------------------------------------------------------
# Tile sizing (VMEM budget -> (NB, BU)) and MXU-occupancy analytics
# ---------------------------------------------------------------------------

def folded_width(Wp: int, C: int, stride: int) -> tuple[int, int]:
    """``(Wf, Cf)``: the input's width and lane dims as the conv and dw
    kernels see them.  The wrappers fold every ``stride`` consecutive
    columns into the lane axis (a free row-major reshape after zero-padding
    the width to a multiple of ``stride``), so tap ``(i, j)`` of output
    column v is column ``v + j // stride`` at lanes ``(j % stride)·C`` and
    up: every tap is a contiguous load, never a strided one."""
    return -(-Wp // stride), stride * C


def fold_columns(x: jax.Array, stride: int) -> jax.Array:
    """[B, H, Wp, C] -> [B, H, Wf, stride·C] (see :func:`folded_width`)."""
    B, H, Wp, C = x.shape
    Wf, Cf = folded_width(Wp, C, stride)
    if Wf * stride != Wp:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Wf * stride - Wp), (0, 0)))
    return x.reshape(B, H, Wf, Cf)


def slab_rows(bu: int, kh: int, *, stride: int = 1, pool: int = 1) -> int:
    """Input rows one program needs for ``bu`` pooled output rows (halo incl.)."""
    return (bu * pool - 1) * stride + kh


def gemm_rows(nb: int, bu: int, V: int, *, pool: int = 1) -> int:
    """GEMM row count one program feeds the MXU: NB images × BU·pool conv
    rows × V conv columns."""
    return nb * bu * pool * V


def mxu_row_occupancy(rows: int) -> float:
    """Fraction of the MXU's padded row dimension carrying real work:
    rows / roundup(rows, MXU_ROWS)."""
    return rows / (-(-rows // MXU_ROWS) * MXU_ROWS)


def batch_padded_rows(B: int, nb: int, rows_img: int) -> int:
    """Total MXU rows a whole batch moves (zero-padding included): each of
    the ceil(B/nb) programs pads its nb·rows_img GEMM rows (the ragged last
    program's missing images ride as zero rows) up to a multiple of
    MXU_ROWS."""
    progs = -(-B // nb)
    return progs * (-(-nb * rows_img // MXU_ROWS) * MXU_ROWS)


def batch_row_utilization(B: int, nb: int, rows_img: int) -> float:
    """End-to-end fraction of the batch's padded MXU rows carrying real
    work: B·rows_img / batch_padded_rows — unlike the per-program
    ``mxu_row_occupancy`` this also charges the ragged-batch zero images."""
    return B * rows_img / batch_padded_rows(B, nb, rows_img)


def unpack_work_per_output(nb: int, bu: int, vo: int, K: int, *,
                           m: int = 1) -> float:
    """Weight-unpack element ops per pooled output element of one program.

    A program unpacks ``m·K·bd`` weight elements once and produces
    ``nb·bu·vo·bd`` pooled outputs, so folding NB images divides the
    per-output unpack work by NB — the amortization the batch tile buys.
    """
    return m * K / (nb * bu * max(vo, 1))


def tile_vmem_bytes(W: int, C: int, kh: int, kw: int, bd: int, *, bu: int,
                    pool: int = 1, stride: int = 1, m: int = 1,
                    nb: int = 1) -> int:
    """VMEM footprint of one fused-conv program for an ``nb``-image,
    ``bu``-pooled-row output tile, as Mosaic lays it out (see the module
    docstring's table): every buffer padded to (sublane, 128-lane) tiles,
    the pipelined blocks counted twice (double buffering).

    ``W`` is the *padded* input width (SAME resolved upstream).  The same
    numbers drive ``pick_tile``/``pick_bu``, the kernel's scoped-VMEM limit
    and benchmarks/kernel_bench.py.
    """
    tb = bmk.vmem_tile_bytes
    V = (W - kw) // stride + 1
    slab = slab_rows(bu, kh, stride=stride, pool=pool)
    c8 = -(-C // 8)
    T = kh * kw
    K = T * C
    rows = nb * bu * pool * V
    rows_pad = -(-rows // MXU_ROWS) * MXU_ROWS
    blocks = (tb((nb * slab, *folded_width(W, C, stride)))
              + tb((m * T, c8, bd), 1)
              + tb((m, 1, bd)) + tb((1, bd))
              + tb((nb * bu, max(V // pool, 1), bd)))
    scratch = tb((rows_pad, m * K)) + tb((rows_pad, bd))   # P_cat, acc
    # int32 bits + ±1 signs of the unpack, alpha-expanded and folded W_cat
    weights = 2 * tb((m * T * c8, 8, bd)) + 2 * tb((m * K, bd))
    # one P_cat row group being assembled, one pooled output row
    values = 2 * tb((8 * V, m * K)) + (pool + 1) * tb((V, bd))
    return 2 * blocks + scratch + weights + values


def tile_hbm_bytes(W: int, C: int, kh: int, kw: int, bd: int, *, bu: int,
                   pool: int = 1, stride: int = 1, m: int = 1, nb: int = 1,
                   H: int | None = None) -> tuple[int, int]:
    """Analytic HBM bytes one (batch-tile, D-tile, row-tile) program moves:
    ``(fused, im2col)`` for fp32 activations.

    fused: read the NB input row-slabs (halo included, clipped to the image
    height ``H`` when given) + the bit-packed per-tap weight tile, write the
    *pooled* output tile — the patch tensor lives only in VMEM.  im2col
    (core/binconv.py conv2d + relu_maxpool): additionally writes the tile's
    ``[nb·u·V, kh·kw·C]`` patch slice to HBM and reads it back for the
    matmul, then writes the unpooled conv output and re-reads it for
    pooling.  Shared by benchmarks/kernel_bench.py and the deploy compiler's
    per-layer stats so neither can drift from the BlockSpec reality.
    """
    V = (W - kw) // stride + 1
    u_tile = bu * pool
    slab = slab_rows(bu, kh, stride=stride, pool=pool)
    if H is not None:
        slab = min(slab, H)
    c8 = -(-C // 8)
    x_b = nb * slab * W * C * 4
    w_packed = m * kh * kw * c8 * bd
    out_pooled = nb * bu * (V // pool) * bd * 4
    out_unpooled = nb * u_tile * V * bd * 4
    patches = nb * u_tile * V * kh * kw * C * 4
    fused = x_b + w_packed + out_pooled
    im2col = x_b + 2 * patches + w_packed + out_unpooled * 2 + out_pooled
    return fused, im2col


def conv_block_shapes(Hp: int, Wp: int, C: int, D: int, kh: int, kw: int, *,
                      bd: int, bu: int, nb: int, pool: int = 1,
                      stride: int = 1, m: int = 1, group_size: int | None
                      = None, B: int | None = None) -> dict:
    """The exact BlockSpec geometry ``binary_conv2d_pallas`` builds for a
    (clamped) tile plan — exported so ``repro.analysis`` checks the real
    schedule instead of re-deriving its own.

    Returns ``{"blocks": {operand: (block_shape, padded_array_shape, dtype)},
    "grid": grid, "padded_rows": rows of the padded x, "slab": slab_rows,
    "adv": row advance per tile, "nt": row tiles}``.  ``Hp``/``Wp`` are the
    SAME-resolved input dims; ``B`` defaults to one batch tile.  Callers must
    pass the clamped plan (``bd <= max(8, D)``, ``bu <= Uo``, ``nb <= B``) —
    the same values the kernel would execute.
    """
    U = (Hp - kh) // stride + 1
    V = (Wp - kw) // stride + 1
    uo = max(U // pool, 1)
    T = kh * kw
    C8 = -(-C // 8)
    K = T * C
    G = K // (group_size or K)
    d_rem = (-D) % bd
    Dp = D + d_rem
    nt = -(-uo // bu)
    adv = bu * pool * stride
    slab = slab_rows(bu, kh, stride=stride, pool=pool)
    rows_needed = (nt - 1) * adv + slab
    row_pad = max(rows_needed - Hp, 0)
    b = B if B is not None else nb
    Bp = b + (-b) % nb
    Wf, Cf = folded_width(Wp, C, stride)
    blocks = {
        "x": ((nb, slab, Wf, Cf), (Bp, Hp + row_pad, Wf, Cf), "float32"),
        "B_tap_packed": ((m, T, C8, bd), (m, T, C8, Dp), "uint8"),
        "alpha": ((m, G, bd), (m, G, Dp), "float32"),
        "bias": ((1, bd), (1, Dp), "float32"),
        "out": ((nb, bu, V // pool, bd), (Bp, nt * bu, V // pool, Dp),
                "float32"),
    }
    return {"blocks": blocks, "grid": (Bp // nb, Dp // bd, nt),
            "padded_rows": Hp + row_pad, "slab": slab, "adv": adv, "nt": nt}


def pick_bu(H: int, W: int, C: int, kh: int, kw: int, bd: int,
            pool: int = 1, budget_bytes: int = DEFAULT_VMEM_BUDGET, *,
            stride: int = 1, m: int = 1, nb: int = 1) -> int:
    """Largest row-tile BU (pooled output rows per program) whose VMEM
    working set fits ``budget_bytes`` at a fixed batch tile ``nb``.

    ``H``/``W`` are the *padded* input dims.  Returns ``Uo = U // pool``
    (whole-image blocking) whenever the image fits the budget, else the
    largest fitting BU, with a floor of 1 (a single pooled row; if even
    that exceeds the budget the kernel still runs — the budget is a target,
    not a hard VMEM limit).
    """
    _note_plan_pick()
    U = (H - kh) // stride + 1
    uo = max(U // pool, 1)
    for bu in range(uo, 1, -1):
        if tile_vmem_bytes(W, C, kh, kw, bd, bu=bu, pool=pool, stride=stride,
                           m=m, nb=nb) <= budget_bytes:
            return bu
    return 1


def pick_tile(B: int, H: int, W: int, C: int, kh: int, kw: int, bd: int,
              pool: int = 1, budget_bytes: int = DEFAULT_VMEM_BUDGET, *,
              stride: int = 1, m: int = 1) -> tuple[int, int]:
    """Co-pick the (NB, BU) tile for the fused conv kernel.

    Two regimes, split by whether one whole image fits the budget:

      * big early layers (``pick_bu`` returns BU < Uo): the row slab already
        saturates the MXU row dim and VMEM is the binding constraint —
        keep NB=1 and row-tile.
      * small late layers (whole image fits): keep BU = Uo and pick the NB
        that minimizes the *whole batch's* padded MXU rows
        (``batch_padded_rows``: ceil(B/NB) programs, each rounded up to a
        multiple of MXU_ROWS — so ragged-batch zero images and per-program
        pad rows are both charged), tie-broken toward fewer programs (the
        weight unpack runs once per program).  A 7×7 point-wise map is 49
        rows/image (38% occupancy alone); at B=128 the pick lands on NB=13
        (637/640 rows = 99.5% per program), while a batch of exactly 16
        folds all 16 images into one 784-row program rather than leave a
        mostly-empty ragged program behind.

    Candidate NBs stop at the VMEM budget (or 64 images).  Every (NB, BU)
    produces bit-identical outputs — tiling is a throughput decision, never
    an accuracy one.
    """
    _note_plan_pick()
    U = (H - kh) // stride + 1
    V = (W - kw) // stride + 1
    uo = max(U // pool, 1)
    bu = pick_bu(H, W, C, kh, kw, bd, pool, budget_bytes, stride=stride, m=m)
    if bu < uo or B <= 1:
        return 1, bu
    rows1 = gemm_rows(1, uo, V, pool=pool)
    best_nb, best_key = 1, None
    for nb in range(1, min(B, 64) + 1):
        if nb > 1 and tile_vmem_bytes(W, C, kh, kw, bd, bu=uo, pool=pool,
                                      stride=stride, m=m,
                                      nb=nb) > budget_bytes:
            break
        key = (batch_padded_rows(B, nb, rows1), -(-B // nb))
        if best_key is None or key < best_key:
            best_nb, best_key = nb, key
    return best_nb, uo


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _kernel(x_ref, bp_ref, alpha_ref, bias_ref, o_ref, p_ref, acc_ref, *,
            kh: int, kw: int, C: int, stride: int, pool: int, nb: int,
            u_tile: int, V: int, group_size: int, m_active: int, relu: bool):
    """One (NB images, BD channels, BU rows) tile: patches + GEMM + epilogue.

    ``p_ref`` [rows_pad, m·K] and ``acc_ref`` [rows_pad, BD] are VMEM
    scratch: the level-concatenated patch matrix P_cat and the GEMM result,
    one row per (image, conv row, conv column) of the tile.
    """
    conv_rows = nb * u_tile
    rows = conv_rows * V
    K = kh * kw * C
    bd = o_ref.shape[-1]

    # --- AGU: implicit im2col, tap-major to match the K layout (i, j, c) ---
    def patch_rows(r0, count):
        """P_cat rows of conv rows r0 .. r0+count-1: per conv row, each tap
        is one contiguous load of the column-folded input slab."""
        blocks = []
        for k in range(count):
            n, u = (r0 + k) // u_tile, (r0 + k) % u_tile
            taps = [x_ref[n, u * stride + i, pl.ds(j // stride, V),
                          (j % stride) * C:(j % stride + 1) * C]
                    for i in range(kh) for j in range(kw)]
            blocks.append(jnp.concatenate(taps, axis=1))
        p = jnp.concatenate(blocks, axis=0).astype(jnp.float32)
        return jnp.concatenate([p] * m_active, axis=1)

    # P_cat stores start on an 8-row boundary, so conv rows go in groups of
    # g with g·V % 8 == 0; a ragged last group is stored at a static offset.
    g = 8 // math.gcd(V, 8)
    n_groups = conv_rows // g

    def fill(q, carry):
        p_ref[pl.ds(pl.multiple_of(q * g * V, 8), g * V), :] = patch_rows(
            q * g, g)
        return carry

    if n_groups:
        jax.lax.fori_loop(0, n_groups, fill, 0)
    if conv_rows % g:
        p_ref[n_groups * g * V:rows, :] = patch_rows(
            n_groups * g, conv_rows % g)
    if p_ref.shape[0] > rows:
        p_ref[rows:, :] = jnp.zeros((p_ref.shape[0] - rows, m_active * K),
                                    jnp.float32)

    # --- PA: unpack every active level at once, fold alpha per level-row ---
    G = K // group_size
    w = unpack_signs(bp_ref[...])                    # [m, kh·kw, c8·8, bd]
    w = w[:, :, :C, :].reshape(m_active, K, bd)
    a = alpha_ref[...]                               # [m, G, bd]
    a_exp = jnp.broadcast_to(
        a[:, :, None, :], (m_active, G, group_size, bd)).reshape(
        m_active, K, bd)
    w_cat = (w * a_exp).reshape(m_active * K, bd)    # level-major row blocks

    # One contraction per program, issued in fixed MXU-row-sized passes:
    # every pass is an identical-shape [MXU_ROWS, m·K] @ [m·K, bd] dot (zero
    # row padding on the ragged last pass), so each output row's reduction
    # order is invariant to the (NB, BU) tiling — the bit-exactness
    # guarantee — and matches how the MXU consumes the row dimension.  A
    # single [rows, m·K] dot would let the backend re-block the reduction
    # as a function of the row count, which differs across tilings.
    def one_pass(r, carry):
        r0 = pl.multiple_of(r * MXU_ROWS, MXU_ROWS)
        acc_ref[pl.ds(r0, MXU_ROWS), :] = dot_f32(
            p_ref[pl.ds(r0, MXU_ROWS), :], w_cat)
        return carry

    jax.lax.fori_loop(0, p_ref.shape[0] // MXU_ROWS, one_pass, 0)

    # --- AMU epilogue: bias + 2D max-pool + ReLU, then the only HBM write ---
    bu = u_tile // pool
    bias = bias_ref[...]                             # [1, bd]

    def epilogue(q, carry):
        n, uo = q // bu, q % bu
        y = None
        for pi in range(pool):                       # pool conv rows ...
            r = n * u_tile + uo * pool + pi
            a = acc_ref[pl.ds(r * V, V), :] + bias   # [V, bd]
            y = a if y is None else jnp.maximum(y, a)
        if pool > 1:                                 # ... and pool columns
            y = y.reshape(V // pool, pool, bd).max(axis=1)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[n, uo] = y
        return carry

    jax.lax.fori_loop(0, nb * bu, epilogue, 0)


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "pool", "group_size",
                     "m_active", "relu", "bd", "bu", "nb", "vmem_budget",
                     "interpret"),
)
def binary_conv2d_pallas(
    x: jax.Array,
    B_tap_packed: jax.Array,
    alpha: jax.Array,
    bias: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pool: int = 1,
    group_size: int,
    m_active: int | None = None,
    relu: bool = True,
    bd: int = 128,
    bu: int | None = None,
    nb: int | None = None,
    vmem_budget: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused binary conv + bias + 2D max-pool + ReLU.  fp32 output.

    x:            [B, Hp, Wp, C]  (already padded for SAME by the caller)
    B_tap_packed: [M, kh*kw, ceil(C/8), D] uint8  (see pack_taps)
    alpha:        [M, G, D] float  (G = kh*kw*C // group_size)
    bias:         [D] float
    returns       [B, U//pool, V//pool, D] float32 where
                  U = (Hp-kh)//stride + 1, V = (Wp-kw)//stride + 1.

    U and V must be divisible by ``pool`` (downsampling-only pooling, paper
    §III-B — binconv.relu_maxpool asserts the same).  ``nb`` fixes the batch
    tile (images folded into the GEMM row dim per program) and ``bu`` the
    row tile (pooled output rows per program); leaving both None co-picks
    them from ``vmem_budget`` (default ``DEFAULT_VMEM_BUDGET``) via
    :func:`pick_tile` —
    whole-image NB=1 blocking whenever that already saturates the MXU.
    Giving ``bu`` alone keeps per-image blocking (nb=1).  Every (nb, bu)
    tiling is bit-identical: each output element's concatenated m·K
    reduction is the same in every tiling.
    """
    B, Hp, Wp, C = x.shape
    M, T, C8, D = B_tap_packed.shape
    assert T == kh * kw, (T, kh, kw)
    assert C8 * 8 >= C, (C8, C)
    m_active = min(m_active or M, M)  # can't apply more levels than packed
    U = (Hp - kh) // stride + 1
    V = (Wp - kw) // stride + 1
    assert U % pool == 0 and V % pool == 0, (U, V, pool)
    G = alpha.shape[1]
    assert G * group_size == kh * kw * C, (G, group_size, kh, kw, C)

    bd = min(bd, max(8, D))
    d_rem = (-D) % bd
    if d_rem:  # zero alpha/bias in the pad: padded channels contribute zeros
        B_tap_packed = jnp.pad(B_tap_packed, ((0, 0), (0, 0), (0, 0), (0, d_rem)))
        alpha = jnp.pad(alpha, ((0, 0), (0, 0), (0, d_rem)))
        bias = jnp.pad(bias, ((0, d_rem),))
    Dp = D + d_rem

    # --- joint (NB, BU) tiling: batch fold + halo row slabs ---
    uo = U // pool
    budget = vmem_budget or DEFAULT_VMEM_BUDGET
    if nb is None and bu is None:
        nb, bu = pick_tile(B, Hp, Wp, C, kh, kw, bd, pool, budget,
                           stride=stride, m=m_active)
    elif nb is None:
        nb = 1  # explicit BU: per-image row tiling (the pre-batch semantics)
    elif bu is None:
        bu = pick_bu(Hp, Wp, C, kh, kw, bd, pool, budget,
                     stride=stride, m=m_active, nb=max(1, min(nb, B)))
    nb = max(1, min(nb, B))
    bu = max(1, min(bu, uo))
    nt = -(-uo // bu)                       # row tiles (last may be ragged)
    adv = bu * pool * stride                # slab start advance per tile
    slab = slab_rows(bu, kh, stride=stride, pool=pool)
    rows_needed = (nt - 1) * adv + slab     # last slab's end, incl. halo
    b_rem = (-B) % nb                       # ragged batch: zero images,
    row_pad = max(rows_needed - Hp, 0)      # ragged rows: zero rows — both
    if b_rem or row_pad:                    # sliced off after the call
        x = jnp.pad(x, ((0, b_rem), (0, row_pad), (0, 0), (0, 0)))
    x = fold_columns(x, stride)
    Bp = B + b_rem
    u_tile = bu * pool

    B_tap_packed = B_tap_packed[:m_active]
    alpha = alpha[:m_active].astype(jnp.float32)
    bias2 = bias.astype(jnp.float32).reshape(1, Dp)

    # row-tile dim innermost: the weight tile stays resident per D-tile
    # while the x slabs stream through it.
    grid = (Bp // nb, Dp // bd, nt)
    rows_pad = -(-(nb * u_tile * V) // MXU_ROWS) * MXU_ROWS
    footprint = tile_vmem_bytes(Wp, C, kh, kw, bd, bu=bu, pool=pool,
                                stride=stride, m=m_active, nb=nb)
    out = pl.pallas_call(
        functools.partial(
            _kernel, kh=kh, kw=kw, C=C, stride=stride, pool=pool, nb=nb,
            u_tile=u_tile, V=V, group_size=group_size, m_active=m_active,
            relu=relu),
        grid=grid,
        in_specs=[
            # overlapping halo slabs need element offsets -> pl.Element
            # (all dims or none, so the batch offset is in elements too)
            pl.BlockSpec(tuple(map(pl.Element, (nb, slab, *x.shape[2:]))),
                         lambda b, d, t: (b * nb, t * adv, 0, 0)),
            pl.BlockSpec((m_active, T, C8, bd), lambda b, d, t: (0, 0, 0, d)),
            pl.BlockSpec((m_active, G, bd), lambda b, d, t: (0, 0, d)),
            pl.BlockSpec((1, bd), lambda b, d, t: (0, d)),
        ],
        out_specs=pl.BlockSpec((nb, bu, V // pool, bd),
                               lambda b, d, t: (b, t, 0, d)),
        out_shape=jax.ShapeDtypeStruct((Bp, nt * bu, V // pool, Dp),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rows_pad, m_active * T * C), jnp.float32),
            pltpu.VMEM((rows_pad, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=bmk.vmem_limit_bytes(footprint)),
        interpret=interpret,
    )(x, B_tap_packed, alpha, bias2)
    return out[:B, :uo, :, :D]
