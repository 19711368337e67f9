"""Public jit'd wrappers around the Pallas kernels.

``binary_matmul`` flattens leading dims, picks legal block sizes for the
actual problem shape, and routes to the Pallas kernel (TPU, or interpret=True
for CPU validation).  ``binary_conv2d`` does the same for the fused
implicit-GEMM conv kernel (SAME padding resolved here, so the kernel only
ever sees pre-padded inputs).  The dry-run / pure-XLA path uses
kernels/ref.py instead (see repro.core.binlinear / repro.core.binconv).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import binary_conv as bck
from repro.kernels import binary_matmul as bmk


def _pick_block(dim: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that keeps padding sane."""
    bck._note_plan_pick()
    b = preferred
    while b > dim and b > 8:
        b //= 2
    return max(b, 8)


def pick_matmul_plan(T: int, K: int, N: int, *, G: int,
                     group_size: int) -> tuple[int, int, int]:
    """The (bt, bn, bk) block plan ``binary_matmul`` auto-picks for a
    ``[T, K] @ [K, N]`` binary matmul with G alpha groups.

    Exported so the deploy compiler (repro/deploy) can freeze the *same*
    blocks at compile time that the per-call path would pick — identical
    blocks mean an identical K-reduction order, which is what makes
    compiled-program execution bit-exact against the legacy path.
    """
    K8 = -(-K // 8)
    bt = _pick_block(T, 128)
    # Lane legality (analysis/mosaic_rules.py `mosaic-lane`): a block's last
    # dim must be a multiple of 128 lanes *or* cover the whole padded array
    # dim — Mosaic pads a lone sub-128-lane array transparently, but several
    # sub-128 blocks violate the register tiling.  So below 128 we take one
    # block over the whole 8-aligned padded dim instead of a smaller
    # power of two.  bn only re-blocks output columns (never the K reduction
    # order), so this stays bit-exact against any other bn.
    bn = _pick_block(N, 128)
    if bn < 128:
        bn = -(-N // 8) * 8
    # bk must divide group_size (or G == 1); cap at 256 for VMEM.  Sublane
    # legality (`mosaic-sublane`): the packed block's BK/8 uint8 rows must be
    # a multiple of 32, so a K tile is 256 rows or the whole padded K.
    if G == 1 or group_size % 256 == 0:
        bk = 256 if K8 * 8 > 256 else K8 * 8
    else:
        # no legal K tile aligns with the group boundaries, so take the
        # kernel's single-block grouped-alpha path (whole padded K in one
        # block, alpha folded in per row).
        bk = K8 * 8
    return bt, bn, bk


def binary_matmul(
    x: jax.Array,
    B_packed: jax.Array,
    alpha: jax.Array,
    *,
    K: int,
    group_size: int,
    m_active: int | None = None,
    interpret: bool = False,
    bt: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
) -> jax.Array:
    """y[..., N] = sum_m alpha_m ⊙ (x[..., K] @ B_m);  fp32 accumulate."""
    lead = x.shape[:-1]
    T = 1
    for d in lead:
        T *= d
    x2 = x.reshape(T, K)
    M, K8, N = B_packed.shape

    if bt is None or bn is None or bk is None:
        pbt, pbn, pbk = pick_matmul_plan(T, K, N, G=alpha.shape[1],
                                         group_size=group_size)
        bt, bn, bk = bt or pbt, bn or pbn, bk or pbk
    y = bmk.binary_matmul_pallas(
        x2, B_packed, alpha, K=K, group_size=group_size,
        m_active=m_active, bt=bt, bn=bn, bk=bk, interpret=interpret,
    )
    return y.reshape(*lead, N).astype(x.dtype) if x.dtype != jnp.float32 else y.reshape(*lead, N)


def binary_conv2d(
    x: jax.Array,
    B_tap_packed: jax.Array,
    alpha: jax.Array,
    bias: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "VALID",
    pool: int = 1,
    m_active: int | None = None,
    relu: bool = True,
    interpret: bool = False,
    bd: int | None = None,
    bu: int | None = None,
    nb: int | None = None,
    vmem_budget: int | None = None,
) -> jax.Array:
    """Fused binary conv + bias + max-pool + ReLU via the Pallas kernel.

    x: [B, H, W, C] -> [B, U//pool, V//pool, D] in fp32.  The im2col tensor
    never touches HBM (patch extraction runs in VMEM inside the kernel).
    ``nb``/``bu`` fix the batch tile (images folded into the GEMM row dim
    per program) and the output row tile; leaving both None co-picks them
    from the VMEM budget (kernels/binary_conv.py pick_tile) — NB grows on
    small late-layer maps until the MXU row dim saturates, big maps keep
    NB=1 and row-tile.
    """
    from repro.core.binconv import same_pads

    B, H, W, C = x.shape
    if padding == "SAME":
        x = jnp.pad(x, ((0, 0), same_pads(H, kh, stride),
                        same_pads(W, kw, stride), (0, 0)))
    elif padding != "VALID":
        raise ValueError(padding)
    K = kh * kw * C
    group_size = K // alpha.shape[1]
    D = alpha.shape[-1]
    return bck.binary_conv2d_pallas(
        x, B_tap_packed, alpha, bias,
        kh=kh, kw=kw, stride=stride, pool=pool, group_size=group_size,
        m_active=m_active, relu=relu, bd=bd or _pick_block(D, 128),
        bu=bu, nb=nb, vmem_budget=vmem_budget, interpret=interpret,
    )


def binary_dwconv2d(
    x: jax.Array,
    B_tap_packed: jax.Array,
    alpha: jax.Array,
    bias: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    m_active: int | None = None,
    relu: bool = True,
    interpret: bool = False,
    bu: int | None = None,
    nb: int | None = None,
    vmem_budget: int | None = None,
) -> jax.Array:
    """Fused binary depth-wise conv + bias + ReLU via the Pallas kernel.

    x: [B, H, W, C] -> [B, U, V, C] fp32 (paper §V-A3: depth-wise layers are
    approximated channel-wise; D_arch = 1).  SAME padding is resolved here
    like :func:`binary_conv2d`, so the kernel only sees pre-padded inputs;
    ``nb``/``bu`` tile the batch/row dims (None = pick_tile_dw co-pick).
    """
    from repro.core.binconv import same_pads
    from repro.kernels import binary_dwconv as bdw

    B, H, W, C = x.shape
    if padding == "SAME":
        x = jnp.pad(x, ((0, 0), same_pads(H, kh, stride),
                        same_pads(W, kw, stride), (0, 0)))
    elif padding != "VALID":
        raise ValueError(padding)
    return bdw.binary_dwconv2d_pallas(
        x, B_tap_packed, alpha, bias,
        kh=kh, kw=kw, stride=stride, m_active=m_active, relu=relu,
        bu=bu, nb=nb, vmem_budget=vmem_budget, interpret=interpret,
    )
