"""BinArrayProgram: the compiled deployment form of a binary-approximated CNN.

The paper's BinArray is an *instruction-set processor* (§IV): an offline
compiler turns each network layer into one macro-instruction — weights,
addresses, and the whole schedule decided ahead of time — and the accelerator
merely executes the stream.  This module is that instruction set for the
Pallas port:

    ============  ====================================  =====================
    instruction   paper §IV macro-instruction           kernel it drives
    ============  ====================================  =====================
    ConvInstr     CONV (AGU patch walk + PA levels +    kernels/binary_conv
                  AMU bias/pool/ReLU)
    DWConvInstr   CONV, channel-wise D_arch=1 (§V-A3)   kernels/binary_dwconv
    LinearInstr   FC (PE accumulate over N_in)          kernels/binary_matmul
    ============  ====================================  =====================

Each instruction carries its packed weights (array leaves), the *frozen* tile
plan the compiler picked — ``(NB, BU, bd)`` for convs, ``(bt, bn, bk)`` for
matmuls — and the static per-layer facts (VMEM/HBM byte estimates, MAC
counts, MXU row occupancy) as :class:`LayerStats`.  Pre-layer epilogue fields
(``pre``: flatten / global-average-pool) and post-layer AMU fields (``pool``,
``relu``) make the instruction list a complete forward description: the
executor (deploy/executor.py) is a dumb loop.

Instructions are registered as JAX pytrees with the static fields as aux
data, so a whole :class:`BinArrayProgram` can be passed straight through
``jax.jit`` (plans ride in the treedef, weights are leaves), through
``jax.eval_shape`` (abstract programs: real plans + stats, ShapeDtypeStruct
weights — what the benchmarks introspect), and through
``checkpoint/manager.py`` (serialization round-trip).
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A frozen kernel schedule.  Convs use (nb, bu, bd) on the tile path
    and (nb, bu = Uo) on the row path (``path``); matmuls use (bt, bn, bk);
    the depth-wise kernel uses (nb, bu).  Unused fields stay None.  Every
    field is static — the plan lives in the pytree aux data, so two
    programs with different plans compile to different executables."""

    nb: int | None = None   # conv/dw: images folded per program
    bu: int | None = None   # conv/dw: pooled output rows per program
    bd: int | None = None   # conv tile path: output-channel (MXU lane) tile
    bt: int | None = None   # matmul: row block
    bn: int | None = None   # matmul: output-column block
    bk: int | None = None   # matmul: reduction block
    path: str | None = None  # conv: "tile" or "row" (pick_conv_path)


@dataclasses.dataclass(frozen=True)
class LayerStats:
    """Static per-layer facts the compiler derives once (paper §IV-E inputs).

    All plain ints/floats/tuples — hashable (pytree aux data) and trivially
    JSON-able (``BinArrayProgram.layer_stats``)."""

    in_shape: tuple[int, ...]       # activation entering the layer (post-pre)
    out_shape: tuple[int, ...]      # activation leaving it (post-pool/relu)
    padded_in: tuple[int, ...] = () # (Hp, Wp) after SAME resolution, convs
    macs: int = 0                   # fp-equivalent multiply-accumulates
    weight_bytes: int = 0           # packed deployment weight stream (HBM)
    vmem_bytes: int = 0             # per-program working set under the plan
    hbm_fused_bytes: int = 0        # per-program HBM traffic, fused kernel
    hbm_im2col_bytes: int = 0       # same tile via the explicit-im2col path
    mxu_row_occupancy: float = 1.0  # GEMM rows / padded MXU rows (convs)
    batch_row_utilization: float = 1.0  # whole-batch row utilization

    def device_view(self, *, n_model: int = 1, sharded: bool = False) -> dict:
        """Per-device byte split of this layer under a mesh: an output-
        channel (bd) shard divides the packed weight stream and its HBM
        traffic evenly over the ``n_model`` axis (channel slices are
        independent); a replicated layer carries the full copy on every
        device.  VMEM for sharded layers depends on the device-local tile
        plan, so ``repro.distributed.stats`` recomputes it from the kernel
        formula instead of splitting this estimate."""
        share = n_model if sharded else 1
        return {
            "per_device_weight_bytes": self.weight_bytes // share,
            "per_device_hbm_fused_bytes": self.hbm_fused_bytes // share,
        }


def _register(cls, array_fields: tuple[str, ...]) -> None:
    """Register a dataclass as a pytree: ``array_fields`` are children, every
    other field is aux data (static, hashable)."""
    static_fields = tuple(f.name for f in dataclasses.fields(cls)
                          if f.name not in array_fields)

    def flatten_with_keys(obj):
        children = [(jax.tree_util.GetAttrKey(f), getattr(obj, f))
                    for f in array_fields]
        aux = tuple(getattr(obj, f) for f in static_fields)
        return children, aux

    def flatten(obj):
        return tuple(getattr(obj, f) for f in array_fields), tuple(
            getattr(obj, f) for f in static_fields)

    def unflatten(aux, children):
        kw = dict(zip(array_fields, children))
        kw.update(zip(static_fields, aux))
        return cls(**kw)

    jax.tree_util.register_pytree_with_keys(
        cls, flatten_with_keys, unflatten, flatten_func=flatten)


@dataclasses.dataclass(frozen=True)
class ConvInstr:
    """Fused conv + bias + max-pool + ReLU (PE→PA→AMU, paper Eq. 8 + 13)."""

    # array leaves
    B_tap_packed: jax.Array   # [M, kh*kw, ceil(C/8), D] uint8 (pack_taps)
    alpha: jax.Array          # [M, G, D]
    bias: jax.Array           # [D] (zeros when the layer has none)
    # static
    name: str = ""
    kh: int = 1
    kw: int = 1
    stride: int = 1
    padding: str = "VALID"
    pool: int = 1
    relu: bool = True
    pre: str = "none"
    M: int = 1
    group_size: int = 1
    plan: TilePlan = TilePlan()
    stats: LayerStats = LayerStats((), ())

    kind = "conv"


@dataclasses.dataclass(frozen=True)
class DWConvInstr:
    """Fused channel-wise depth-wise conv + bias + ReLU (paper §V-A3)."""

    B_tap_packed: jax.Array   # [M, kh*kw, ceil(C/8)] uint8 (pack_dw_taps)
    alpha: jax.Array          # [M, C]
    bias: jax.Array           # [C]
    name: str = ""
    kh: int = 3
    kw: int = 3
    stride: int = 1
    relu: bool = True
    pre: str = "none"
    M: int = 1
    plan: TilePlan = TilePlan()
    stats: LayerStats = LayerStats((), ())

    kind = "dwconv"


@dataclasses.dataclass(frozen=True)
class LinearInstr:
    """Binary matmul + bias (+ ReLU) — the paper's FC macro-instruction."""

    B_packed: jax.Array       # [M, ceil(K/8), N] uint8 (flat packing)
    alpha: jax.Array          # [M, G, N]
    bias: jax.Array           # [N]
    name: str = ""
    K: int = 1                # logical reduction dim (pre-padding)
    relu: bool = False
    pre: str = "none"
    M: int = 1
    group_size: int = 1
    plan: TilePlan = TilePlan()
    stats: LayerStats = LayerStats((), ())

    kind = "linear"


Instr = ConvInstr | DWConvInstr | LinearInstr

_register(ConvInstr, ("B_tap_packed", "alpha", "bias"))
_register(DWConvInstr, ("B_tap_packed", "alpha", "bias"))
_register(LinearInstr, ("B_packed", "alpha", "bias"))


@dataclasses.dataclass(frozen=True)
class GoldenRecord:
    """Compile-time BIST reference: seeded input spec + output digests.

    ``deploy.compile`` runs a canonical probe input (batch 1, drawn from
    ``jax.random.normal(PRNGKey(seed), input_shape)``) through every §IV-D
    rung once and records the CRC32 of each output — the expected answers a
    deployed program must still produce.  ``deploy.self_test`` replays the
    probe and compares digests: the dynamic check that catches in-memory /
    packed-buffer corruption static verification cannot.

    Frozen + all-tuple, so it is hashable and rides in the pytree aux data
    (a golden change is a retrace, like any other static field), and
    trivially JSON-able for the checkpoint manifest.
    """

    seed: int
    input_shape: tuple[int, ...]                       # probe shape, batch 1
    digests: tuple[tuple[tuple[int, ...], str], ...]   # (schedule, crc32 hex)

    def schedules(self) -> tuple[tuple[int, ...], ...]:
        return tuple(s for s, _ in self.digests)

    def digest_for(self, schedule: tuple[int, ...]) -> str | None:
        for s, d in self.digests:
            if s == tuple(schedule):
                return d
        return None

    def to_json(self) -> dict:
        return {"seed": self.seed, "input_shape": list(self.input_shape),
                "digests": [[list(s), d] for s, d in self.digests]}

    @classmethod
    def from_json(cls, doc: dict) -> "GoldenRecord":
        return cls(seed=int(doc["seed"]),
                   input_shape=tuple(int(v) for v in doc["input_shape"]),
                   digests=tuple((tuple(int(m) for m in s), str(d))
                                 for s, d in doc["digests"]))


@dataclasses.dataclass(frozen=True)
class BinArrayProgram:
    """A compiled network: a macro-instruction stream plus program facts.

    ``input_shape`` is the (B, H, W, C) the tile plans were optimized for —
    executing other batch sizes stays *correct* (the kernels clamp and
    remain bit-exact across tilings), just not necessarily optimal.
    ``interpret`` records the compile-time default for the Pallas interpret
    flag (CPU validation); ``execute`` can override it.  ``golden`` is the
    compile-time :class:`GoldenRecord` (None for abstract programs and
    ``compile(..., golden=False)``).
    """

    instrs: tuple[Instr, ...]
    arch: str = ""
    input_shape: tuple[int, ...] = ()
    interpret: bool = False
    golden: GoldenRecord | None = None

    def __len__(self) -> int:
        return len(self.instrs)

    @property
    def m_max(self) -> int:
        return max(i.M for i in self.instrs)

    def resolve_schedule(self, m_active) -> tuple[int, ...]:
        """Normalize ``m_active`` into one static level count per
        instruction: None -> all packed levels; an int -> global, clamped to
        each instruction's M (§IV-D); a sequence -> per-layer schedule
        (length must match), each entry clamped to [1, M_layer]."""
        if m_active is None:
            return tuple(i.M for i in self.instrs)
        if isinstance(m_active, int):
            if m_active < 1:
                raise ValueError(f"m_active must be >= 1, got {m_active}")
            return tuple(min(m_active, i.M) for i in self.instrs)
        sched = tuple(int(m) for m in m_active)
        if len(sched) != len(self.instrs):
            raise ValueError(
                f"m_active schedule has {len(sched)} entries for "
                f"{len(self.instrs)} instructions "
                f"({[i.name for i in self.instrs]})")
        if any(m < 1 for m in sched):
            raise ValueError(f"schedule entries must be >= 1: {sched}")
        return tuple(min(m, i.M) for m, i in zip(sched, self.instrs))

    def layer_stats(self) -> list[dict]:
        """One JSON-able dict per instruction: geometry, frozen tile plan,
        VMEM/HBM byte estimates, MAC counts — the single source the
        benchmarks (kernel_bench, table3, run.py --json) read instead of
        hand-maintained layer lists."""
        out = []
        for idx, i in enumerate(self.instrs):
            d = {
                "index": idx, "name": i.name, "kind": i.kind,
                "pre": i.pre, "relu": bool(i.relu), "M": int(i.M),
                "in_shape": list(i.stats.in_shape),
                "out_shape": list(i.stats.out_shape),
                "macs": int(i.stats.macs),
                "weight_bytes": int(i.stats.weight_bytes),
                "vmem_bytes": int(i.stats.vmem_bytes),
                "plan": {k: v for k, v in dataclasses.asdict(i.plan).items()
                         if v is not None},
            }
            if i.kind in ("conv", "dwconv"):
                d.update(kh=i.kh, kw=i.kw, stride=i.stride,
                         padded_in=list(i.stats.padded_in))
            if i.kind == "conv":
                d.update(
                    path=i.plan.path, padding=i.padding, pool=i.pool,
                    group_size=int(i.group_size),
                    hbm_fused_bytes=int(i.stats.hbm_fused_bytes),
                    hbm_im2col_bytes=int(i.stats.hbm_im2col_bytes),
                    mxu_row_occupancy=float(i.stats.mxu_row_occupancy),
                    batch_row_utilization=float(
                        i.stats.batch_row_utilization))
            if i.kind == "linear":
                d.update(K=int(i.K), group_size=int(i.group_size))
            out.append(d)
        return out

    def conv_paths(self) -> dict[str, int]:
        """How many conv instructions run each fused-conv kernel path
        (``"tile"`` / ``"row"``, see ``binary_conv.pick_conv_path``)."""
        counts = {"tile": 0, "row": 0}
        for i in self.instrs:
            if i.kind == "conv":
                counts[i.plan.path] = counts.get(i.plan.path, 0) + 1
        return counts

    def totals(self) -> dict:
        """Whole-program roll-up of the per-layer stats."""
        return {
            "arch": self.arch,
            "input_shape": list(self.input_shape),
            "n_instructions": len(self.instrs),
            "macs": int(sum(i.stats.macs for i in self.instrs)),
            "weight_bytes": int(sum(i.stats.weight_bytes
                                    for i in self.instrs)),
            "max_vmem_bytes": int(max(i.stats.vmem_bytes
                                      for i in self.instrs)),
        }


jax.tree_util.register_pytree_with_keys(
    BinArrayProgram,
    lambda p: ([(jax.tree_util.GetAttrKey("instrs"), p.instrs)],
               (p.arch, p.input_shape, p.interpret, p.golden)),
    lambda aux, children: BinArrayProgram(
        instrs=tuple(children[0]), arch=aux[0], input_shape=aux[1],
        interpret=aux[2], golden=aux[3]),
    flatten_func=lambda p: ((p.instrs,),
                            (p.arch, p.input_shape, p.interpret, p.golden)),
)
