"""MeshPlan: map a compiled BinArrayProgram onto a device mesh.

The paper scales throughput by instantiating more Processing Arrays behind
one instruction stream (§IV) — the schedule is fixed offline, the arrays
replicate compute.  Our analog is JAX devices: a :class:`MeshPlan` is the
offline decision of *how* a :class:`~repro.deploy.program.BinArrayProgram`
spreads over a ``jax.sharding.Mesh``, frozen before any trace runs (same
compile-once contract as the tile plans):

  * **data parallelism** (the default, every layer): the global batch splits
    over the ``data`` axis, packed weights are replicated — the direct
    Processing-Array analog, bit-exact because the kernels clamp and stay
    bit-exact across any batch tiling (the PR-4 contract).
  * **output-channel (bd-dim) model parallelism** (opt-in per layer): big
    point-wise ``ConvInstr`` layers split their D output channels over the
    ``model`` axis; each device runs the conv on its channel slice with a
    device-local frozen :class:`~repro.deploy.program.TilePlan` (picked with
    the *same* exported ``pick_tile``/``_pick_block`` machinery the compiler
    uses), and an ``all_gather(tiled=True)`` concatenates the slices.
    Channel slices are computed independently — there is no fp reduction —
    so the gathered output is bitwise equal to the unsharded layer.

``plan_mesh`` is the planner; the per-layer decisions live in
:class:`LayerShard` records (one per instruction, hashable, auditable by
``analysis.verify_mesh_plan``).  Everything here is static: no devices are
touched until :func:`MeshPlan.build_mesh` / ``distributed.execute_sharded``.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType

from repro.deploy.program import BinArrayProgram, ConvInstr, TilePlan
from repro.kernels import binary_conv as bck
from repro.kernels import ops as kops

DATA_AXIS = "data"
MODEL_AXIS = "model"

# Point-wise layers below this packed-weight size are not worth splitting:
# the all_gather latency outweighs the VMEM/byte relief (the planner also
# splits any layer whose working set exceeds the VMEM budget, regardless).
DEFAULT_MIN_SHARD_BYTES = 16 * 1024


@dataclasses.dataclass(frozen=True)
class LayerShard:
    """One instruction's placement under the mesh.

    ``kind`` is ``"replicated"`` (weights on every device, the default) or
    ``"bd"`` (output channels split over the model axis).  For ``bd``
    shards, ``d_local`` is the per-device channel count, ``plan`` the
    device-local tile plan (frozen — the sharded trace must pick nothing),
    and ``per_device_weight_bytes`` the accounting the verifier re-derives.
    """

    kind: str = "replicated"            # replicated | bd
    d_local: int = 0                    # per-device output channels (bd)
    plan: TilePlan | None = None        # device-local frozen plan (bd)
    per_device_weight_bytes: int = 0    # packed weight bytes on one device


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A frozen program→mesh mapping: axis sizes + one LayerShard per
    instruction.  Hashable (jit-cache key) and device-free until
    :meth:`build_mesh`."""

    n_data: int
    n_model: int = 1
    shards: tuple[LayerShard, ...] = ()
    global_batch: int = 0               # the batch the plan was picked for
    axis_data: str = DATA_AXIS
    axis_model: str = MODEL_AXIS

    @property
    def devices(self) -> int:
        """Devices one forward occupies (the paper's Processing Array count)."""
        return self.n_data * self.n_model

    @property
    def local_batch(self) -> int:
        """Per-device batch after the ragged pad (ceil division)."""
        return -(-max(self.global_batch, 1) // self.n_data)

    def build_mesh(self) -> jax.sharding.Mesh:
        """Materialize the (n_data, n_model) device mesh.  Raises if the
        process has fewer than ``devices`` JAX devices.  The axes are
        ``Auto``: the executor places data itself through ``shard_map``, and
        slicing a ragged batch off a sharded output needs no stated output
        sharding then."""
        return jax.make_mesh((self.n_data, self.n_model),
                             (self.axis_data, self.axis_model),
                             axis_types=(AxisType.Auto, AxisType.Auto))

    def describe(self) -> list[str]:
        """One human line per shard (tools/verify_program.py --mesh)."""
        out = [f"mesh {self.n_data}x{self.n_model} "
               f"({self.axis_data},{self.axis_model}), "
               f"global_batch={self.global_batch}"]
        for i, s in enumerate(self.shards):
            if s.kind == "bd":
                out.append(f"  [{i}] bd-sharded: d_local={s.d_local}, "
                           f"plan=(nb={s.plan.nb}, bu={s.plan.bu}, "
                           f"bd={s.plan.bd}), "
                           f"{s.per_device_weight_bytes} B/device")
            else:
                out.append(f"  [{i}] replicated "
                           f"({s.per_device_weight_bytes} B/device)")
        return out


def _shardable(instr, n_model: int, *, pointwise_only: bool) -> bool:
    """Structural preconditions for bd-sharding one instruction: ConvInstr,
    point-wise (unless overridden), D divisible into >= 8-channel byte-even
    slices (so the per-device lane dim stays Mosaic-padddable)."""
    if n_model < 2 or not isinstance(instr, ConvInstr):
        return False
    if pointwise_only and not (instr.kh == 1 and instr.kw == 1):
        return False
    D = int(instr.alpha.shape[-1])
    if D % n_model:
        return False
    d_local = D // n_model
    return d_local >= 8 and d_local % 8 == 0


def plan_mesh(program: BinArrayProgram, *, n_data: int, n_model: int = 1,
              global_batch: int | None = None,
              vmem_budget: int | None = None,
              min_shard_bytes: int = DEFAULT_MIN_SHARD_BYTES,
              pointwise_only: bool = True) -> MeshPlan:
    """Plan a program onto an ``n_data`` x ``n_model`` mesh.

    Every layer is data-parallel with replicated weights by default; a
    ``ConvInstr`` is bd-sharded over the model axis when it is structurally
    shardable (:func:`_shardable`) **and** the split is justified — its
    packed weights reach ``min_shard_bytes`` or its working set exceeds the
    VMEM budget.  Device-local tile plans are co-picked with the same
    exported machinery the compiler freezes (``_pick_block`` for the local
    lane tile, ``pick_tile`` for (NB, BU) at the per-device batch), wrapped
    so planning never counts as a trace-time plan pick.

    ``global_batch`` defaults to the program's compiled batch; the plan is
    picked for ``ceil(global_batch / n_data)`` images per device but stays
    *correct* for any batch (kernels clamp, bit-exact).  Works on abstract
    programs too — only shapes and static aux data are read.
    """
    from repro.analysis.verify import _no_pick_accounting

    if n_data < 1 or n_model < 1:
        raise ValueError(f"mesh axes must be >= 1, got "
                         f"n_data={n_data}, n_model={n_model}")
    gb = int(global_batch if global_batch is not None
             else (program.input_shape[0] if program.input_shape else 1))
    if gb < 1:
        raise ValueError(f"global_batch must be >= 1, got {gb}")
    budget = vmem_budget or bck.DEFAULT_VMEM_BUDGET
    b_local = -(-gb // n_data)
    shards = []
    for instr in program.instrs:
        wb = int(instr.stats.weight_bytes)
        if not (_shardable(instr, n_model, pointwise_only=pointwise_only)
                and (wb >= min_shard_bytes
                     or instr.stats.vmem_bytes > budget)):
            shards.append(LayerShard(per_device_weight_bytes=wb))
            continue
        D = int(instr.alpha.shape[-1])
        d_local = D // n_model
        st = instr.stats
        Hp, Wp = (tuple(st.padded_in) if st.padded_in
                  else tuple(st.in_shape[1:3]))
        C = int(st.in_shape[-1])
        with _no_pick_accounting():
            bd_local = kops._pick_block(d_local, 128)
            nb_l, bu_l = bck.pick_tile(
                b_local, Hp, Wp, C, instr.kh, instr.kw, bd_local,
                instr.pool, budget, stride=instr.stride, m=instr.M)
        shards.append(LayerShard(
            kind="bd", d_local=d_local,
            plan=TilePlan(nb=nb_l, bu=bu_l, bd=bd_local),
            per_device_weight_bytes=wb // n_model))
    return MeshPlan(n_data=n_data, n_model=n_model, shards=tuple(shards),
                    global_batch=gb)
