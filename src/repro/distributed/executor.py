"""execute_sharded(): run one BinArrayProgram forward across a device mesh.

The multi-device twin of ``deploy.execute``: a chain of jitted ``shard_map``
macro-instructions, bit-exact against the single-device path for every
§IV-D schedule because nothing numeric changes —

  * data parallelism splits the batch; the kernels clamp and stay bit-exact
    across batch tilings (the compile-once contract deploy relies on), so a
    device computing 1/n of the batch produces the same rows;
  * bd-sharded convs compute disjoint output-channel slices with no fp
    reduction; ``all_gather(tiled=True)`` concatenates them in channel
    order, bitwise equal to the unsharded conv;
  * replicated layers run ``deploy.executor._apply`` verbatim.

Execution granularity is one compiled module per (instruction, level,
shard) — the paper's accelerator likewise executes one macro-instruction
at a time (§IV ISA), and on the partitioned module this is what makes the
bit-exactness *provable*: fusing the whole chain into one ``shard_map``
lets XLA form fp contractions across layer boundaries whose choice depends
on the surrounding module, producing deterministic 1-ulp drift vs the
single-device executable (observed on CPU at small per-device batches even
with ``optimization_barrier`` pinning every boundary).  Per-instruction
modules compile each layer in the same isolation the golden path sees, so
every (nb, bu, bd) tiling stays bit-identical.  The per-layer functions are
cached on (mesh, shard, level, geometry), so layers sharing a schedule
share one executable and repeated forwards never retrace.

Ragged global batches are padded with zero images and sliced back exactly
like the kernels' NB path.  Scheduling stays frozen: every kernel call
passes a complete frozen plan (the instruction's own, or the LayerShard's
device-local one), so the sharded trace contains zero plan auto-picks —
``kernels.binary_conv.plan_pick_count`` proves it, same as deploy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.deploy import executor as dexec
from repro.deploy.program import BinArrayProgram, ConvInstr
from repro.distributed.plan import LayerShard, MeshPlan
from repro.kernels import ops as kops
from repro.models.cnn import apply_pre

# Trace-entry accounting, mirroring deploy.executor: bumps once per layer
# module actually (re)traced, so trace_lint/soak can prove repeated
# identical sharded traffic holds a bounded number of compiled variants.
_trace_entries = [0]


def trace_entry_count() -> int:
    """How many layer-module traces have run (process-wide)."""
    return _trace_entries[0]


def reset_trace_entry_count() -> None:
    _trace_entries[0] = 0


def cache_stats() -> dict:
    """Compiled-variant counts for the soak/retrace harness: one
    ``sharded_fns`` entry per distinct (mesh, shard, level, interpret,
    instruction-geometry) layer module."""
    return {"trace_entries": _trace_entries[0],
            "sharded_fns": _layer_fn.cache_info().currsize}


def cache_gauges() -> dict:
    """``name -> callable`` gauges for ``repro.testing.soak``."""
    return {"dist_trace_entries": lambda: float(_trace_entries[0]),
            "dist_sharded_fns": lambda: float(
                _layer_fn.cache_info().currsize)}


def _instr_specs(shard: LayerShard, axis_model: str, itd):
    """The instruction-shaped PartitionSpec pytree: three leaves (packed
    taps, alpha, bias — the registered array-field order), sharded along
    the model axis on their channel dim for bd shards, replicated
    otherwise."""
    if shard.kind == "bd":
        leaves = [P(None, None, None, axis_model),   # [M, T, C8, D]
                  P(None, None, axis_model),         # [M, G, D]
                  P(axis_model)]                     # [D]
    else:
        leaves = [P(), P(), P()]
    return jax.tree_util.tree_unflatten(itd, leaves)


@functools.lru_cache(maxsize=None)
def _layer_fn(mesh, axis_data: str, axis_model: str, shard: LayerShard,
              m: int, interpret: bool, itd):
    """Build + jit one macro-instruction ``shard_map`` module.

    ``itd`` is the instruction's treedef — it carries every static field
    (kind, geometry, frozen plan), so the cache key pins the exact
    executable while layers with identical schedules share one entry.
    The cache is bounded by (distinct layer geometries × levels served),
    the same bound as deploy's jit cache; ``cache_stats`` exposes the size
    for the soak harness.
    """

    def body(instr, y: jax.Array) -> jax.Array:
        _trace_entries[0] += 1      # runs at trace time only, not per call
        if shard.kind == "bd":
            assert isinstance(instr, ConvInstr), instr
            y = apply_pre(instr.pre, y)
            y_loc = kops.binary_conv2d(
                y, instr.B_tap_packed, instr.alpha, instr.bias,
                kh=instr.kh, kw=instr.kw, stride=instr.stride,
                padding=instr.padding, pool=instr.pool, m_active=m,
                relu=instr.relu, bd=shard.plan.bd, bu=shard.plan.bu,
                nb=shard.plan.nb, path="tile", interpret=interpret)
            # disjoint channel slices -> tiled concat, no fp reduction:
            # bitwise equal to the unsharded conv output
            return jax.lax.all_gather(y_loc, axis_model,
                                      axis=y_loc.ndim - 1, tiled=True)
        return dexec._apply(instr, y, m, interpret)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_instr_specs(shard, axis_model, itd), P(axis_data)),
        out_specs=P(axis_data),
        # replicated layers compute identically on every model column
        # (deterministic kernels, identical inputs/weights), so the output
        # is replicated along the model axis by construction
        check_vma=False)
    return jax.jit(fn)


def execute_sharded(program: BinArrayProgram, plan: MeshPlan, x: jax.Array,
                    m_active=None, *, interpret: bool | None = None,
                    mesh: jax.sharding.Mesh | None = None) -> jax.Array:
    """Run the program on a batch across the mesh.  x: [B, H, W, C] -> logits.

    ``m_active`` takes every §IV-D schedule form ``deploy.execute`` does
    (None | int | per-instruction sequence); ``interpret`` overrides the
    program's compile-time Pallas default; ``mesh`` reuses an existing mesh
    instead of building ``plan.build_mesh()`` per call (equal meshes hash
    equal, so repeated calls with equal plans still share the compiled
    layer modules).  A global batch not divisible by ``plan.n_data`` is
    padded with zero images and sliced back — exactly the kernels' ragged-NB
    treatment, bit-exact for the real rows.
    """
    dexec._check_input(program, x)
    if len(plan.shards) != len(program.instrs):
        raise ValueError(
            f"MeshPlan carries {len(plan.shards)} shard(s) for "
            f"{len(program.instrs)} instruction(s) — re-plan with "
            f"plan_mesh(program, ...)")
    sched = program.resolve_schedule(m_active)
    itp = program.interpret if interpret is None else interpret
    if mesh is None:
        mesh = plan.build_mesh()
    B = x.shape[0]
    pad = (-B) % plan.n_data
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + tuple(x.shape[1:]), x.dtype)])
    y = x
    for instr, m, s in zip(program.instrs, sched, plan.shards):
        itd = jax.tree_util.tree_structure(instr)
        fn = _layer_fn(mesh, plan.axis_data, plan.axis_model, s, m, itp, itd)
        y = fn(instr, y)
    return y[:B] if pad else y
