"""The TPU/Mosaic legality rules the BinArray kernels must obey — as data.

The paper's compiler (§IV) emits macro-instructions the FPGA executes
unconditionally; there is no runtime legality fallback.  Our Pallas port has
the same contract: every frozen :class:`~repro.deploy.program.TilePlan` turns
into BlockSpecs that Mosaic either accepts or rejects at lowering time, and
interpret-mode CI never exercises the reject path.  This module is the single
place those rules live — ``verify.py`` evaluates them against the kernels' own
block-shape exports (``binary_conv.conv_block_shapes`` /
``binary_dwconv.dw_block_shapes`` / ``binary_matmul.matmul_block_shapes``),
and ``docs/analysis.md`` renders the same registry as the rule table.

Tiling model (pallas guide):

  * the last ("lane") dim of every block must be a multiple of ``LANE`` = 128
    — or equal the full (padded) array dim, since Mosaic transparently pads a
    lone sub-128-lane array to one tile;
  * the second-to-last ("sublane") dim must be a multiple of the dtype's
    sublane count (f32: 8, bf16: 16, int8/uint8: 32) — or equal the full dim,
    or be 1 (degenerate row blocks relayout fine);
  * ``pl.Element`` halo slabs must stay inside the zero-padded input rows;
  * packed weights are exactly ``ceil(K/8)`` / ``ceil(C/8)`` bytes wide;
  * the conv kernel feeds the MXU fixed 128-row passes (``MXU_ROWS``).
"""
from __future__ import annotations

import dataclasses

# Mosaic register tiling: (sublane, lane) = (SUBLANE_BY_DTYPE[dtype], LANE).
LANE = 128
SUBLANE_BY_DTYPE = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16,
    "int8": 32, "uint8": 32, "bool": 32,
}

ERROR = "ERROR"
WARN = "WARN"


@dataclasses.dataclass(frozen=True)
class Rule:
    """One checkable legality/consistency rule with a stable id.

    ``severity`` is the default for findings under this rule: ERROR means the
    program is not safe to hand to a TPU (or is not the schedule that would
    actually execute); WARN means legal-but-suspicious (wasted MXU rows,
    schedules that drifted from the canonical pick, silent kernel overrides).
    """

    id: str
    severity: str
    summary: str


RULES: dict[str, Rule] = {r.id: r for r in [
    # --- Mosaic BlockSpec tiling -------------------------------------------
    Rule("mosaic-lane", ERROR,
         "block last dim must be a multiple of 128 lanes or the full padded "
         "array dim"),
    Rule("mosaic-sublane", ERROR,
         "block second-to-last dim must be a multiple of the dtype sublane "
         "(f32 8 / bf16 16 / u8 32), the full dim, or 1"),
    Rule("element-bounds", ERROR,
         "pl.Element halo slabs must stay inside the zero-padded input "
         "rows"),
    Rule("mxu-pass-rows", ERROR,
         "the conv kernel's fixed MXU pass height must stay 128 rows"),
    # --- packed-buffer / instruction consistency ---------------------------
    Rule("pack-width", ERROR,
         "packed weight widths must be exactly ceil(K/8) / ceil(C/8) bytes"),
    Rule("alpha-shape", ERROR,
         "alpha/bias must match the packed layout: [M, G, D] with "
         "G*group_size == K (conv/linear) or [M, C] (dw)"),
    Rule("levels-mismatch", ERROR,
         "packed buffers and the instruction must agree on the level count "
         "M"),
    Rule("shape-chain", ERROR,
         "each instruction's input (after its pre-op) must match the "
         "previous instruction's output"),
    Rule("epilogue-pool", ERROR,
         "conv output must be divisible by the AMU pool window "
         "(downsampling-only pooling, paper §III-B)"),
    Rule("epilogue-pre", ERROR,
         "pre-op must be one of none | flatten | gap"),
    # --- frozen tile plans --------------------------------------------------
    Rule("plan-missing", ERROR,
         "plan fields the kernel needs must be frozen (a None re-picks "
         "inside the trace)"),
    Rule("plan-range", ERROR,
         "frozen plan outside the kernel's legal range — the kernel would "
         "silently clamp, so the plan is not the executed schedule"),
    Rule("plan-bk-group", WARN,
         "bk incompatible with the alpha groups: the kernel silently "
         "switches to single-K-block grouped mode with a different bk"),
    Rule("plan-noncanonical", WARN,
         "plan differs from every pick_tile/pick_matmul_plan canonical "
         "choice (hand-built or stale)"),
    # --- budgets & stats ----------------------------------------------------
    Rule("vmem-budget", ERROR,
         "per-program VMEM working set exceeds the budget at full level "
         "count"),
    Rule("stats-drift", WARN,
         "LayerStats disagree with values re-derived from the kernels' own "
         "exports"),
    Rule("ragged-batch", WARN,
         "batch not divisible by NB: the last program carries zero images"),
    Rule("mxu-occupancy", WARN,
         "under half the MXU's padded GEMM rows carry real work"),
    # --- mesh shards (repro.distributed) ------------------------------------
    Rule("shard-divisibility", ERROR,
         "a bd-sharded layer's output channels must divide evenly over the "
         "model axis (and the recorded d_local must be that quotient)"),
    Rule("shard-lane", ERROR,
         "a bd shard's device-local lane tile must be a multiple of 128 or "
         "the full 8-padded per-device channel dim"),
    Rule("shard-plan", ERROR,
         "MeshPlan structure must match the program: one LayerShard per "
         "instruction, bd only on ConvInstr, with a frozen device-local "
         "plan (a None re-picks inside the sharded trace)"),
    Rule("shard-accounting", WARN,
         "LayerShard per-device weight bytes disagree with the stats "
         "re-derived split (replicated copy vs weight_bytes / n_model)"),
    Rule("shard-batch", WARN,
         "global batch not divisible by the data axis: the last device "
         "carries zero images every forward"),
    # --- trace lint ---------------------------------------------------------
    Rule("trace-fp-conv", ERROR,
         "full-binary trace contains fp conv_general_dilated primitives"),
    Rule("trace-plan-pick", ERROR,
         "tile auto-picks ran inside the traced forward (scheduling leaked "
         "past compile time)"),
    Rule("trace-f64", ERROR,
         "float64 values in the trace (accidental x64 promotion)"),
    Rule("trace-retrace", ERROR,
         "repeated identical calls re-traced: a compiled-variant cache is "
         "leaking"),
]}


def sublane(dtype: str) -> int:
    """Sublane tile for a dtype name (conservative f32 default)."""
    return SUBLANE_BY_DTYPE.get(str(dtype), 8)


def block_findings(operand: str, block: tuple, full: tuple,
                   dtype: str) -> list[tuple[str, str]]:
    """Mosaic tiling violations of one BlockSpec as (rule_id, message) pairs.

    ``block`` is the BlockSpec block shape, ``full`` the *padded* array shape
    it tiles (so ``block[i] == full[i]`` means the dim is untiled).  Rank < 2
    operands have no (sublane, lane) tiling to violate.
    """
    out: list[tuple[str, str]] = []
    if len(block) < 2 or len(full) < 2:
        return out
    lane_b, lane_f = int(block[-1]), int(full[-1])
    if lane_b % LANE and lane_b != lane_f:
        out.append(("mosaic-lane",
                    f"{operand}: last-dim block {lane_b} is neither a "
                    f"multiple of {LANE} nor the full padded dim {lane_f} "
                    f"(block {tuple(block)} over {tuple(full)})"))
    sub = sublane(dtype)
    sl_b, sl_f = int(block[-2]), int(full[-2])
    if sl_b % sub and sl_b != sl_f and sl_b != 1:
        out.append(("mosaic-sublane",
                    f"{operand}: second-to-last block dim {sl_b} is not a "
                    f"multiple of the {dtype} sublane {sub}, the full dim "
                    f"{sl_f}, or 1 (block {tuple(block)} over "
                    f"{tuple(full)})"))
    return out


def blocks_findings(prefix: str,
                    blocks: dict[str, tuple]) -> list[tuple[str, str]]:
    """Run :func:`block_findings` over a kernel's ``*_block_shapes`` export:
    a dict of ``operand -> (block_shape, padded_array_shape, dtype)``."""
    out: list[tuple[str, str]] = []
    for name, (block, full, dtype) in blocks.items():
        out.extend(block_findings(f"{prefix}.{name}", block, full, dtype))
    return out
