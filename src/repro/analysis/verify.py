"""verify_program(): statically prove a BinArrayProgram is safe to execute.

The checker re-derives every instruction's geometry from the program's
``input_shape`` and static fields, evaluates the Mosaic tiling rules
(``mosaic_rules``) against the kernels' own block-shape exports
(``conv_block_shapes`` / ``dw_block_shapes`` / ``matmul_block_shapes``), and
re-runs the canonical pick functions to detect hand-built or stale plans —
so a program that passes is, instruction for instruction, the schedule the
kernels would actually execute, inside the VMEM budget, with no silent
clamps or overrides.

Works on abstract programs too (``deploy.abstract_program``): every check
reads shapes and static aux data only, never array values.  Canonical-pick
re-runs are wrapped so they do NOT bump the process-wide
``plan_pick_count`` — verification must not poison the trace-lint gate.

``Finding`` severity semantics live on the rules (``mosaic_rules.RULES``):
ERROR = not safe to hand to a TPU / not the executed schedule; WARN =
legal but suspicious.  ``assert_verified`` raises
:class:`ProgramVerificationError` on any ERROR — ``deploy.compile(...,
verify=True)`` calls it.
"""
from __future__ import annotations

import contextlib
import dataclasses

from repro.analysis import mosaic_rules
from repro.core import binconv
from repro.deploy.program import (BinArrayProgram, ConvInstr, DWConvInstr,
                                  LinearInstr)
from repro.kernels import binary_conv as bck
from repro.kernels import binary_dwconv as bdw
from repro.kernels import binary_matmul as bmk
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verifier result: a rule id, where it fired, and why."""

    rule: str        # id in mosaic_rules.RULES
    severity: str    # ERROR | WARN (rule default unless overridden)
    instr: str       # instruction name ("" = program/trace level)
    index: int       # instruction index (-1 = program/trace level)
    message: str

    def __str__(self) -> str:
        where = f"{self.instr}[{self.index}]" if self.index >= 0 else "trace"
        return f"{self.severity} {self.rule} @ {where}: {self.message}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ProgramVerificationError(ValueError):
    """Raised by :func:`assert_verified` when ERROR findings exist."""


def make_finding(rule: str, instr: str, index: int, message: str,
                 severity: str | None = None) -> Finding:
    """Build a Finding, defaulting severity from the rule registry."""
    sev = severity or mosaic_rules.RULES[rule].severity
    return Finding(rule=rule, severity=sev, instr=instr, index=index,
                   message=message)


@contextlib.contextmanager
def _no_pick_accounting():
    """Re-running pick_* for canonical-plan comparison must not count as a
    trace-time plan pick (the counter is the trace-lint gate)."""
    before = bck.plan_pick_count()
    try:
        yield
    finally:
        bck._plan_picks[0] = before


def summarize(findings: list[Finding]) -> dict:
    """JSON-able roll-up for ``benchmarks/run.py --json``'s verify section."""
    by_rule: dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    return {
        "errors": sum(1 for f in findings if f.severity == mosaic_rules.ERROR),
        "warnings": sum(1 for f in findings
                        if f.severity == mosaic_rules.WARN),
        "by_rule": by_rule,
    }


# ---------------------------------------------------------------------------
# Per-instruction checkers.  Each returns (out_shape, findings); out_shape is
# re-derived (never trusted from stats) so shape-chain errors localize.
# ---------------------------------------------------------------------------

def _pre_shape(pre: str, shape: tuple[int, ...]) -> tuple[int, ...]:
    if pre == "flatten":
        n = 1
        for d in shape[1:]:
            n *= d
        return (shape[0], n)
    if pre == "gap":
        return (shape[0], shape[-1])
    return shape


def _check_pre(instr, idx: int, shape, fs) -> tuple[int, ...]:
    if instr.pre not in ("none", "flatten", "gap"):
        fs.append(make_finding("epilogue-pre", instr.name, idx,
                               f"unknown pre-op {instr.pre!r}"))
        return shape
    return _pre_shape(instr.pre, shape)


def _check_stats_vmem(instr, idx: int, vmem_by_m: dict[int, int], fs) -> None:
    """stats.vmem_bytes must match the kernel formula for *some* level count
    1..M (compiles may be m_active-biased)."""
    got = instr.stats.vmem_bytes
    if got and got not in vmem_by_m.values():
        fs.append(make_finding(
            "stats-drift", instr.name, idx,
            f"stats.vmem_bytes={got} matches no level count 1..{instr.M} "
            f"(kernel formula gives {sorted(set(vmem_by_m.values()))})"))


def _verify_conv(instr: ConvInstr, idx: int, shape, budget: int):
    fs: list[Finding] = []
    name = instr.name
    shape = _check_pre(instr, idx, shape, fs)
    if len(shape) != 4:
        fs.append(make_finding(
            "shape-chain", name, idx,
            f"conv needs a rank-4 [B,H,W,C] input, got {shape}"))
        return tuple(instr.stats.out_shape), fs
    B, H, W, C = shape
    M, T, C8, D = instr.B_tap_packed.shape
    kh, kw = instr.kh, instr.kw
    if T != kh * kw:
        fs.append(make_finding(
            "pack-width", name, idx,
            f"B_tap_packed has {T} taps for a {kh}x{kw} window"))
    if C8 != -(-C // 8):
        fs.append(make_finding(
            "pack-width", name, idx,
            f"B_tap_packed per-tap width {C8} != ceil(C/8) = {-(-C // 8)} "
            f"for C={C}"))
    if M != instr.M:
        fs.append(make_finding(
            "levels-mismatch", name, idx,
            f"B_tap_packed carries {M} levels, instruction says {instr.M}"))
    al = tuple(instr.alpha.shape)
    if len(al) != 3 or al[0] != M or al[2] != D:
        fs.append(make_finding(
            "alpha-shape", name, idx,
            f"alpha {al} != [M={M}, G, D={D}]"))
        G = 1
    else:
        G = al[1]
        if G * instr.group_size != kh * kw * C:
            fs.append(make_finding(
                "alpha-shape", name, idx,
                f"G={G} * group_size={instr.group_size} != K="
                f"{kh * kw * C}"))
    if tuple(instr.bias.shape) != (D,):
        fs.append(make_finding(
            "alpha-shape", name, idx,
            f"bias {tuple(instr.bias.shape)} != ({D},)"))

    # geometry (the wrapper resolves SAME before the kernel sees x)
    if instr.padding == "SAME":
        pt, pb = binconv.same_pads(H, kh, instr.stride)
        pl_, pr = binconv.same_pads(W, kw, instr.stride)
        Hp, Wp = H + pt + pb, W + pl_ + pr
    else:
        Hp, Wp = H, W
    U = (Hp - kh) // instr.stride + 1
    V = (Wp - kw) // instr.stride + 1
    if U % instr.pool or V % instr.pool:
        fs.append(make_finding(
            "epilogue-pool", name, idx,
            f"conv output {U}x{V} not divisible by AMU pool {instr.pool}"))
        return tuple(instr.stats.out_shape), fs
    uo = max(U // instr.pool, 1)
    out_shape = (B, uo, V // instr.pool, D)

    plan = instr.plan
    if plan.nb is None or plan.bu is None or plan.bd is None:
        fs.append(make_finding(
            "plan-missing", name, idx,
            f"conv plan needs (nb, bu, bd), got {plan}"))
        return out_shape, fs
    nb, bu, bd = plan.nb, plan.bu, plan.bd
    if not 1 <= nb <= B:
        fs.append(make_finding(
            "plan-range", name, idx,
            f"nb={nb} outside [1, B={B}] (kernel clamps silently)"))
    if not 1 <= bu <= uo:
        fs.append(make_finding(
            "plan-range", name, idx,
            f"bu={bu} outside [1, Uo={uo}] (kernel clamps silently)"))
    if not 1 <= bd <= max(8, D):
        fs.append(make_finding(
            "plan-range", name, idx,
            f"bd={bd} outside [1, max(8, D={D})] (kernel clamps silently)"))
    # check the schedule the kernel would actually run (clamped plan)
    nb_e = max(1, min(nb, B))
    bu_e = max(1, min(bu, uo))
    bd_e = max(1, min(bd, max(8, D)))
    geo = bck.conv_block_shapes(
        Hp, Wp, C, D, kh, kw, bd=bd_e, bu=bu_e, nb=nb_e, pool=instr.pool,
        stride=instr.stride, m=M, group_size=instr.group_size, B=B)
    for rule, msg in mosaic_rules.blocks_findings(name, geo["blocks"]):
        fs.append(make_finding(rule, name, idx, msg))
    last_slab_end = (geo["nt"] - 1) * geo["adv"] + geo["slab"]
    if geo["adv"] < 1 or geo["slab"] < kh \
            or last_slab_end > geo["padded_rows"]:
        fs.append(make_finding(
            "element-bounds", name, idx,
            f"halo slabs (nt={geo['nt']}, adv={geo['adv']}, "
            f"slab={geo['slab']}) overrun the {geo['padded_rows']} padded "
            f"input rows"))

    vmem_by_m = {m: bck.tile_vmem_bytes(
        Wp, C, kh, kw, bd_e, bu=bu_e, pool=instr.pool, stride=instr.stride,
        m=m, nb=nb_e) for m in range(1, M + 1)}
    worst = vmem_by_m[M]
    if worst > budget:
        # the pick floor (nb=bu=1) may legitimately exceed the budget on
        # huge layers — the budget is a target there, not a hard limit
        floor = nb_e == 1 and bu_e == 1
        fs.append(make_finding(
            "vmem-budget", name, idx,
            f"working set {worst} B > budget {budget} B at m={M} "
            f"(nb={nb_e}, bu={bu_e}, bd={bd_e})",
            severity=mosaic_rules.WARN if floor else None))

    with _no_pick_accounting():
        canonical = set()
        for m in range(1, M + 1):
            cbd = kops._pick_block(D, 128)
            canonical.add((bck.pick_tile(
                B, Hp, Wp, C, kh, kw, cbd, instr.pool, budget,
                stride=instr.stride, m=m), cbd))
            for cnb in range(1, B + 1):
                canonical.add(((cnb, bck.pick_bu(
                    Hp, Wp, C, kh, kw, cbd, instr.pool, budget,
                    stride=instr.stride, m=m, nb=cnb)), cbd))
    if ((nb, bu), bd) not in canonical:
        fs.append(make_finding(
            "plan-noncanonical", name, idx,
            f"(nb={nb}, bu={bu}, bd={bd}) matches no pick_tile/pick_bu "
            f"choice for this layer (hand-built or stale plan)"))

    # stats drift + utilization warnings
    st = instr.stats
    if tuple(st.out_shape) and tuple(st.out_shape) != out_shape:
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.out_shape {tuple(st.out_shape)} != derived {out_shape}"))
    if tuple(st.padded_in) and tuple(st.padded_in) != (Hp, Wp):
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.padded_in {tuple(st.padded_in)} != ({Hp}, {Wp})"))
    macs = U * V * D * kh * kw * C
    if st.macs and st.macs != macs:
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.macs {st.macs} != derived {macs}"))
    _check_stats_vmem(instr, idx, vmem_by_m, fs)
    if B % nb_e:
        fs.append(make_finding(
            "ragged-batch", name, idx,
            f"B={B} % nb={nb_e} != 0: last program carries "
            f"{(-B) % nb_e} zero image(s)"))
    occ = bck.mxu_row_occupancy(bck.gemm_rows(nb_e, bu_e, V, pool=instr.pool))
    if occ < 0.5:
        fs.append(make_finding(
            "mxu-occupancy", name, idx,
            f"MXU row occupancy {occ:.0%} under the plan "
            f"(rows={bck.gemm_rows(nb_e, bu_e, V, pool=instr.pool)})"))
    return out_shape, fs


def _verify_dwconv(instr: DWConvInstr, idx: int, shape, budget: int):
    fs: list[Finding] = []
    name = instr.name
    shape = _check_pre(instr, idx, shape, fs)
    if len(shape) != 4:
        fs.append(make_finding(
            "shape-chain", name, idx,
            f"dwconv needs a rank-4 [B,H,W,C] input, got {shape}"))
        return tuple(instr.stats.out_shape), fs
    B, H, W, C = shape
    M, T, c8 = instr.B_tap_packed.shape
    kh, kw = instr.kh, instr.kw
    if T != kh * kw:
        fs.append(make_finding(
            "pack-width", name, idx,
            f"B_tap_packed has {T} taps for a {kh}x{kw} window"))
    if c8 != -(-C // 8):
        fs.append(make_finding(
            "pack-width", name, idx,
            f"B_tap_packed width {c8} != ceil(C/8) = {-(-C // 8)}"))
    if M != instr.M:
        fs.append(make_finding(
            "levels-mismatch", name, idx,
            f"B_tap_packed carries {M} levels, instruction says {instr.M}"))
    if tuple(instr.alpha.shape) != (M, C):
        fs.append(make_finding(
            "alpha-shape", name, idx,
            f"dw alpha {tuple(instr.alpha.shape)} != (M={M}, C={C})"))
    if tuple(instr.bias.shape) != (C,):
        fs.append(make_finding(
            "alpha-shape", name, idx,
            f"bias {tuple(instr.bias.shape)} != ({C},)"))

    pt, pb = binconv.same_pads(H, kh, instr.stride)
    pl_, pr = binconv.same_pads(W, kw, instr.stride)
    Hp, Wp = H + pt + pb, W + pl_ + pr
    U = (Hp - kh) // instr.stride + 1
    V = (Wp - kw) // instr.stride + 1
    out_shape = (B, U, V, C)

    plan = instr.plan
    if plan.nb is None or plan.bu is None:
        fs.append(make_finding(
            "plan-missing", name, idx,
            f"dw plan needs (nb, bu), got {plan}"))
        return out_shape, fs
    nb, bu = plan.nb, plan.bu
    if not 1 <= nb <= B:
        fs.append(make_finding(
            "plan-range", name, idx,
            f"nb={nb} outside [1, B={B}] (kernel clamps silently)"))
    if not 1 <= bu <= U:
        fs.append(make_finding(
            "plan-range", name, idx,
            f"bu={bu} outside [1, U={U}] (kernel clamps silently)"))
    nb_e = max(1, min(nb, B))
    bu_e = max(1, min(bu, U))
    geo = bdw.dw_block_shapes(Hp, Wp, C, kh, kw, bu=bu_e, nb=nb_e,
                              stride=instr.stride, m=M, B=B)
    for rule, msg in mosaic_rules.blocks_findings(name, geo["blocks"]):
        fs.append(make_finding(rule, name, idx, msg))
    last_slab_end = (geo["nt"] - 1) * geo["adv"] + geo["slab"]
    if geo["adv"] < 1 or geo["slab"] < kh \
            or last_slab_end > geo["padded_rows"]:
        fs.append(make_finding(
            "element-bounds", name, idx,
            f"halo slabs (nt={geo['nt']}, adv={geo['adv']}, "
            f"slab={geo['slab']}) overrun the {geo['padded_rows']} padded "
            f"input rows"))

    vmem_by_m = {m: bdw.tile_vmem_bytes_dw(
        Wp, C, kh, kw, bu=bu_e, stride=instr.stride, m=m, nb=nb_e)
        for m in range(1, M + 1)}
    worst = vmem_by_m[M]
    if worst > budget:
        floor = nb_e == 1 and bu_e == 1
        fs.append(make_finding(
            "vmem-budget", name, idx,
            f"working set {worst} B > budget {budget} B at m={M} "
            f"(nb={nb_e}, bu={bu_e})",
            severity=mosaic_rules.WARN if floor else None))

    with _no_pick_accounting():
        canonical = set()
        for m in range(1, M + 1):
            canonical.add(bdw.pick_tile_dw(B, Hp, Wp, C, kh, kw, budget,
                                           stride=instr.stride, m=m))
            for cnb in range(1, B + 1):
                canonical.add((cnb, bdw.pick_bu_dw(
                    Hp, Wp, C, kh, kw, budget, stride=instr.stride, m=m,
                    nb=cnb)))
    if (nb, bu) not in canonical:
        fs.append(make_finding(
            "plan-noncanonical", name, idx,
            f"(nb={nb}, bu={bu}) matches no pick_tile_dw/pick_bu_dw choice "
            f"for this layer (hand-built or stale plan)"))

    st = instr.stats
    if tuple(st.out_shape) and tuple(st.out_shape) != out_shape:
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.out_shape {tuple(st.out_shape)} != derived {out_shape}"))
    if tuple(st.padded_in) and tuple(st.padded_in) != (Hp, Wp):
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.padded_in {tuple(st.padded_in)} != ({Hp}, {Wp})"))
    _check_stats_vmem(instr, idx, vmem_by_m, fs)
    if B % nb_e:
        fs.append(make_finding(
            "ragged-batch", name, idx,
            f"B={B} % nb={nb_e} != 0: last program carries "
            f"{(-B) % nb_e} zero image(s)"))
    return out_shape, fs


def _verify_linear(instr: LinearInstr, idx: int, shape, budget: int):
    fs: list[Finding] = []
    name = instr.name
    shape = _check_pre(instr, idx, shape, fs)
    B = shape[0]
    k_in = shape[-1] if len(shape) >= 2 else 0
    if k_in != instr.K:
        fs.append(make_finding(
            "shape-chain", name, idx,
            f"incoming features {k_in} (shape {shape} after pre="
            f"{instr.pre!r}) != instruction K={instr.K}"))
    K = instr.K
    M, K8, N = instr.B_packed.shape
    if K8 != -(-K // 8):
        fs.append(make_finding(
            "pack-width", name, idx,
            f"B_packed width {K8} != ceil(K/8) = {-(-K // 8)} for K={K}"))
    if M != instr.M:
        fs.append(make_finding(
            "levels-mismatch", name, idx,
            f"B_packed carries {M} levels, instruction says {instr.M}"))
    al = tuple(instr.alpha.shape)
    if len(al) != 3 or al[0] != M or al[2] != N:
        fs.append(make_finding(
            "alpha-shape", name, idx, f"alpha {al} != [M={M}, G, N={N}]"))
        G = 1
    else:
        G = al[1]
        if G * instr.group_size != K:
            fs.append(make_finding(
                "alpha-shape", name, idx,
                f"G={G} * group_size={instr.group_size} != K={K}"))
    if tuple(instr.bias.shape) != (N,):
        fs.append(make_finding(
            "alpha-shape", name, idx,
            f"bias {tuple(instr.bias.shape)} != ({N},)"))
    out_shape = (B, N)

    plan = instr.plan
    if plan.bt is None or plan.bn is None or plan.bk is None:
        fs.append(make_finding(
            "plan-missing", name, idx,
            f"matmul plan needs (bt, bn, bk), got {plan}"))
        return out_shape, fs
    bt, bn, bk = plan.bt, plan.bn, plan.bk
    if bt < 1 or bn < 1 or bk < 8 or bk % 8:
        fs.append(make_finding(
            "plan-range", name, idx,
            f"(bt={bt}, bn={bn}, bk={bk}) needs bt,bn >= 1 and bk a "
            f"positive multiple of 8 (bit-packed K tiles)"))
        return out_shape, fs
    blocks, eff_bk = bmk.matmul_block_shapes(
        B, K, N, bt=bt, bn=bn, bk=bk, m=M, G=G,
        group_size=instr.group_size)
    if eff_bk != bk:
        fs.append(make_finding(
            "plan-bk-group", name, idx,
            f"bk={bk} does not divide group_size={instr.group_size} "
            f"(G={G}): kernel silently overrides to single-block "
            f"bk={eff_bk}"))
    for rule, msg in mosaic_rules.blocks_findings(name, blocks):
        fs.append(make_finding(rule, name, idx, msg))

    vmem_by_m = {m: bmk.tile_vmem_bytes_mm(bt, bn, eff_bk, m=m)
                 for m in range(1, M + 1)}
    worst = vmem_by_m[M]
    if worst > budget:
        fs.append(make_finding(
            "vmem-budget", name, idx,
            f"working set {worst} B > budget {budget} B at m={M} "
            f"(bt={bt}, bn={bn}, bk={eff_bk})"))

    with _no_pick_accounting():
        canonical = kops.pick_matmul_plan(B, K, N, G=G,
                                          group_size=instr.group_size)
    if (bt, bn, bk) != canonical:
        fs.append(make_finding(
            "plan-noncanonical", name, idx,
            f"(bt={bt}, bn={bn}, bk={bk}) != pick_matmul_plan "
            f"{canonical} (hand-built or stale plan)"))

    st = instr.stats
    if tuple(st.out_shape) and tuple(st.out_shape) != out_shape:
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.out_shape {tuple(st.out_shape)} != derived {out_shape}"))
    if st.macs and st.macs != K * N:
        fs.append(make_finding(
            "stats-drift", name, idx,
            f"stats.macs {st.macs} != derived {K * N}"))
    _check_stats_vmem(instr, idx, vmem_by_m, fs)
    return out_shape, fs


# ---------------------------------------------------------------------------
# Program-level entry points
# ---------------------------------------------------------------------------

def verify_program(program: BinArrayProgram, *,
                   vmem_budget: int | None = None) -> list[Finding]:
    """Statically verify every instruction of a compiled (or abstract)
    program.  Returns all findings, ERRORs first; empty list == clean.

    ``vmem_budget`` defaults to the kernels' ``DEFAULT_VMEM_BUDGET`` (the
    same target the pick functions optimize against).
    """
    budget = vmem_budget or bck.DEFAULT_VMEM_BUDGET
    findings: list[Finding] = []
    if bck.MXU_ROWS != mosaic_rules.LANE:
        findings.append(make_finding(
            "mxu-pass-rows", "", -1,
            f"kernels.binary_conv.MXU_ROWS = {bck.MXU_ROWS}, expected "
            f"{mosaic_rules.LANE}"))
    shape = tuple(program.input_shape)
    for idx, instr in enumerate(program.instrs):
        if isinstance(instr, ConvInstr):
            shape, fs = _verify_conv(instr, idx, shape, budget)
        elif isinstance(instr, DWConvInstr):
            shape, fs = _verify_dwconv(instr, idx, shape, budget)
        else:
            shape, fs = _verify_linear(instr, idx, shape, budget)
        findings.extend(fs)
    findings.sort(key=lambda f: (f.severity != mosaic_rules.ERROR, f.index))
    return findings


def verify_mesh_plan(program: BinArrayProgram, plan, *,
                     vmem_budget: int | None = None) -> list[Finding]:
    """Statically verify a :class:`~repro.distributed.plan.MeshPlan` against
    its program: shard arity/kind structure (``shard-plan``), channel
    divisibility over the model axis (``shard-divisibility``), Mosaic
    lane-128 legality of each device-local bd tile (``shard-lane``),
    per-device working sets against the VMEM budget (``vmem-budget``), and
    the replication byte accounting (``shard-accounting``).  Returns all
    findings, ERRORs first; empty list == clean.  Abstract-program safe —
    shapes and static aux only, like :func:`verify_program`.
    """
    budget = vmem_budget or bck.DEFAULT_VMEM_BUDGET
    fs: list[Finding] = []
    if plan.n_data < 1 or plan.n_model < 1:
        fs.append(make_finding(
            "shard-plan", "", -1,
            f"mesh axes must be >= 1, got n_data={plan.n_data}, "
            f"n_model={plan.n_model}"))
        return fs
    if len(plan.shards) != len(program.instrs):
        fs.append(make_finding(
            "shard-plan", "", -1,
            f"MeshPlan carries {len(plan.shards)} LayerShard(s) for "
            f"{len(program.instrs)} instruction(s)"))
        return fs
    if plan.global_batch % plan.n_data:
        fs.append(make_finding(
            "shard-batch", "", -1,
            f"global_batch={plan.global_batch} % n_data={plan.n_data} != 0: "
            f"every forward pads {(-plan.global_batch) % plan.n_data} zero "
            f"image(s)"))
    for idx, (instr, s) in enumerate(zip(program.instrs, plan.shards)):
        name = instr.name
        if s.kind == "replicated":
            if (s.per_device_weight_bytes
                    and s.per_device_weight_bytes
                    != instr.stats.weight_bytes):
                fs.append(make_finding(
                    "shard-accounting", name, idx,
                    f"replicated shard records "
                    f"{s.per_device_weight_bytes} B/device, stats say the "
                    f"full copy is {instr.stats.weight_bytes} B"))
            continue
        if s.kind != "bd":
            fs.append(make_finding(
                "shard-plan", name, idx,
                f"unknown shard kind {s.kind!r} (replicated | bd)"))
            continue
        if not isinstance(instr, ConvInstr):
            fs.append(make_finding(
                "shard-plan", name, idx,
                f"bd sharding applies to ConvInstr only, got {instr.kind}"))
            continue
        D = int(instr.alpha.shape[-1])
        if D % plan.n_model:
            fs.append(make_finding(
                "shard-divisibility", name, idx,
                f"D={D} output channels do not divide over "
                f"n_model={plan.n_model}"))
            continue
        d_local = D // plan.n_model
        if s.d_local != d_local:
            fs.append(make_finding(
                "shard-divisibility", name, idx,
                f"recorded d_local={s.d_local} != D/n_model = {d_local}"))
        lp = s.plan
        if lp is None or lp.nb is None or lp.bu is None or lp.bd is None:
            fs.append(make_finding(
                "shard-plan", name, idx,
                f"bd shard needs a frozen device-local (nb, bu, bd) plan, "
                f"got {lp}"))
            continue
        d_pad = -(-d_local // 8) * 8
        if lp.bd % mosaic_rules.LANE and lp.bd != d_pad:
            fs.append(make_finding(
                "shard-lane", name, idx,
                f"device-local bd={lp.bd} is neither a multiple of "
                f"{mosaic_rules.LANE} nor the full 8-padded per-device "
                f"channel dim {d_pad} (d_local={d_local})"))
        st = instr.stats
        Hp, Wp = (tuple(st.padded_in) if st.padded_in
                  else tuple(st.in_shape[1:3]))
        C = int(st.in_shape[-1])
        local_vmem = bck.tile_vmem_bytes(
            Wp, C, instr.kh, instr.kw, min(lp.bd, d_pad),
            bu=lp.bu, pool=instr.pool, stride=instr.stride, m=instr.M,
            nb=lp.nb)
        if local_vmem > budget and not (lp.nb == 1 and lp.bu == 1):
            fs.append(make_finding(
                "vmem-budget", name, idx,
                f"device-local working set {local_vmem} B > budget "
                f"{budget} B (nb={lp.nb}, bu={lp.bu}, bd={lp.bd}, "
                f"d_local={d_local})"))
        if (s.per_device_weight_bytes
                and s.per_device_weight_bytes
                != st.weight_bytes // plan.n_model):
            fs.append(make_finding(
                "shard-accounting", name, idx,
                f"bd shard records {s.per_device_weight_bytes} B/device, "
                f"stats split gives {st.weight_bytes // plan.n_model} B "
                f"(weight_bytes={st.weight_bytes}, n_model={plan.n_model})"))
    fs.sort(key=lambda f: (f.severity != mosaic_rules.ERROR, f.index))
    return fs


def assert_verified(program: BinArrayProgram, *,
                    vmem_budget: int | None = None) -> list[Finding]:
    """Raise :class:`ProgramVerificationError` on any ERROR finding; returns
    the (WARN-only) findings otherwise."""
    findings = verify_program(program, vmem_budget=vmem_budget)
    errors = [f for f in findings if f.severity == mosaic_rules.ERROR]
    if errors:
        raise ProgramVerificationError(
            f"{len(errors)} ERROR finding(s):\n"
            + "\n".join(f"  {f}" for f in errors))
    return findings
