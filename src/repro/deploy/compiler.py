"""compile(): layer params + arch spec -> BinArrayProgram (paper §IV).

The compiler does everything that is static, ONCE, ahead of deployment:

  1. **Pack** — fp trees are binarized (Algorithm 2) into the kernels'
     packed layouts; already-packed trees are reused as-is, and legacy
     trees that predate the fused conv kernel are upgraded through
     ``binconv.ensure_tap_packed`` so every emitted ``ConvInstr`` carries
     ``B_tap_packed`` (the per-call ``repack_taps`` path is retired).
  2. **Plan** — the exact tile auto-picks the per-call paths run on every
     trace (``pick_tile`` / ``pick_tile_dw`` / ``pick_matmul_plan``) run
     here instead, against the compile-time ``input_shape``, and freeze
     into each instruction's :class:`~repro.deploy.program.TilePlan`.
     Using the same pick functions is what makes ``execute`` bit-exact
     against the legacy ``QuantConfig.fuse_conv`` forwards.
  3. **Account** — per-layer VMEM working sets, fused-vs-im2col HBM bytes,
     MAC counts, and MXU row occupancy land in :class:`LayerStats`, so the
     benchmarks read ``program.layer_stats()`` instead of hand-maintained
     layer lists.

``compile`` is pure JAX: run it under ``jax.eval_shape`` (see
:func:`abstract_program`) and you get the full program — real plans, real
stats — with ShapeDtypeStruct weights, for free.
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp

from repro.core import binconv
from repro.core import binlinear as bl
from repro.core.binlinear import QuantConfig
from repro.deploy.program import (BinArrayProgram, ConvInstr, DWConvInstr,
                                  GoldenRecord, LayerStats, LinearInstr,
                                  TilePlan)
from repro.kernels import binary_conv as bck
from repro.kernels import binary_dwconv as bdw
from repro.kernels import binary_matmul as bmk
from repro.kernels import ops as kops
from repro.models import cnn

ARCHS = ("cnn_a", "mobilenet")

log = logging.getLogger("repro.deploy")


def _specs(arch):
    if isinstance(arch, (tuple, list)):   # explicit LayerSpec list (fuzz /
        return tuple(arch)                # custom topologies)
    if arch == "cnn_a":
        return cnn.cnn_a_specs()
    if arch == "mobilenet":
        return cnn.mobilenet_specs()
    raise ValueError(f"unknown arch {arch!r}; expected one of {ARCHS} "
                     "or an explicit LayerSpec sequence")


def _bias(p: dict, n: int) -> jax.Array:
    b = p.get("b")
    if b is None:
        return jnp.zeros((n,), jnp.float32)
    return b.astype(jnp.float32)


def _compile_conv(spec, p, shape, quant):
    """One conv spec -> (ConvInstr, out_shape)."""
    if "B_packed" not in p and "B_tap_packed" not in p:
        p = binconv.binarize_conv_params(p, quant)
    B, H, W, C = shape
    p = binconv.ensure_tap_packed(p, C)      # legacy flat-only trees upgrade
    tap = p["B_tap_packed"]
    M, T, C8, D = tap.shape
    kh, kw = spec.kh, spec.kw
    assert T == kh * kw, (spec.name, T, kh, kw)
    if spec.padding == "SAME":
        (pt, pb) = binconv.same_pads(H, kh, spec.stride)
        (pl, pr) = binconv.same_pads(W, kw, spec.stride)
        Hp, Wp = H + pt + pb, W + pl + pr
    else:
        Hp, Wp = H, W
    U = (Hp - kh) // spec.stride + 1
    V = (Wp - kw) // spec.stride + 1
    if U % spec.pool or V % spec.pool:
        raise ValueError(
            f"{spec.name}: conv output {U}x{V} not divisible by AMU pool "
            f"{spec.pool} (paper §III-B: downsampling only)")
    G = p["alpha"].shape[1]
    group_size = kh * kw * C // G
    m_plan = min(quant.m_active or M, M)
    budget = quant.conv_vmem_budget or bck.DEFAULT_VMEM_BUDGET
    uo = U // spec.pool
    path = bck.pick_conv_path(Hp, Wp, C, D, kh, kw, stride=spec.stride,
                              pool=spec.pool, m=M)

    if path == "row":
        if quant.conv_batch_tile is not None:
            nb = max(1, min(quant.conv_batch_tile, B))
        else:
            nb = bck.pick_row_nb(B, Hp, Wp, C, D, kh, kw, pool=spec.pool,
                                 m=m_plan, budget_bytes=budget)
        bu, bd, rows_img = uo, None, U
        vmem = bck.row_vmem_bytes(Hp, Wp, C, D, kh, kw, pool=spec.pool,
                                  m=m_plan, nb=nb)
        fused = bck.row_hbm_bytes(Hp, Wp, C, D, kh, kw, pool=spec.pool, m=M,
                                  nb=nb)
        im2col = bck.tile_hbm_bytes(Wp, C, kh, kw, D, bu=uo, pool=spec.pool,
                                    m=M, nb=nb, H=Hp)[1]
        rows = nb * U
    else:
        bd = kops._pick_block(D, 128)
        if quant.conv_batch_tile is not None:
            nb = max(1, min(quant.conv_batch_tile, B))
            bu = bck.pick_bu(Hp, Wp, C, kh, kw, bd, spec.pool, budget,
                             stride=spec.stride, m=m_plan, nb=nb)
        else:
            nb, bu = bck.pick_tile(B, Hp, Wp, C, kh, kw, bd, spec.pool,
                                   budget, stride=spec.stride, m=m_plan)
        vmem = bck.tile_vmem_bytes(
            Wp, C, kh, kw, bd, bu=bu, pool=spec.pool, stride=spec.stride,
            m=m_plan, nb=nb)
        fused, im2col = bck.tile_hbm_bytes(
            Wp, C, kh, kw, min(bd, D), bu=bu, pool=spec.pool,
            stride=spec.stride, m=M, nb=nb, H=Hp)
        rows_img = bck.gemm_rows(1, bu, V, pool=spec.pool)
        rows = bck.gemm_rows(nb, bu, V, pool=spec.pool)

    stats = LayerStats(
        in_shape=(B, H, W, C),
        out_shape=(B, uo, V // spec.pool, D),
        padded_in=(Hp, Wp),
        macs=U * V * D * kh * kw * C,
        weight_bytes=int(tap.size) + int(p["alpha"].size) * 4,
        vmem_bytes=vmem,
        hbm_fused_bytes=fused, hbm_im2col_bytes=im2col,
        mxu_row_occupancy=bck.mxu_row_occupancy(rows),
        batch_row_utilization=(bck.batch_row_utilization(B, nb, rows_img)
                               if bu == uo else bck.mxu_row_occupancy(rows)),
    )
    instr = ConvInstr(
        B_tap_packed=tap, alpha=p["alpha"], bias=_bias(p, D),
        name=spec.name, kh=kh, kw=kw, stride=spec.stride,
        padding=spec.padding, pool=spec.pool, relu=spec.relu, pre=spec.pre,
        M=M, group_size=group_size,
        plan=TilePlan(nb=nb, bu=bu, bd=bd, path=path), stats=stats)
    return instr, stats.out_shape


def _compile_dwconv(spec, p, shape, quant):
    """One depth-wise spec -> (DWConvInstr, out_shape).  Always SAME."""
    if "B_tap_packed" not in p:
        p = binconv.binarize_dwconv_params(p, quant)
    B, H, W, C = shape
    tap = p["B_tap_packed"]
    M, T, c8 = tap.shape
    kh, kw = spec.kh, spec.kw
    assert T == kh * kw and c8 * 8 >= C, (spec.name, tap.shape, C)
    (pt, pb) = binconv.same_pads(H, kh, spec.stride)
    (pl, pr) = binconv.same_pads(W, kw, spec.stride)
    Hp, Wp = H + pt + pb, W + pl + pr
    U = (Hp - kh) // spec.stride + 1
    V = (Wp - kw) // spec.stride + 1
    m_plan = min(quant.m_active or M, M)
    budget = quant.conv_vmem_budget or bck.DEFAULT_VMEM_BUDGET
    if quant.conv_batch_tile is not None:
        nb = max(1, min(quant.conv_batch_tile, B))
        bu = bdw.pick_bu_dw(Hp, Wp, C, kh, kw, budget, stride=spec.stride,
                            m=m_plan, nb=nb)
    else:
        nb, bu = bdw.pick_tile_dw(B, Hp, Wp, C, kh, kw, budget,
                                  stride=spec.stride, m=m_plan)
    stats = LayerStats(
        in_shape=(B, H, W, C), out_shape=(B, U, V, C), padded_in=(Hp, Wp),
        macs=U * V * C * kh * kw,
        weight_bytes=int(tap.size) + int(p["alpha"].size) * 4,
        vmem_bytes=bdw.tile_vmem_bytes_dw(
            Wp, C, kh, kw, bu=bu, stride=spec.stride, m=m_plan, nb=nb),
    )
    instr = DWConvInstr(
        B_tap_packed=tap, alpha=p["alpha"], bias=_bias(p, C),
        name=spec.name, kh=kh, kw=kw, stride=spec.stride, relu=spec.relu,
        pre=spec.pre, M=M, plan=TilePlan(nb=nb, bu=bu), stats=stats)
    return instr, stats.out_shape


def _compile_linear(spec, p, shape, quant):
    """One linear spec -> (LinearInstr, out_shape)."""
    if "B_packed" not in p:
        p = bl.binarize_params(p, quant)
    B = shape[0]
    if spec.pre == "flatten":
        K = 1
        for d in shape[1:]:
            K *= d
    else:  # "gap" (channels survive the mean) or "none" (already [B, K])
        K = shape[-1]
    M, K8, N = p["B_packed"].shape
    G = p["alpha"].shape[1]
    group_size = K // G
    bt, bn, bk = kops.pick_matmul_plan(B, K, N, G=G, group_size=group_size)
    vmem = bmk.tile_vmem_bytes_mm(bt, bn, bk, m=M)
    stats = LayerStats(
        in_shape=(B, K), out_shape=(B, N),
        macs=K * N,
        weight_bytes=int(p["B_packed"].size) + int(p["alpha"].size) * 4,
        vmem_bytes=vmem,
    )
    instr = LinearInstr(
        B_packed=p["B_packed"], alpha=p["alpha"], bias=_bias(p, N),
        name=spec.name, K=K, relu=spec.relu, pre=spec.pre, M=M,
        group_size=group_size, plan=TilePlan(bt=bt, bn=bn, bk=bk),
        stats=stats)
    return instr, stats.out_shape


def compile(params: dict, arch: str, quant: QuantConfig,
            input_shape: tuple[int, ...], *,
            verify: bool = False,
            golden: bool | int = True) -> BinArrayProgram:
    """Compile a network into a :class:`BinArrayProgram`.

    params:      fp tree (binarized here with ``quant``), a packed tree from
                 ``binarize_cnn_a`` / ``binarize_mobilenet`` (reused as-is),
                 or a legacy packed tree without ``B_tap_packed`` (upgraded).
    arch:        "cnn_a" | "mobilenet" — selects the LayerSpec list in
                 models/cnn.py (the single topology source of truth) — or
                 an explicit LayerSpec sequence (custom/fuzzed topologies;
                 the program's ``arch`` records "custom").
    quant:       packing config (M, algorithm, K_iters, group_size) plus the
                 compile-time knobs: ``m_active`` biases the VMEM plan,
                 ``conv_batch_tile`` / ``conv_vmem_budget`` override the
                 auto pick, ``interpret`` sets the program's default Pallas
                 interpret flag.
    input_shape: (B, H, W, C) the tile plans are optimized for.
    verify:      run ``repro.analysis.verify_program`` on the result and
                 raise :class:`~repro.analysis.ProgramVerificationError` on
                 any ERROR finding (Mosaic-illegal blocks, out-of-range
                 plans, VMEM overruns) before the program ever reaches a
                 TPU.  Off by default — the CLI gate
                 (``tools/verify_program.py``) covers the shipped programs.
    golden:      record a :class:`~repro.deploy.program.GoldenRecord` (the
                 compile-time BIST: seeded probe input executed once per
                 §IV-D rung, output digests frozen — see deploy/selftest.py).
                 True (default) uses seed 0; an int supplies the probe seed;
                 False skips it (e.g. multi-minute 224² compiles whose
                 callers never self-test).  Automatically skipped under
                 ``jax.eval_shape`` — abstract programs carry ``golden=None``.

    All scheduling (``pick_tile`` / ``pick_tile_dw`` / ``pick_matmul_plan``)
    happens HERE — ``execute`` runs zero plan picks inside its trace
    (``kernels.binary_conv.plan_pick_count`` proves it).
    """
    if len(input_shape) != 4:
        raise ValueError(f"input_shape must be (B, H, W, C): {input_shape}")
    specs = _specs(arch)
    shape: tuple[int, ...] = tuple(int(d) for d in input_shape)
    instrs = []
    for spec in specs:
        p = params[spec.name]
        if spec.kind == "conv":
            instr, shape = _compile_conv(spec, p, shape, quant)
        elif spec.kind == "dwconv":
            instr, shape = _compile_dwconv(spec, p, shape, quant)
        else:
            instr, shape = _compile_linear(spec, p, shape, quant)
        instrs.append(instr)
    program = BinArrayProgram(
        instrs=tuple(instrs),
        arch=arch if isinstance(arch, str) else "custom",
        input_shape=tuple(int(d) for d in input_shape),
        interpret=quant.interpret)
    log.info("compiled %s %s: conv paths %s", program.arch,
             program.input_shape, program.conv_paths())
    if golden is not False and not any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree_util.tree_leaves(program)):
        # deferred import: selftest pulls in the executor
        from repro.deploy.selftest import compute_golden

        seed = 0 if golden is True else int(golden)
        program = dataclasses.replace(
            program, golden=compute_golden(program, seed=seed))
    if verify:
        # deferred import: analysis depends on deploy.program, and pulling
        # the verifier in only when asked keeps plain compiles light
        from repro.analysis.verify import assert_verified

        assert_verified(program)
    return program


def abstract_program(arch: str, quant: QuantConfig,
                     input_shape: tuple[int, ...], *,
                     width_mult: float = 1.0,
                     n_classes: int = 1000) -> BinArrayProgram:
    """Compile without computing: init + binarize + plan under
    ``jax.eval_shape``.  The returned program carries the *real* frozen tile
    plans and LayerStats (they are static aux data) with ShapeDtypeStruct
    weight leaves — this is what the benchmarks and ``run.py --json``
    introspect, and the restore target for checkpoint round-trips."""

    def build(key):
        if arch == "cnn_a":
            p = cnn.init_cnn_a(key)
        else:
            p = cnn.init_mobilenet(key, width_mult=width_mult,
                                   n_classes=n_classes)
        return compile(p, arch, quant, input_shape)

    return jax.eval_shape(build, jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Checkpoint round-trip (checkpoint/manager.py)
# ---------------------------------------------------------------------------

def save_program(manager, step: int, program: BinArrayProgram, *,
                 extra: dict | None = None) -> str:
    """Persist a compiled program (packed weights; plans/stats ride in the
    pytree structure, which the restore target re-supplies).  The program's
    :class:`GoldenRecord` is serialized into the (digest-protected)
    manifest so :func:`load_program` can re-attach it even when the restore
    target is an abstract program with ``golden=None``."""
    meta = {"deploy": program.totals()}
    if program.golden is not None:
        meta["golden"] = program.golden.to_json()
    meta.update(extra or {})
    return manager.save(step, {"program": program}, extra=meta)


def _attach_golden(program: BinArrayProgram, extra) -> BinArrayProgram:
    """Re-attach the manifest's golden record when the restore target had
    none (`manager.restore` takes aux data from the target, not disk)."""
    if program.golden is None and isinstance(extra, dict) \
            and extra.get("golden"):
        return dataclasses.replace(
            program, golden=GoldenRecord.from_json(extra["golden"]))
    return program


class ProgramIntegrityError(ValueError):
    """A restored program failed static verification — a corrupt, truncated,
    or stale checkpoint that must not reach ``execute``.  Carries the ERROR
    :class:`~repro.analysis.verify.Finding`s as ``.findings``."""

    def __init__(self, message: str, findings=()):
        super().__init__(message)
        self.findings = tuple(findings)


def load_program(manager, step: int, like: BinArrayProgram, *,
                 verify: bool = True) -> BinArrayProgram:
    """Restore a program saved with :func:`save_program`.  ``like`` supplies
    the structure + plans — typically :func:`abstract_program` with the same
    arch/quant/input_shape (compilation is deterministic, so the treedefs
    match) or any same-shaped compiled program.

    By default the restored program is re-verified
    (``repro.analysis.verify_program``) and any ERROR finding raises
    :class:`ProgramIntegrityError` — a torn read, a truncated leaf, or a
    checkpoint from a stale layout fails loudly HERE, not as garbage logits
    (or an opaque Mosaic fault) at execute time.  ``verify=False`` opts out
    for hot loops that verify out of band (the fuzz tier compiles, verifies,
    and round-trips thousands of programs per run).
    """
    restored, extra = manager.restore(step, {"program": like})
    program = _attach_golden(restored["program"], extra)
    if verify:
        # deferred import, same reason as compile(verify=True)
        from repro.analysis.verify import verify_program

        errors = [f for f in verify_program(program)
                  if f.severity == "ERROR"]
        if errors:
            raise ProgramIntegrityError(
                f"restored program (step {step}) failed verification with "
                f"{len(errors)} ERROR finding(s):\n  "
                + "\n  ".join(str(f) for f in errors),
                findings=errors)
    return program


def load_latest_good(manager, like: BinArrayProgram, *, verify: bool = True,
                     selftest: bool = True):
    """Restore the newest checkpoint step whose program passes every gate.

    Wraps ``CheckpointManager.restore_latest_good``: the walk runs
    newest-first; any step failing digest verification, static verification
    (``verify``), or the golden self-test (``selftest``, when the saved
    program carries a :class:`GoldenRecord`) is quarantined with its reason
    and the walk continues.  Returns ``(step, program)``; raises
    :class:`~repro.checkpoint.manager.NoGoodCheckpoint` when every step is
    bad — a state the caller must handle loudly, not paper over.
    """
    def validate(restored, extra):
        program = _attach_golden(restored["program"], extra)
        if verify:
            from repro.analysis.verify import verify_program

            errors = [f for f in verify_program(program)
                      if f.severity == "ERROR"]
            if errors:
                raise ProgramIntegrityError(
                    f"restored program failed verification with "
                    f"{len(errors)} ERROR finding(s):\n  "
                    + "\n  ".join(str(f) for f in errors),
                    findings=errors)
        if selftest and program.golden is not None:
            from repro.deploy.selftest import self_test

            self_test(program)

    step, restored, extra = manager.restore_latest_good(
        {"program": like}, validate=validate)
    return step, _attach_golden(restored["program"], extra)
