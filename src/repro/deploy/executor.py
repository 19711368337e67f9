"""execute(): run a compiled BinArrayProgram — the accelerator side of §IV.

One jitted loop over the instruction stream.  Every scheduling decision
(tile plans, block sizes, padding resolution) was frozen at compile time, so
the trace contains zero auto-picks (``kernels.binary_conv.plan_pick_count``
is the proof hook) and the only per-call degrees of freedom are the input
batch and the §IV-D ``m_active`` level schedule:

  * ``m_active=None`` — every layer applies all of its packed levels;
  * ``m_active=k`` — the global runtime accuracy↔throughput switch, clamped
    per instruction to its packed M (identical numerics to the legacy
    ``QuantConfig(m_active=k)`` path);
  * ``m_active=[m0, m1, ...]`` — a per-layer schedule (one entry per
    instruction), the paper's per-layer generalization of §IV-D: early
    high-resolution layers can run fewer levels than the accuracy-critical
    back half without recompiling anything but this trace.

The schedule is static (level counts select packed buffer slices), so each
distinct schedule compiles once and is cached by ``jax.jit``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.deploy.program import (BinArrayProgram, ConvInstr, DWConvInstr,
                                  LinearInstr)
from repro.kernels import ops as kops
from repro.models.cnn import apply_pre

# Trace-entry accounting, the retrace twin of binary_conv.plan_pick_count:
# the body of _execute_jit bumps this only when jax.jit actually (re)traces,
# so repro.analysis.trace_lint can prove repeated identical traffic holds a
# bounded number of compiled variants (one per distinct m_active schedule).
_trace_entries = [0]


def trace_entry_count() -> int:
    """How many times the jitted execute body has been traced (process-wide)."""
    return _trace_entries[0]


def reset_trace_entry_count() -> None:
    _trace_entries[0] = 0


def cache_stats() -> dict:
    """Introspection for the soak/retrace harness: how many compiled
    variants the executor holds.  ``trace_entries`` is the process-wide
    trace counter above; ``jit_cache_entries`` is the live entry count of
    ``_execute_jit``'s jit cache (one entry per distinct (program treedef,
    input aval, schedule) triple)."""
    return {"trace_entries": _trace_entries[0],
            "jit_cache_entries": _execute_jit._cache_size()}


def cache_gauges() -> dict:
    """``name -> callable`` gauges for ``repro.testing.soak`` — each must
    stay exactly flat once a soak workload has seen all its variants."""
    return {"exec_trace_entries": lambda: float(_trace_entries[0]),
            "exec_jit_cache_entries": lambda: float(
                _execute_jit._cache_size())}


def _apply(instr, y: jax.Array, m: int, interpret: bool) -> jax.Array:
    y = apply_pre(instr.pre, y)
    if isinstance(instr, ConvInstr):
        return kops.binary_conv2d(
            y, instr.B_tap_packed, instr.alpha, instr.bias,
            kh=instr.kh, kw=instr.kw, stride=instr.stride,
            padding=instr.padding, pool=instr.pool, m_active=m,
            relu=instr.relu, bd=instr.plan.bd, bu=instr.plan.bu,
            nb=instr.plan.nb, interpret=interpret)
    if isinstance(instr, DWConvInstr):
        return kops.binary_dwconv2d(
            y, instr.B_tap_packed, instr.alpha, instr.bias,
            kh=instr.kh, kw=instr.kw, stride=instr.stride, m_active=m,
            relu=instr.relu, bu=instr.plan.bu, nb=instr.plan.nb,
            interpret=interpret)
    assert isinstance(instr, LinearInstr), instr
    out = kops.binary_matmul(
        y, instr.B_packed, instr.alpha, K=instr.K,
        group_size=instr.group_size, m_active=m,
        bt=instr.plan.bt, bn=instr.plan.bn, bk=instr.plan.bk,
        interpret=interpret)
    out = out + instr.bias.astype(out.dtype)
    return jax.nn.relu(out) if instr.relu else out


@functools.partial(jax.jit, static_argnames=("m_schedule", "interpret"))
def _execute_jit(program: BinArrayProgram, x: jax.Array,
                 m_schedule: tuple[int, ...], interpret: bool) -> jax.Array:
    _trace_entries[0] += 1          # runs at trace time only, not per call
    y = x
    for instr, m in zip(program.instrs, m_schedule):
        y = _apply(instr, y, m, interpret)
    return y


def _check_input(program: BinArrayProgram, x) -> None:
    """Validate ``x`` against ``program.input_shape`` BEFORE the jitted call,
    so a mis-shaped batch is a one-line ValueError naming both shapes instead
    of an opaque Mosaic/XLA shape fault from deep inside the first kernel.

    Only ``.shape``/``.dtype`` attributes are read (tracers and
    ShapeDtypeStructs pass through — trace_lint runs execute under
    ``jax.make_jaxpr``).  The batch dim is free by contract (the kernels
    clamp and stay bit-exact across tilings); rank, the per-image dims, and
    floating dtype are not.
    """
    want = tuple(program.input_shape)
    shape = tuple(getattr(x, "shape", ()))
    if len(shape) != len(want) or shape[1:] != want[1:]:
        raise ValueError(
            f"input shape {shape} does not match program "
            f"{program.arch!r}: expected (B,{','.join(map(str, want[1:]))}) "
            f"(compiled input_shape={want}; batch dim is free)")
    dtype = getattr(x, "dtype", None)
    if dtype is not None and not jnp.issubdtype(dtype, jnp.floating):
        raise ValueError(
            f"input dtype {dtype} is not floating; program "
            f"{program.arch!r} executes fp activations (cast the batch "
            "before execute)")


def execute(program: BinArrayProgram, x: jax.Array, m_active=None, *,
            interpret: bool | None = None) -> jax.Array:
    """Run the program on a batch.  x: [B, H, W, C] -> logits.

    ``m_active``: None | int | per-instruction sequence (see module doc);
    entries are clamped to each instruction's packed M.  ``interpret``
    overrides the program's compile-time Pallas interpret default (CPU
    validation vs TPU).  Raises ValueError when ``x`` does not match
    ``program.input_shape`` (any batch size, but rank/H/W/C/floating-dtype
    must agree — see :func:`_check_input`).
    """
    _check_input(program, x)
    sched = program.resolve_schedule(m_active)
    itp = program.interpret if interpret is None else interpret
    return _execute_jit(program, x, m_schedule=sched, interpret=itp)
