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

Each instruction runs under a ``jax.named_scope``, ``binarray.iNN.<name>``,
which only adds metadata to its ops: :func:`op_owners` reads it back from
the compiled forward to map every op a profiler trace names to its
instruction.
"""
from __future__ import annotations

import functools
import re

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
    for idx, (instr, m) in enumerate(zip(program.instrs, m_schedule)):
        with jax.named_scope(f"binarray.{_owner(idx, instr.name)}"):
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


# ---------------------------------------------------------------------------
# Which instruction owns each op of the compiled forward
# ---------------------------------------------------------------------------

def _owner(idx: int, name: str) -> str:
    return f"i{idx:02d}.{name}"


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w-]*)\(")
_OPERAND = re.compile(r"%([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"binarray\.(i\d+\.[^/]+)")
_INSTR_ARG = re.compile(r"program\.instrs\[(\d+)\]")
_NOT_OPS = frozenset({"parameter", "constant", "bitcast", "tuple"})


def _hlo_owners(text: str, names) -> dict[str, str]:
    """Owner of each op of the entry computation of compiled HLO ``text``
    (the ops a TPU runs one by one; a fusion's body is one op), for a
    program whose instructions are named ``names``.  See :func:`op_owners`
    for the rules."""
    owners: dict[str, str] = {}
    out: dict[str, str] = {}
    lines = text[text.index("\nENTRY "):].splitlines()[1:]
    for line in lines:
        if line.startswith("}"):
            break
        m = _HLO_OP.match(line)
        if m is None:
            continue
        op, rest = m.groups()
        code = _OPCODE.search(rest)
        meta = _OP_NAME.search(rest)
        op_name = meta.group(1) if meta else ""
        scope = _SCOPE.search(op_name)
        arg = _INSTR_ARG.match(op_name)
        if scope:
            owner = scope.group(1)
        elif arg:
            idx = int(arg.group(1))
            owner = _owner(idx, names[idx])
        elif op_name == "x":
            owner = "input"
        else:
            operands = _OPERAND.findall(
                rest[code.end():rest.index(")", code.end())])
            owner = next((owners[o] for o in operands
                          if owners.get(o, "unattributed") != "unattributed"),
                         "unattributed")
        owners[op] = owner
        if code.group(1) not in _NOT_OPS:
            out[op] = owner
    return out


def op_owners(program: BinArrayProgram, x, m_active=None) -> dict[str, str]:
    """Map each op of the compiled forward, named as a profiler trace names
    it (``"binary_conv2d_pallas.14"``, ``"pad.88"``), to the instruction
    that owns it, ``"iNN.<name>"``, for reducing a device trace to time per
    instruction.  ``x`` may be an array or a ``jax.ShapeDtypeStruct``, and
    so may the program's weights: the forward is lowered and compiled for
    the devices they name (or the default one), never run.

    An op's owner is, in this order: (a) the instruction whose
    ``binarray.iNN.<name>`` scope its ``op_name`` metadata holds; (b) the
    instruction whose weights it comes from (``op_name`` ``program.instrs[N]
    ...``: a layout copy of a weight); (c) ``"input"`` for the input's own
    relayout (``op_name="x"``); (d) the owner of its first operand that has
    one (weight prefetches, ``copy-start``/``copy-done``, carry no
    metadata); (e) ``"unattributed"``.  Parameters, constants, bitcasts and
    tuples are left out: they take no device time of their own.
    """
    sched = program.resolve_schedule(m_active)
    compiled = _execute_jit.lower(program, x, m_schedule=sched,
                                  interpret=program.interpret).compile()
    return _hlo_owners(compiled.as_text(), [i.name for i in program.instrs])
