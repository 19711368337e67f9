"""Device time per program instruction: which instruction owns each op.

The program's executor runs every instruction under a
``binarray.iNN.<name>`` scope, and ``deploy.executor.op_owners`` maps each
op name of the compiled forward, as the trace prints it, to its owner:
``iNN.<name>``, ``input`` (the input's relayout) or ``unattributed``.
:func:`compiled` asks the program for that map once, after a traced
window, by compiling the window's forward again from shapes (the
persistent compilation cache normally holds it).  The rest is arithmetic
on the reduced trace (``harness.trace``), tested on a recorded one.

An op is an instruction's *kernel* when it is a call of one of the three
Pallas kernel families and an instruction owns it; every other op (pads,
slices, layout copies, weight prefetches, bias and ReLU fusions, the
input's relayout, ops no instruction owns) is *glue*.
"""
from __future__ import annotations

import json
import re
import time

from harness import trace as trace_mod
from harness.cell import CompileCounter, log

FAMILIES = ("binary_conv2d_pallas", "binary_dwconv2d_pallas",
            "binary_matmul_pallas")
_INSTR = re.compile(r"i\d+\.")


def compiled(ctx) -> dict[str, str] | None:
    """The owner of each op of the forward the traced window ran, or None
    where there is nothing to map: not a TPU (interpret mode has no kernel
    ops), more than one forward shape, or a program without
    ``op_owners``."""
    from repro.deploy import executor

    if (not ctx.device.startswith("/device:TPU:")
            or len(ctx.window.forward_batches) != 1
            or not hasattr(executor, "op_owners")):
        return None
    import jax
    from jax.sharding import SingleDeviceSharding

    from harness import model

    device_id = int(ctx.device.rsplit(":", 1)[1])
    device = next(d for d in jax.devices() if d.id == device_id)
    one = SingleDeviceSharding(device)

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    (batch,) = ctx.window.forward_batches
    config = ctx.config
    cfg = json.dumps(config, sort_keys=True)
    t0 = time.perf_counter()
    with CompileCounter() as counter:
        key = jax.ShapeDtypeStruct((2,), "uint32", sharding=one)
        params, _ = jax.eval_shape(model._make_params_fn(cfg), key)
        program = placed(jax.eval_shape(model._build_fn(cfg, batch, False),
                                        placed(params)))
        x = jax.ShapeDtypeStruct(
            (batch, config["resolution"], config["resolution"],
             config["in_channels"]), "float32", sharding=one)
        owners = executor.op_owners(program, x, ctx.m)
    log(f"op_owners: {time.perf_counter() - t0:.2f} s, {len(owners)} ops, "
        f"backend compiles {counter.n} (0: the forward came from the "
        f"persistent cache)")
    return owners


def is_kernel(op: str, owner: str) -> bool:
    """Whether ``op`` is the kernel call of the instruction ``owner``."""
    return op.split(".")[0] in FAMILIES and _INSTR.match(owner) is not None


def _clipped(trace, device):
    """(op name, start, end) of the device's ops, clipped to the window."""
    w0, w1 = trace.window()
    for e in trace.devices.get(device, []):
        if e.end_ns > w0 and e.start_ns < w1:
            yield (trace_mod.op_name(e.name).lstrip("%"),
                   max(e.start_ns, w0), min(e.end_ns, w1))


def per_owner(trace, device, owners: dict[str, str]) -> dict[str, list]:
    """Device time in the window per owner, in ns, as [kernel, glue]; an op
    the map lacks is ``unattributed``."""
    out: dict[str, list] = {}
    for op, s, e in _clipped(trace, device):
        owner = owners.get(op, "unattributed")
        out.setdefault(owner, [0.0, 0.0])[
            0 if is_kernel(op, owner) else 1] += e - s
    return out


def glue_pct(trace, device, owners: dict[str, str]) -> float:
    """Device time of glue ops in the window over device busy time, in %."""
    glue = trace_mod.union_ns(
        (s, e) for op, s, e in _clipped(trace, device)
        if not is_kernel(op, owners.get(op, "unattributed")))
    return 100.0 * sum(e - s for s, e in glue) / trace.busy_ns(device)


def log_costliest(table: dict[str, list], busy_ns: float,
                  top: int = 10) -> None:
    """The ``top`` owners by device time, split into kernel and glue, and
    the share of busy time that some owner other than ``unattributed``
    covers."""
    ranked = sorted(table.items(), key=lambda kv: -sum(kv[1]))[:top]
    log("device s per owner (kernel + glue): " + "; ".join(
        f"{o} {k * 1e-9:.3f} + {g * 1e-9:.3f}" for o, (k, g) in ranked))
    lost = sum(table.get("unattributed", [0.0, 0.0]))
    log(f"owners other than unattributed cover "
        f"{100.0 * (1.0 - lost / busy_ns):.3f}% of device busy time")
