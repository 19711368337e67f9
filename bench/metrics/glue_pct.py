"""glue_pct: share (%) of device busy time in the traced window spent in
ops that are not an instruction's own kernel call: XLA's pads, slices and
layout copies between the kernels, weight prefetches, bias and ReLU
fusions, the input's relayout, and ops no instruction owns
(``harness.owners``).  Where the program cannot say which instruction owns
an op (a program without ``deploy.executor.op_owners``), nothing is read.
Logs the ten costliest owners' device seconds, kernel and glue."""
from harness import owners


def read(ctx):
    table = owners.compiled(ctx)
    if not table:
        return None
    busy = ctx.trace.busy_ns(ctx.device)
    if busy <= 0:
        return None
    owners.log_costliest(owners.per_owner(ctx.trace, ctx.device, table), busy)
    return owners.glue_pct(ctx.trace, ctx.device, table)
