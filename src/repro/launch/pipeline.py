"""Optional pipeline parallelism: GPipe-style microbatch pipeline on a
'pipe' mesh axis via shard_map + collective_permute (DESIGN.md §4).

Composable with the (data, model) mesh: stages hold contiguous layer blocks;
microbatches stream through stages with one collective_permute per tick
(fill + steady-state + drain = n_micro + n_stages - 1 ticks).

This module is self-contained (toy per-stage fn or a layer-stack closure) so
the mainline FSDP/TP path stays pipeline-free; it exists to prove the
communication schedule lowers and computes correctly (tests/test_pipeline.py
validates numerically against the unpipelined reference on 8 host devices).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline_mesh(n_pipe: int, n_data: int = 1):
    devs = jax.devices()
    assert len(devs) >= n_pipe * n_data, (len(devs), n_pipe, n_data)
    return jax.make_mesh((n_pipe, n_data), ("pipe", "data"))


def pipeline_apply(stage_fn, params_stacked, x, *, mesh: Mesh,
                   n_micro: int):
    """y = stage_{S-1}(...stage_0(x)) with stages sharded over 'pipe'.

    stage_fn(stage_params, h) -> h'
    params_stacked: pytree with leading dim n_stages (sharded over 'pipe').
    x: [B, ...] with B % n_micro == 0; batch microbatched and streamed.
    """
    n_stages = mesh.shape["pipe"]
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro

    def per_device(params_local, x_local):
        # params_local: stage slice [1, ...] -> this device's stage params
        stage_params = jax.tree.map(lambda t: t[0], params_local)
        stage_idx = jax.lax.axis_index("pipe")
        mbs = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        outs = jnp.zeros_like(mbs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            inject = jnp.where(t < n_micro, 1, 0)
            incoming = jnp.where(
                (stage_idx == 0) & (inject == 1),
                mbs[jnp.clip(t, 0, n_micro - 1)], buf)
            h = stage_fn(stage_params, incoming)
            # last stage emits microbatch (t - (n_stages-1))
            emit_idx = t - (n_stages - 1)
            outs = jax.lax.cond(
                (stage_idx == n_stages - 1) & (emit_idx >= 0),
                lambda o: o.at[jnp.clip(emit_idx, 0, n_micro - 1)].set(h),
                lambda o: o, outs)
            # rotate activations downstream: stage i -> stage i+1
            nxt = jax.lax.ppermute(
                h, "pipe",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs: zero elsewhere + psum
        outs = jnp.where(stage_idx == n_stages - 1, outs, 0)
        outs = jax.lax.psum(outs, "pipe")
        return outs.reshape(B, *x_local.shape[1:])

    fn = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(params_stacked, x)


def reference_apply(stage_fn, params_stacked, x):
    """Unpipelined ground truth: apply stages sequentially."""
    n_stages = jax.tree.leaves(params_stacked)[0].shape[0]
    h = x
    for i in range(n_stages):
        h = stage_fn(jax.tree.map(lambda t: t[i], params_stacked), h)
    return h
