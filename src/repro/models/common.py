"""Shared model components: norms, RoPE, embeddings, sharded-linear glue.

All weight-bearing matmuls route through ``repro.core.binlinear`` so the
paper's multi-level binary approximation is a config switch on every layer
(DESIGN.md §5).  Activation sharding uses *logical* axis names resolved
against rules installed by the launcher (set_axis_rules); on CPU tests no
rules are installed and constraints are no-ops.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import binlinear as bl

_STATE = threading.local()


def set_axis_rules(rules: dict[str, tuple[str, ...] | str | None] | None,
                   axis_sizes: dict[str, int] | None = None):
    """Install logical->mesh axis rules (e.g. {'batch': ('pod','data')}).
    axis_sizes enables divisibility checks (a constraint that doesn't divide
    the dim is dropped rather than failing the partitioner)."""
    _STATE.rules = rules
    _STATE.axis_sizes = axis_sizes or {}


def get_axis_rules():
    return getattr(_STATE, "rules", None)


def _axes_size(axes, sizes: dict[str, int]) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return sizes.get(axes, 1)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without rules."""
    rules = get_axis_rules()
    if rules is None:
        return x
    sizes = getattr(_STATE, "axis_sizes", {})
    spec = []
    for i, name in enumerate(logical):
        axes = rules.get(name) if name else None
        if axes is not None and x.shape[i] % _axes_size(axes, sizes) != 0:
            axes = None  # dim not divisible -> leave unconstrained
        spec.append(axes)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rms_norm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def rms_norm_gated(params, x: jax.Array, z: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Mamba2's gated RMSNorm: norm(x * silu(z)) * scale."""
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, hd], positions: [B, S] (or [S]) -> rotated x."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                      # [B, S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Fixed sinusoidal embeddings (Whisper encoder positional stub)."""
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10_000 ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Linear / embedding (quantization-aware)
# ---------------------------------------------------------------------------

def init_linear(key, in_dim: int, out_dim: int, dtype, *, bias: bool = False):
    p = bl.init_linear(key, in_dim, out_dim, dtype=dtype)
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(params, x: jax.Array, quant: bl.QuantConfig = bl.DENSE) -> jax.Array:
    return bl.apply_linear(params, x, quant)


def layer_quant_cfg(cfg, idx: int):
    """Resolve a per-layer §IV-D quant schedule for decoder layer ``idx``.

    With ``cfg.quant.m_schedule`` set, returns ``cfg`` specialized to that
    layer's level count (entry ``idx``, last entry extended if the schedule
    is short); otherwise returns ``cfg`` unchanged.  ``idx`` counts global
    decoder layers — leading dense layers first, then the main stack — the
    same order ``deploy``'s per-instruction schedules use for CNNs.
    """
    sched = cfg.quant.m_schedule
    if sched is None:
        return cfg
    m = sched[idx] if idx < len(sched) else sched[-1]
    return cfg.replace(
        quant=cfg.quant.replace(m_active=int(m), m_schedule=None))


def init_embedding(key, vocab: int, dim: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


def embed(params, tokens: jax.Array) -> jax.Array:
    """Token embedding lookup.  Under explicitly sharded mesh axes the
    gather's output sharding is ambiguous (the table is sharded over both
    axes), so it is stated: the embeddings follow the tokens' sharding, with
    the feature dim unsharded, on the table's mesh."""
    table = params["table"]
    spec = P(*jax.typeof(tokens).sharding.spec, None)
    return table.at[tokens].get(
        out_sharding=jax.typeof(table).sharding.update(spec=spec))


def unembed(params, x: jax.Array) -> jax.Array:
    """Logits in fp32 (loss numerics)."""
    return x.astype(jnp.float32) @ params["table"].astype(jnp.float32).T


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def causal_mask(S: int, window: int | None = None) -> jax.Array:
    """[S, S] bool; True = attend.  window = sliding-window width."""
    q = jnp.arange(S)[:, None]
    k = jnp.arange(S)[None, :]
    m = k <= q
    if window is not None:
        m &= (q - k) < window
    return m


def softcap(logits: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)
