"""The work a forward pass needs, counted from a configuration's layer list.

This is the benchmark's own arithmetic: it reads only the configuration
file, so no change to the program can move it.  For every layer it gives

* ``macs``: fp-equivalent multiply-accumulates per image, independent of the
  number of binary levels and of how the levels are computed;
* ``least_bytes(batch, m)``: the fewest bytes one call of the layer's kernel
  has to move through HBM: the f32 activation in and out, ``m`` bits per
  weight, and the f32 alphas and bias.

A kernel's least time is the larger of ``2 * macs / peak FLOP/s`` and
``least bytes / peak bytes/s``.
"""
from __future__ import annotations

import dataclasses

F32 = 4


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    kind: str                  # conv | dwconv | linear
    in_shape: tuple[int, ...]  # per image, after the layer's ``pre`` op
    out_shape: tuple[int, ...]  # per image, after pool
    macs: int                  # per image
    n_weights: int             # binary weights per level
    n_alpha: int               # f32 scales per level
    n_bias: int

    def least_bytes(self, batch: int, m: int) -> int:
        act = batch * (_prod(self.in_shape) + _prod(self.out_shape)) * F32
        weights = -(-m * self.n_weights // 8)
        return act + weights + (m * self.n_alpha + self.n_bias) * F32

    def least_s(self, batch: int, m: int, peak: dict) -> tuple[float, str]:
        """Least time of one call and which bound sets it."""
        t_flop = 2 * self.macs * batch / peak["flops"]
        t_byte = self.least_bytes(batch, m) / peak["bytes_per_s"]
        return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def same_pads(size: int, k: int, stride: int) -> tuple[int, int]:
    """XLA's SAME padding of one spatial dim: the odd element goes high."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def layers(config: dict) -> list[Layer]:
    """Walk the configuration's layer list from its input shape."""
    shape = (config["resolution"], config["resolution"], config["in_channels"])
    group = config.get("group_size")
    out = []
    for spec in config["layers"]:
        kind = spec["kind"]
        pre = spec.get("pre", "none")
        if pre == "flatten":
            shape = (_prod(shape),)
        elif pre == "gap":
            shape = (shape[-1],)
        if kind in ("conv", "dwconv"):
            H, W, C = shape
            kh, kw, s = spec["kh"], spec["kw"], spec.get("stride", 1)
            if spec.get("padding", "VALID") == "SAME":
                H += sum(same_pads(H, kh, s))
                W += sum(same_pads(W, kw, s))
            U, V = (H - kh) // s + 1, (W - kw) // s + 1
            pool = spec.get("pool", 1)
            if U % pool or V % pool:
                raise ValueError(f"{spec['name']}: {U}x{V} not divisible "
                                 f"by pool {pool}")
            D = spec["out"] if kind == "conv" else C
            K = kh * kw * (C if kind == "conv" else 1)
            macs = U * V * D * K
            n_w = K * D
            G = 1 if group is None else K // group
            new = (U // pool, V // pool, D)
            out.append(Layer(spec["name"], kind, shape, new, macs, n_w,
                             G * D, D))
        elif kind == "linear":
            (K,) = shape
            N = spec["out"]
            G = 1 if group is None else K // group
            new = (N,)
            out.append(Layer(spec["name"], kind, shape, new, K * N, K * N,
                             G * N, N))
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        shape = new
    return out


def macs_per_image(config: dict) -> int:
    return sum(layer.macs for layer in layers(config))
