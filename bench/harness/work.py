"""The work a forward pass needs, counted from a configuration's layer list.

This is the benchmark's own arithmetic: it reads only the configuration
file, so no change to the program can move it.  For every layer it gives

* ``macs``: fp-equivalent multiply-accumulates per image, independent of the
  number of binary levels and of how the levels are computed;
* ``least_bytes(batch, m)``: the fewest bytes one call of the layer's kernel
  has to move through HBM: the f32 activation in and out (and the f32 skip
  operand of an ``add``), ``m`` bits per weight, and the f32 alphas and
  bias.

A kernel's least time is the larger of ``2 * macs / peak FLOP/s`` and
``least bytes / peak bytes/s``.

The layer vocabulary.  ``layers`` is a list of entries, each with a unique
``name`` and a ``kind``: ``conv`` (``kh``, ``kw``, ``out``), ``dwconv``
(``kh``, ``kw``; one filter per input channel) or ``linear`` (``out``).
Every other key is optional; where it is absent, the layer reads the
previous layer's output and nothing else changes.

* ``src``: the name of an earlier layer, or ``"input"``, whose output the
  layer reads in place of the previous layer's.
* ``pre``: an op on that input before the layer: ``"flatten"`` or
  ``"gap"`` (global average pool).
* ``stride`` (1) and ``padding`` (``"VALID"``) of a conv or dwconv:
  ``"SAME"`` (XLA's: the odd element goes high), ``"VALID"``, or explicit
  ``[[top, bottom], [left, right]]``.
* ``pool``: a ``pool`` x ``pool`` max-pool of the conv output (1: none),
  after the bias.  By default its windows do not overlap and it has to
  divide the output; ``pool_stride`` and ``pool_padding`` (padded with
  -inf, in the form of ``padding``) make it overlap, as ResNet's 3 x 3
  pool at stride 2 padded 1.  Max commutes with the ReLU that follows.
* ``add``: the name of an earlier layer whose output is added to this
  layer's, after the bias and pool and before the ReLU; the two shapes must
  agree.
* ``relu`` (true): a ReLU last.

A layer's output, which ``src`` and ``add`` name, is its value after the
ReLU (or without it, where ``relu`` is false).
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
    n_add: int = 0             # skip operand's elements per image (``add``)

    def least_bytes(self, batch: int, m: int) -> int:
        act = batch * (_prod(self.in_shape) + _prod(self.out_shape)
                       + self.n_add) * F32
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


def window_out(H: int, W: int, k: tuple[int, int], stride: int,
               padding) -> tuple[int, int]:
    """Output rows and columns of a ``k`` window at ``stride`` over H x W
    under ``padding``: "SAME", "VALID" or [[top, bottom], [left, right]]."""
    if padding == "SAME":
        rows, cols = same_pads(H, k[0], stride), same_pads(W, k[1], stride)
    elif padding == "VALID":
        rows, cols = (0, 0), (0, 0)
    else:
        rows, cols = padding
    H, W = H + sum(rows), W + sum(cols)
    return (H - k[0]) // stride + 1, (W - k[1]) // stride + 1


def layers(config: dict) -> list[Layer]:
    """Walk the configuration's layer graph from its input shape."""
    x = (config["resolution"], config["resolution"], config["in_channels"])
    group = config.get("group_size")
    outs, shape, out = {}, x, []
    for spec in config["layers"]:
        name, kind = spec["name"], spec["kind"]
        if name in outs or name == "input":
            raise ValueError(f"layer name {name!r} is taken")
        if "src" in spec:
            src = spec["src"]
            if src != "input" and src not in outs:
                raise ValueError(f"{name}: src {src!r} is not an earlier "
                                 f"layer or 'input'")
            shape = x if src == "input" else outs[src]
        pre = spec.get("pre", "none")
        if pre == "flatten":
            shape = (_prod(shape),)
        elif pre == "gap":
            shape = (shape[-1],)
        elif pre != "none":
            raise ValueError(f"{name}: unknown pre {pre!r}")
        if kind in ("conv", "dwconv"):
            H, W, C = shape
            kh, kw, s = spec["kh"], spec["kw"], spec.get("stride", 1)
            U, V = window_out(H, W, (kh, kw), s, spec.get("padding", "VALID"))
            pool = spec.get("pool", 1)
            pool_stride = spec.get("pool_stride", pool)
            pool_padding = spec.get("pool_padding", "VALID")
            if (pool_stride, pool_padding) == (pool, "VALID") \
                    and (U % pool or V % pool):
                raise ValueError(f"{name}: {U}x{V} not divisible "
                                 f"by pool {pool}")
            D = spec["out"] if kind == "conv" else C
            K = kh * kw * (C if kind == "conv" else 1)
            macs, n_w, N = U * V * D * K, K * D, D
            new = window_out(U, V, (pool, pool), pool_stride,
                             pool_padding) + (D,)
        elif kind == "linear":
            (K,) = shape
            N = spec["out"]
            macs = n_w = K * N
            new = (N,)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        n_add = 0
        if "add" in spec:
            other = outs.get(spec["add"])
            if other is None:
                raise ValueError(f"{name}: add {spec['add']!r} is not an "
                                 f"earlier layer")
            if other != new:
                raise ValueError(f"{name}: add {spec['add']!r} has shape "
                                 f"{other}, the layer {new}")
            n_add = _prod(new)
        G = 1 if group is None else K // group
        out.append(Layer(name, kind, shape, new, macs, n_w, G * N, N, n_add))
        outs[name] = shape = new
    return out


def macs_per_image(config: dict) -> int:
    return sum(layer.macs for layer in layers(config))
