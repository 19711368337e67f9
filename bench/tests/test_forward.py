"""The reference's forward pass along a layer graph, on the CPU: against an
independent float64 NumPy forward (explicit im2col) on the tiny ResNet,
and on the chain configurations against the logits it gave before it
walked a graph."""
import json

import jax
import numpy as np
import pytest

from harness import model, reference
from test_work import DATA, config, fixture

# float32 against float64 over about ten layers reads about 1e-6
LIMIT = 1e-5


def rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def np_pads(padding):
    if padding == "VALID":
        return (0, 0), (0, 0)
    if isinstance(padding, str):
        raise ValueError(f"the NumPy forward takes explicit pads, not "
                         f"{padding!r}")
    return tuple(tuple(p) for p in padding)


def np_windows(x, k, stride, padding, fill):
    """Every k x k window of x [B, H, W, C] padded with ``fill``:
    [B, U, V, k * k, C]."""
    (t, b), (lo, hi) = np_pads(padding)
    x = np.pad(x, ((0, 0), (t, b), (lo, hi), (0, 0)), constant_values=fill)
    U = (x.shape[1] - k[0]) // stride + 1
    V = (x.shape[2] - k[1]) // stride + 1
    return np.stack([x[:, i:i + stride * (U - 1) + 1:stride,
                       j:j + stride * (V - 1) + 1:stride]
                     for i in range(k[0]) for j in range(k[1])], axis=3)


def np_forward(params, x, config, add_after_relu=False):
    """The configuration's forward in float64: conv as im2col times the
    [kh * kw * C, D] weight matrix, the skip add before the ReLU (after it,
    with ``add_after_relu``)."""
    y, outs = x.astype(np.float64), {}
    for s in config["layers"]:
        w = np.asarray(params[s["name"]]["w"], np.float64)
        b = np.asarray(params[s["name"]]["b"], np.float64)
        if "src" in s:
            y = x.astype(np.float64) if s["src"] == "input" else outs[s["src"]]
        pre = s.get("pre")
        if pre == "gap":
            y = y.mean(axis=(1, 2))
        elif pre is not None:
            raise ValueError(f"the NumPy forward has no pre {pre!r}")
        if s["kind"] == "linear":
            y = y @ w + b
        elif s["kind"] == "conv":
            cols = np_windows(y, (s["kh"], s["kw"]), s.get("stride", 1),
                              s.get("padding", "VALID"), 0.0)
            n, u, v = cols.shape[:3]
            y = cols.reshape(n, u, v, -1) @ w.reshape(-1, w.shape[-1]) + b
            k = s.get("pool", 1)
            if k > 1:
                y = np_windows(y, (k, k), s.get("pool_stride", k),
                               s.get("pool_padding", "VALID"),
                               -np.inf).max(axis=3)
        else:
            raise ValueError(f"the NumPy forward has no {s['kind']}")
        skip = outs[s["add"]] if "add" in s else 0.0
        if not add_after_relu:
            y = y + skip
        if s.get("relu", True):
            y = np.maximum(y, 0.0)
        if add_after_relu:
            y = y + skip
        outs[s["name"]] = y
    return y


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
def test_tiny_resnet_matches_numpy(seed):
    """The skip operand, explicit pads, the overlapping max-pool and ``src``
    as a float64 NumPy forward reads them; the same forward with the add
    after the ReLU is more than 100 times the limit away, so the comparison
    sees where the add goes."""
    cfg = fixture("tiny_resnet")
    params, _ = model.make_params(cfg, seed)
    x = model.make_images(cfg, seed, 4)
    got = np.asarray(jax.jit(lambda p, x: reference.forward(
        p, x, cfg, "highest"))(params, x))
    want = np_forward(params, x, cfg)
    assert rel_err(got, want) < LIMIT
    wrong = np_forward(params, x, cfg, add_after_relu=True)
    assert rel_err(wrong, want) > 100 * LIMIT


def test_weights_take_the_kind_gain():
    """Every layer's weights are drawn at its kind's gain from
    ``assumed.init``: no layer of the tiny ResNet sets its own."""
    cfg = fixture("tiny_resnet")
    params, _ = model.make_params(cfg, 5)
    for s in cfg["layers"]:
        w = np.asarray(params[s["name"]]["w"])
        gain = np.std(w) * np.sqrt(np.prod(w.shape[:-1]))
        want = cfg["assumed"]["init"]["gain"][s["kind"]]
        assert gain == pytest.approx(want, rel=0.3), s["name"]


@pytest.mark.parametrize("load,name", [(fixture, "tiny_mobilenet"),
                                       (config, "cnn_a")],
                         ids=["tiny_mobilenet", "cnn_a"])
def test_chain_logits_are_what_they_were(load, name):
    """Seeded weights and images through the reference give the logits they
    gave before the graph keys existed (``data/chain_logits.json``), to
    float32 rounding of the largest."""
    doc = json.loads((DATA / "chain_logits.json").read_text())
    cfg = load(name)
    params, near = model.make_params(cfg, doc["seed"])
    assert near == 0
    x = model.make_images(cfg, doc["seed"], 4)
    got = reference.logits(params, x, cfg, cfg["M"], block=4)
    assert rel_err(got, np.asarray(doc["logits"][name])) <= 1e-7
