"""Seeded weights and images, and the program under test built from them.

Weights are float32, made on the device in one jitted call from the seed,
in the layout the program's compiler takes (conv HWIO, depth-wise
[kh, kw, 1, C], dense [K, N]).  The same tree feeds the program's compiler
and the plain reference, which binarizes it on its own.

The draw is put in general position for Algorithm 2: a weight whose sign
decision lies within ``TIE_EPS`` of a tie (its residual within ``TIE_EPS``
of its channel's mean |W| from zero) is pushed to twice that distance.  At
a tie either sign is a right answer, and which one float32 picks depends
on the order of a reduction; one weight of millions taking the other sign
moves every logit by up to 1e-3 of its size, which the comparison with the
reference could not tell from a fault.  About 0.1% of the weights move, by
2e-4 of their channel's scale.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

TIE_EPS = 1e-4      # distance from a tie, in units of the channel's mean |W|
TIE_ROUNDS = 4     # pushes at most


def key_of(seed: int, salt: int):
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    key = jax.random.PRNGKey(salt)
    seed = int(seed)
    for _ in range(3):
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def weight_shapes(config: dict) -> dict:
    """name -> (weight shape, bias length), walked from the input shape."""
    from harness.work import layers

    shapes = {}
    for spec, layer in zip(config["layers"], layers(config)):
        if spec["kind"] == "conv":
            C = layer.in_shape[-1]
            shapes[spec["name"]] = ((spec["kh"], spec["kw"], C, spec["out"]),
                                    spec["out"])
        elif spec["kind"] == "dwconv":
            C = layer.in_shape[-1]
            shapes[spec["name"]] = ((spec["kh"], spec["kw"], 1, C), C)
        else:
            shapes[spec["name"]] = ((layer.in_shape[0], spec["out"]),
                                    spec["out"])
    return shapes


def _fan_in(shape) -> int:
    return int(np.prod(shape[:-1]))


@functools.cache
def _make_params_fn(config_json: str):
    config = json.loads(config_json)
    init = config["assumed"]["init"]
    kinds = {s["name"]: s["kind"] for s in config["layers"]}
    shapes = weight_shapes(config)

    def make(key):
        from harness.reference import general_position

        params, near = {}, {}
        for i, (name, (wshape, nb)) in enumerate(shapes.items()):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            gain = init["gain"][kinds[name]]
            w = jax.random.normal(kw, wshape, jnp.float32) \
                * (gain / np.sqrt(_fan_in(wshape)))
            w2, near[name] = general_position(
                w.reshape(-1, wshape[-1]), config["M"], config["K_iters"],
                TIE_EPS, TIE_ROUNDS)
            params[name] = {
                "w": w2.reshape(wshape),
                "b": jax.random.normal(kb, (nb,), jnp.float32)
                * init["bias_std"]}
        return params, near

    return jax.jit(make)


def make_params(config: dict, seed: int) -> tuple[dict, int]:
    """The seeded weights, and how many of them are still within
    ``TIE_EPS`` of a tie (0 expected)."""
    params, near = _make_params_fn(json.dumps(config, sort_keys=True))(
        key_of(seed, 1))
    return params, int(sum(int(n) for n in near.values()))


@functools.cache
def _images_fn(shape: tuple[int, ...]):
    return jax.jit(lambda key: jax.random.normal(key, shape, jnp.float32))


def make_images(config: dict, seed: int, n: int, salt: int = 2) -> np.ndarray:
    """``n`` distinct images [n, R, R, C], made on the device, on the host."""
    shape = (n, config["resolution"], config["resolution"],
             config["in_channels"])
    return np.asarray(_images_fn(shape)(key_of(seed, salt)))


def quant_config(config: dict, interpret: bool):
    from repro.core.binlinear import QuantConfig

    return QuantConfig(mode="binary", M=config["M"],
                       algorithm=config["algorithm"],
                       K_iters=config["K_iters"],
                       group_size=config["group_size"], interpret=interpret)


@functools.cache
def _build_fn(config_json: str, batch: int, interpret: bool):
    from repro import deploy

    config = json.loads(config_json)
    shape = (batch, config["resolution"], config["resolution"],
             config["in_channels"])
    quant = quant_config(config, interpret)
    # under jit the compiler's golden record is skipped (it needs concrete
    # weights); the cells do not self-test
    return jax.jit(lambda params: deploy.compile(
        params, config["arch"], quant, input_shape=shape, golden=False))


def build_program(config: dict, params: dict, batch: int,
                  interpret: bool = False):
    """The compiled BinArrayProgram: binarization, packing and tile plans,
    in one jitted call, planned for ``batch`` images."""
    program = _build_fn(json.dumps(config, sort_keys=True), batch,
                        interpret)(params)
    names = [i.name for i in program.instrs]
    want = [s["name"] for s in config["layers"]]
    if names != want:
        raise ValueError(f"program layers {names} differ from the "
                         f"configuration's {want}")
    return program
