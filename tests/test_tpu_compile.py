"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

Each case lowers one instruction of a shipped program (CNN-A 48² and
MobileNet-B2 224², batch 8, M=2) through Mosaic at its frozen tile plan, at
m_active 2 and 1, and checks that the compiled module holds the Pallas
kernel.  Nothing runs: this is what the chip's compiler refuses or accepts
(block tiling, layouts, scoped VMEM), which interpret-mode tests cannot see.

The topology is described only inside the fixtures below, never while a
module is imported: only one process at a time may load the TPU library,
and it keeps it until it exits.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro import deploy
from repro.core.binlinear import QuantConfig
from repro.deploy.executor import _apply

PROGRAMS = {
    "cnn_a": ("cnn_a", (8, 48, 48, 3)),
    "b2": ("mobilenet", (8, 224, 224, 3)),
}

CASES = [
    ("cnn_a", "conv1"), ("cnn_a", "conv2"), ("cnn_a", "fc3"),
    ("b2", "stem"), ("b2", "dw1"), ("b2", "dw0"), ("b2", "pw0"),
    ("b2", "pw12"), ("b2", "head"),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def programs():
    qc = QuantConfig(mode="binary", M=2, K_iters=1, interpret=False)
    return {key: deploy.abstract_program(arch, qc, shape)
            for key, (arch, shape) in PROGRAMS.items()}


def _instr_and_input(program, name):
    """The named instruction and the activation shape it consumes."""
    shape = program.input_shape
    for instr in program.instrs:
        if instr.name == name:
            return instr, shape
        shape = instr.stats.out_shape
    raise KeyError(name)


@pytest.mark.parametrize("m", [2, 1])
@pytest.mark.parametrize("key,name", CASES)
def test_instruction_compiles_for_v5e(programs, one_chip, key, name, m):
    instr, shape = _instr_and_input(programs[key], name)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    args = (jax.tree_util.tree_map(on_chip, instr),
            on_chip(jax.ShapeDtypeStruct(shape, "float32")))
    compiled = jax.jit(
        lambda i, y: _apply(i, y, m, False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
