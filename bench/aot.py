"""Compile every cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/aot.py [workload ...]

For each cell of BENCHMARK.json (or the ones named) it compiles, for one
chip of a described ``v5e:2x2``: the build (binarization and packing), the
program's forward at the cell's batch and schedule, and the plain
reference.  The TPU compiler then refuses here what it would refuse on the
chip (tiling, layouts, scoped VMEM, memory).  Nothing runs, so this says
nothing about results or times.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import model, reference, spec
    from repro.deploy import executor

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    doc = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in doc["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = spec.load(w["name"])
        config, traffic = cell.config, cell.traffic
        batch = traffic["batch"]
        cfg = json.dumps(config, sort_keys=True)
        t0 = time.perf_counter()
        key = jax.ShapeDtypeStruct((2,), "uint32", sharding=one)
        params, _ = jax.eval_shape(model._make_params_fn(cfg), key)
        params = placed(params)
        model._make_params_fn(cfg).lower(key).compile()
        build = model._build_fn(cfg, batch, False)
        build.lower(params).compile()
        program = jax.eval_shape(build, params)
        m = traffic["m_active"]
        sched = program.resolve_schedule(m)
        x = jax.ShapeDtypeStruct(
            (batch, config["resolution"], config["resolution"],
             config["in_channels"]), "float32", sharding=one)
        fwd = executor._execute_jit.lower(
            placed(program), x, m_schedule=sched, interpret=False).compile()
        mem = fwd.memory_analysis()
        m_ref = config["M"] if m is None else m
        weights, ref = reference.compiled(cfg, m_ref, "highest")
        weights.lower(params).compile()
        q = placed(jax.eval_shape(weights, params))
        ref.lower(q, x).compile()
        print(f"{w['name']}: compiled for {topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.1f} s; forward at batch {batch}, "
              f"schedule {sched}: temp {mem.temp_size_in_bytes} B, args "
              f"{mem.argument_size_in_bytes} B", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
