"""Find a cell's files by the names in BENCHMARK.json, and the chip it runs on.

* ``BENCHMARK.json`` (the repository root): the cell, its configuration's
  file, and the metrics it reports;
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters, among
  them ``loop``, the name of the loop that drives it;
* ``bench/loops/<loop>.py``: a loop (see :func:`loop`);
* ``bench/limits/<workload>.json``: the limit of each number the
  correctness check compares;
* ``bench/peaks.json``: the chip's peaks, by JAX's ``device_kind``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Window:
    """What a loop's window served, for the harness to check and report."""
    images: np.ndarray          # the host images the answers index
    answers: list               # (image indices, logits) pairs, as served
    attempted: int              # requests (a closed loop: batches) sent
    failed: int                 # requests that got no answer
    elapsed_s: float
    metrics: dict               # end-to-end values, by the cell's names
    forward_batches: dict       # batch size -> forward passes it ran
    answered_images: int
    notes: dict = dataclasses.field(default_factory=dict)  # logged only


class NoChip(RuntimeError):
    """The run found no accelerator it may measure on."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]      # the metrics this cell reports, by kind
    per_layer: list[dict]


def _reports(metric: dict, name: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load(workload: str, root: Path = ROOT) -> Cell:
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in doc["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    bench = root / "bench"
    traffic = json.loads(
        (bench / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in doc["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in doc["per_layer"]
                 if _reports(m, workload, e2e_names)]
    return Cell(cell, config, traffic, limits, e2e, per_layer)


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json "
                     f"({sorted(table)})")
    return table[kind]


def chips(count: int) -> list:
    """The first ``count`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} TPU chips, JAX found "
                     f"{len(devices)}")
    return devices[:count]


def _module(kind: str, name: str):
    key = f"bench_{kind}_" + name.replace(".", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        spec.loader.exec_module(module)
    return sys.modules[key]


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return _module("metrics", metric).read


def loop(name: str):
    """The module ``bench/loops/<name>.py``.  A loop has

    * ``SPANS``: the names of the host spans it opens, which the trace's
      idle gaps are attributed to;
    * ``prepare(program, config, traffic, seed, device) -> state``: makes
      its inputs from the seed and warms up every shape the window uses;
    * ``window(state, seconds, span) -> Window``: drives the program for
      ``seconds``; ``span(name)`` opens a host span.
    """
    return _module("loops", name)
