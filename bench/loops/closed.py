"""The closed loop: one caller, offline.

Each step copies a host batch to the device (``jax.device_put``), runs
``deploy.execute`` and fetches the logits to the host; the next step starts
when the logits are there.  Traffic keys: ``batch`` images per step, from a
pool of ``pool_batches`` distinct batches cycled in order, at the schedule
``m_active`` (null: every packed level).  It reports ``images_per_s``:
images whose logits reached the host, over the whole window.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import model
from harness.spec import Window

SPANS = ("device_put", "execute", "fetch_logits")


@dataclasses.dataclass
class State:
    program: object
    m_active: int | None
    images: np.ndarray
    batch: int
    device: object


def prepare(program, config: dict, traffic: dict, seed: int, device):
    import jax

    from repro import deploy

    batch = traffic["batch"]
    images = model.make_images(config, seed, traffic["pool_batches"] * batch)
    state = State(program, traffic["m_active"], images, batch, device)
    for i in range(2):          # warm: transfer, execute, fetch
        x = images[i * batch:(i + 1) * batch]
        np.asarray(deploy.execute(program, jax.device_put(x, device),
                                  state.m_active))
    return state


def window(state: State, seconds: float, span) -> Window:
    import jax

    from repro import deploy

    batch, images = state.batch, state.images
    n_pool = len(images) // batch
    answers = []
    clock = time.perf_counter
    t0 = clock()
    while clock() - t0 < seconds:
        i = len(answers) % n_pool
        idx = np.arange(i * batch, (i + 1) * batch)
        with span("device_put"):
            x = jax.device_put(images[idx[0]:idx[-1] + 1], state.device)
        with span("execute"):
            y = deploy.execute(state.program, x, state.m_active)
        with span("fetch_logits"):
            answers.append((idx, np.asarray(y)))
    elapsed = clock() - t0
    n = len(answers) * batch
    return Window(images=images, answers=answers, attempted=len(answers),
                  failed=0, elapsed_s=elapsed,
                  metrics={"images_per_s": n / elapsed},
                  forward_batches={batch: len(answers)}, answered_images=n,
                  notes={"steps": len(answers), "images": n,
                         "images/s": n / elapsed})
