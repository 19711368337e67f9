"""glue_pct and the device time per instruction, on the recorded CNN-A trace
(``data/cnna_trace_tpu.json``, two closed-loop steps of cnna_offline_b256
on a TPU v5e) and the owner of each of its ops
(``data/cnna_owners_tpu.json``, ``deploy.executor.op_owners`` of the same
forward compiled for a described v5e)."""
import json
import types

import pytest

from harness import owners, spec
from harness.trace import Event, Trace

DEV = "/device:TPU:0"
DATA = spec.BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def recorded():
    doc = json.loads((DATA / "cnna_trace_tpu.json").read_text())
    tr = Trace(devices={d: [Event(*e) for e in evs]
                        for d, evs in doc["devices"].items()},
               host=[Event(*e) for e in doc["host"]])
    table = json.loads((DATA / "cnna_owners_tpu.json").read_text())
    return tr, table["owners"]


def test_every_recorded_op_has_an_owner(recorded):
    tr, table = recorded
    ops = {e.name.split(" = ")[0].lstrip("%") for e in tr.devices[DEV]}
    assert ops <= set(table)
    assert "unattributed" not in {table[op] for op in ops}
    split = owners.per_owner(tr, DEV, table)
    assert set(split) == {"input", "i00.conv1", "i01.conv2", "i02.fc1",
                          "i03.fc2", "i04.fc3"}


def test_glue_pct_is_a_hand_sum(recorded):
    """Every op but the five kernel calls of each step is glue: the
    input's relayout, the pads of weights and activations, the slice and
    reshape before the dense layers, the bias and ReLU fusions."""
    tr, table = recorded
    w0, w1 = tr.window()
    evs = [e for e in tr.devices[DEV] if w0 <= e.start_ns and e.end_ns <= w1]
    assert len(evs) == len(tr.devices[DEV])      # all inside the window
    kernels = [e for e in evs if e.name.startswith("%binary_")]
    assert len(kernels) == 10                    # 5 layers, 2 steps
    glue = sum(e.dur_ns for e in evs) - sum(e.dur_ns for e in kernels)
    got = owners.glue_pct(tr, DEV, table)
    assert got == pytest.approx(100 * glue / tr.busy_ns(DEV))
    assert 0 < got < 100


def test_per_owner_split_adds_up_to_busy_time(recorded):
    tr, table = recorded
    split = owners.per_owner(tr, DEV, table)
    assert split["input"][0] == 0                # the input owns no kernel
    assert all(k > 0 for o, (k, g) in split.items() if o != "input")
    total = sum(k + g for k, g in split.values())
    assert total == pytest.approx(tr.busy_ns(DEV))
    glue = sum(g for k, g in split.values())
    assert 100 * glue / total == pytest.approx(
        owners.glue_pct(tr, DEV, table))


def test_an_op_no_instruction_owns_is_glue(recorded):
    tr, table = recorded
    partial = {op: o for op, o in table.items()
               if op != "binary_conv2d_pallas.2"}
    split = owners.per_owner(tr, DEV, partial)
    assert split["unattributed"][0] == 0 and split["unattributed"][1] > 0
    assert owners.glue_pct(tr, DEV, partial) > owners.glue_pct(
        tr, DEV, table)


def test_nothing_to_read_off_the_chip():
    ctx = types.SimpleNamespace(
        device="/host:CPU",
        window=types.SimpleNamespace(forward_batches={4: 3}))
    assert owners.compiled(ctx) is None
    assert spec.reader("glue_pct")(ctx) is None
