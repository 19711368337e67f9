"""Reduce a profiler trace to the events the per-layer metrics read.

``read(logdir)`` loads the newest ``*.xplane.pb`` that ``jax.profiler``
wrote under ``logdir`` and keeps two kinds of events:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, each named by its HLO text; a Pallas kernel's
  op is named after the function that called ``pallas_call``
  (``%binary_conv2d_pallas.14 = ... custom-call(...)``); on the CPU, which
  rehearsals use, the events of the XLA client's thread;
* host spans: the ``TraceAnnotation``s the benchmark itself opened
  (``window`` and the loop's ``SPANS``), from any line of the host plane.

Everything after that (busy time, kernel time, idle gaps) is plain
arithmetic on those lists, in :class:`Trace`, and is tested on a small
recorded trace in ``bench/tests``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""          # the op's string stats, joined: what a kernel
                            # family's pattern is searched in

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _stat_text(event) -> str:
    parts = []
    for key, value in event.stats:
        if isinstance(value, str):
            parts.append(f"{key}={value}")
    return " ".join(parts)


def read(logdir: str, spans) -> "Trace":
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns,
                              _stat_text(e)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        host.append(Event(e.name, e.start_ns, e.duration_ns))
                    elif line.name.startswith("tf_XLAPjRtCpuClient"):
                        # the CPU backend's ops, for rehearsals without a
                        # chip
                        devices.setdefault("/host:CPU", []).append(
                            Event(e.name, e.start_ns, e.duration_ns,
                                  _stat_text(e)))
    return Trace(devices=devices, host=host)


def op_name(name: str) -> str:
    """The HLO instruction's name: a TPU op event is named by its whole HLO
    text, ``%<name> = <shape> <opcode>(...)``."""
    return name.split(" = ", 1)[0]


def union_ns(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]
    host: list[Event]

    def window(self) -> tuple[float, float]:
        """The benchmark's ``window`` span: where the measured traffic ran."""
        spans = [e for e in self.host if e.name == "window"]
        if len(spans) != 1:
            raise ValueError(f"expected one 'window' span, found "
                             f"{len(spans)}")
        return spans[0].start_ns, spans[0].end_ns

    def ops(self, device: str) -> list[Event]:
        """The device's operations that start inside the window."""
        w0, w1 = self.window()
        return [e for e in self.devices.get(device, [])
                if w0 <= e.start_ns < w1]

    def busy_ns(self, device: str) -> float:
        """Union of the device's operation intervals, clipped to the
        window."""
        w0, w1 = self.window()
        clipped = [(max(e.start_ns, w0), min(e.end_ns, w1))
                   for e in self.devices.get(device, [])
                   if e.end_ns > w0 and e.start_ns < w1]
        return sum(e - s for s, e in union_ns(clipped))

    def idle_gaps(self, device: str) -> list[tuple[float, float]]:
        """Stretches of the window in which no operation ran on the device."""
        w0, w1 = self.window()
        busy = union_ns((max(e.start_ns, w0), min(e.end_ns, w1))
                        for e in self.devices.get(device, [])
                        if e.end_ns > w0 and e.start_ns < w1)
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        return gaps

    def host_activity(self, s: float, e: float) -> str:
        """What the host was doing over [s, e): the benchmark span (other
        than ``window``) that overlaps it most.  The loops open their spans
        one after another, so only the spans that start before ``e`` and
        end after ``s`` are looked at."""
        if not hasattr(self, "_spans"):
            self._spans = sorted((sp for sp in self.host
                                  if sp.name != "window"),
                                 key=lambda sp: sp.start_ns)
            self._starts = [sp.start_ns for sp in self._spans]
        best, best_overlap = "other", 0.0
        i = bisect.bisect_left(self._starts, e) - 1
        while i >= 0:
            span = self._spans[i]
            overlap = min(e, span.end_ns) - max(s, span.start_ns)
            if overlap > best_overlap:
                best, best_overlap = span.name, overlap
            if span.end_ns <= s:
                break
            i -= 1
        return best

    def kernel(self, device: str, name: str) -> list[Event]:
        """Operations in the window that are calls of the kernel ``name``:
        on a TPU, ops named ``%<name>.<n> = ... custom-call(...)``."""
        return [e for e in self.ops(device)
                if op_name(e.name).lstrip("%").split(".")[0] == name]

    def breakdown(self, device: str, top: int = 10) -> dict:
        """The ``breakdown`` of a traced result: device time per operation
        name, and idle time per host activity, largest first, in seconds."""
        per_op: dict[str, float] = {}
        for e in self.ops(device):
            name = op_name(e.name)
            per_op[name] = per_op.get(name, 0.0) + e.dur_ns
        per_host: dict[str, float] = {}
        for s, e in self.idle_gaps(device):
            what = self.host_activity(s, e)
            per_host[what] = per_host.get(what, 0.0) + (e - s)

        def ranked(d):
            return [[k, v * 1e-9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(per_op), "idle_gaps": ranked(per_host)}
