"""Reduction of a JAX profiler trace to the numbers the benchmark reports:
device busy time, the time of named kernels, and the idle gaps with what
the host was doing in them.

A trace is first flattened to plain events ``(plane, line, name, start_ns,
dur_ns)``, so the reduction can be checked on a small recorded fixture
(``fixtures/trace_events.json``) without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"      # the benchmark's annotation of the window
BENCH_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(logdir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    prof = ProfileData.from_file(paths[-1])
    return [Event(plane.name, line.name, op_name(ev.name), ev.start_ns,
                  ev.duration_ns)
            for plane in prof.planes for line in plane.lines
            for ev in line.events]


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%conv2d_rows.22 = bf16[...] custom-call(...)``): keep the
    instruction's name (``conv2d_rows.22``)."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def keep(events: list[Event]) -> list[Event]:
    """The events the reduction reads: device ops and the benchmark's own
    host annotations."""
    return [e for e in events if is_device_op(e)
            or (e.plane.startswith(HOST_PLANE)
                and e.name.startswith(BENCH_PREFIX))]


def load_events(path: str) -> list[Event]:
    """Events saved as a JSON list of ``[plane, line, name, start_ns,
    dur_ns]`` rows, as the recorded fixture is."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def is_device_op(e: Event) -> bool:
    return e.plane.startswith(DEVICE_PLANE) and e.line == OPS_LINE


def window(events: list[Event]) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the benchmark's window annotation."""
    spans = [e for e in events if e.name == WINDOW
             and e.plane.startswith(HOST_PLANE)]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_ops(events: list[Event], lo: float, hi: float) -> dict:
    """``{device plane: [ops clipped to the window]}``."""
    out: dict[str, list[Event]] = defaultdict(list)
    for e in events:
        if is_device_op(e) and e.end_ns > lo and e.start_ns < hi:
            out[e.plane].append(e)
    return out


def busy_s(events: list[Event], lo: float, hi: float,
           n_devices: int) -> float:
    """Seconds in the window in which some op ran, averaged over the
    ``n_devices`` chips used (a chip with no op counts as idle)."""
    per = device_ops(events, lo, hi)
    total = sum(e - s for ops in per.values() for s, e in
                merge(_clip([(o.start_ns, o.end_ns) for o in ops], lo, hi)))
    return total / n_devices / 1e9


def kernel_s(events: list[Event], lo: float, hi: float, prefix: str,
             n_devices: int) -> float:
    """Device seconds of the ops whose name starts with ``prefix``,
    summed over the window and averaged over the chips used."""
    total = sum(e - s for ops in device_ops(events, lo, hi).values()
                for s, e in _clip([(o.start_ns, o.end_ns) for o in ops
                                   if o.name.startswith(prefix)], lo, hi))
    return total / n_devices / 1e9


def top_ops(events: list[Event], lo: float, hi: float, n: int = 10):
    """``[[op name, device seconds], ...]``: the ops that took most time
    in the window, summed over the chips."""
    acc: dict[str, float] = defaultdict(float)
    for ops in device_ops(events, lo, hi).values():
        for o in ops:
            acc[o.name] += (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: list[Event], lo: float, hi: float, n: int = 10):
    """``[[host annotation, seconds], ...]``: the device's idle time in
    the window (on its first chip), each gap credited to the innermost
    ``bench.*`` annotation open on the host at the gap's middle
    (``host`` where none is)."""
    per = device_ops(events, lo, hi)
    if not per:
        return [["host", (hi - lo) / 1e9]]
    ops = per[sorted(per)[0]]
    busy = merge(_clip([(o.start_ns, o.end_ns) for o in ops], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # the annotations nest, so a stack swept along the gaps holds the
    # open ones, innermost on top
    notes = sorted((e for e in events if e.plane.startswith(HOST_PLANE)
                    and e.name.startswith(BENCH_PREFIX)
                    and e.name != WINDOW), key=lambda e: e.start_ns)
    acc: dict[str, float] = defaultdict(float)
    stack: list[Event] = []
    j = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while j < len(notes) and notes[j].start_ns <= mid:
            while stack and stack[-1].end_ns <= notes[j].start_ns:
                stack.pop()
            stack.append(notes[j])
            j += 1
        while stack and stack[-1].end_ns <= mid:
            stack.pop()
        acc[stack[-1].name if stack else "host"] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
