"""The reduction from a profiler trace to busy time, kernel time, idle
gaps and the per-layer metrics: on a hand-made trace with known answers,
and on a small trace recorded on a TPU v5e (vgg16 at 224x224, batch 512),
against a sweep-line recount."""
from pathlib import Path

import pytest

from chipbench import harness, trace
from chipbench.trace import Event

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = ROOT / "chipbench" / "fixtures" / "trace_events.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _hand_made():
    ms = 1_000_000
    return [
        Event(HOST, "python", "bench.window", 0, 100 * ms),
        Event(HOST, "python", "bench.step", 0, 40 * ms),
        Event(HOST, "python", "bench.step", 50 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "conv2d_rows.1", 5 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "fusion.3", 20 * ms, 10 * ms),   # overlaps
        Event(DEV, "XLA Ops", "conv2d_rows.2", 55 * ms, 30 * ms),
        Event(DEV, "XLA Ops", "copy.1", 95 * ms, 10 * ms),     # past the end
        Event(DEV, "Async XLA Ops", "copy-start", 0, 100 * ms),  # not an op
        Event(HOST, "python", "PjitFunction", 0, 100 * ms),    # not ours
    ]


def test_hand_made_trace():
    ev = _hand_made()
    lo, hi = trace.window(ev)
    assert (lo, hi) == (0, 100e6)
    # busy: [5, 30] + [55, 85] + [95, 100] ms
    assert trace.busy_s(ev, lo, hi, 1) == pytest.approx(0.060)
    assert trace.kernel_s(ev, lo, hi, "conv2d_rows", 1) == pytest.approx(0.050)
    assert trace.top_ops(ev, lo, hi)[0] == ["conv2d_rows.2", pytest.approx(0.03)]
    gaps = dict(trace.idle_gaps(ev, lo, hi))
    # gaps [0,5], [30,55], [85,95] ms, credited at their middles: 2.5 in
    # the first step, 42.5 between the steps, 90 where the second ends
    assert gaps["bench.step"] == pytest.approx(0.005)
    assert gaps["host"] == pytest.approx(0.035)
    # half of the busy time is spread over two chips
    assert trace.busy_s(ev, lo, hi, 2) == pytest.approx(0.030)


def test_op_names_are_the_hlo_instruction_names():
    assert trace.op_name("%conv2d_rows.22 = bf16[512,28,512,28] custom-call("
                         "bf16[512,30,512,30] %x)") == "conv2d_rows.22"
    assert trace.op_name("bench.step") == "bench.step"


def test_keep_drops_host_noise():
    kept = trace.keep(_hand_made())
    assert {e.name for e in kept} == {"bench.window", "bench.step",
                                      "conv2d_rows.1", "fusion.3",
                                      "conv2d_rows.2", "copy.1"}


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over sorted start/end points, counting open
    intervals: another way to the union than merging."""
    points = []
    for e in events:
        if trace.is_device_op(e):
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                points += [(s, 1), (t, -1)]
    busy, depth, last = 0.0, 0, None
    for x, d in sorted(points):
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy / 1e9


def test_recorded_trace():
    ev = trace.load_events(str(FIXTURE))
    lo, hi = trace.window(ev)
    busy = trace.busy_s(ev, lo, hi, 1)
    assert busy == pytest.approx(_sweep_busy(ev, lo, hi), rel=1e-12)
    assert 0 < busy <= (hi - lo) / 1e9
    conv = trace.kernel_s(ev, lo, hi, "conv2d_rows", 1)
    assert 0 < conv < busy
    steps = sum(e.name == "bench.step" for e in ev)
    assert sum(e.name.startswith("conv2d_rows") for e in ev
               if trace.is_device_op(e)) == 13 * steps
    gaps = trace.idle_gaps(ev, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo) / 1e9 - busy,
                                                    rel=1e-9)
    ctx = {"events": ev, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
           "busy_s": busy, "n_devices": 1}
    share = harness.load_reader("conv2d.busy_share")(ctx)
    assert share == pytest.approx(100 * conv / busy)
    idle = harness.load_reader("cnn.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - busy / ((hi - lo) / 1e9)))
