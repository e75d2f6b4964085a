"""Device time credited to the program's own names (``chipbench.attribution``
and the readers built on it): on hand-made events with known answers, on a
small DSE trace recorded on a TPU v5e with the program's annotations
(dse.table3), and, for the readers that were there before, unchanged when
the program's spans are kept beside the benchmark's."""
from pathlib import Path

import pytest

from chipbench import attribution, harness, trace
from chipbench.trace import Event

ROOT = Path(__file__).resolve().parents[2]
VGG_FIXTURE = ROOT / "chipbench" / "fixtures" / "trace_events.json"
DSE_FIXTURE = ROOT / "chipbench" / "fixtures" / "dse_table3_events.json"
DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1_000_000


def _ctx(events, **kw):
    lo, hi = trace.window(events)
    return {"events": events, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
            "busy_s": trace.busy_s(events, lo, hi, 1), "n_devices": 1, **kw}


def _dse_hand_made():
    host = [("bench.window", 0, 100), ("campaign", 0, 50),
            ("campaign", 50, 100), ("screen.jax", 0, 20),
            ("screen.tables", 0, 12), ("screen.call", 12, 20),
            ("screen.jax", 50, 66), ("screen.tables", 50, 60),
            ("screen.call", 60, 66), ("cell.eval", 20, 30),
            ("cell.eval", 30, 45), ("cell.eval", 66, 90),
            ("search.full_eval", 21, 25), ("search.full_eval", 26, 28),
            ("search.full_eval", 31, 40), ("search.full_eval", 70, 80),
            ("screen.tables", 120, 130)]                  # past the window
    dev = [(DEV, 13, 15), (DEV, 14, 17), (DEV, 19, 22), (DEV, 61, 63),
           (DEV, 70, 71), (DEV1, 12, 20)]                 # a second chip
    return ([Event(HOST, "python", n, s * MS, (e - s) * MS)
             for n, s, e in host]
            + [Event(p, "XLA Ops", f"fusion.{i}", s * MS, (e - s) * MS)
               for i, (p, s, e) in enumerate(dev)])


@pytest.mark.parametrize("metric,want", [
    # screen.tables of 12 and 10 ms in the window
    ("dse.screen_tables_ms", 11.0),
    # first chip busy [13, 17] + [19, 20] + [61, 63] inside screen.call
    ("dse.screen_device_ms", 3.5),
    # full evaluations of 4 + 2 + 9 + 10 ms over three cells
    ("dse.full_eval_ms", 25 / 3),
])
def test_dse_readers_on_hand_made_events(metric, want):
    ev = _dse_hand_made()
    assert harness.load_reader(metric)(_ctx(ev)) == pytest.approx(want)
    # a program without these spans (the parent's) reads nothing
    bare = [e for e in ev if e.name not in attribution.PROGRAM_SPANS]
    assert harness.load_reader(metric)(_ctx(bare)) is None


SCOPES = {
    "conv2d_rows.1": "jit(f)/conv1/jit(conv2d_same)/conv2d_rows/pallas_call",
    "pad_bitcast_fusion.1": "jit(f)/conv1/jit(conv2d_same)/transpose",
    "conv2d_rows.2": "jit(f)/conv2/jit(conv2d_same)/conv2d_rows/pallas_call",
    "maximum_bitcast_fusion.2": "jit(f)/conv2/jit(relu)/max",
    "reduce_window_max.3": "jit(f)/pool3/reduce_window_max",
    "copy.1": "x",
}


def _cnn_hand_made():
    ops = [("conv2d_rows.1", 0, 30), ("pad_bitcast_fusion.1", 30, 40),
           ("conv2d_rows.2", 40, 80), ("maximum_bitcast_fusion.2", 80, 85),
           ("reduce_window_max.3", 85, 90), ("copy-done.4", 90, 95)]
    return ([Event(HOST, "python", "bench.window", 0, 100 * MS)]
            + [Event(DEV, "XLA Ops", n, s * MS, (e - s) * MS)
               for n, s, e in ops])


@pytest.mark.parametrize("metric,want", [
    # the conv layers' other ops, 10 + 5 ms, over 95 ms busy (not pool3)
    ("conv2d.wrapper_share", 100 * 15 / 95),
    # conv1: 3 ms of roofline over 30 ms of kernel; conv2: 20 over 40
    ("conv2d.worst_layer_roofline", 10.0),
])
def test_cnn_readers_on_hand_made_events(metric, want):
    ctx = _ctx(_cnn_hand_made(), op_scopes=SCOPES,
               conv_roofline_s=[0.003, 0.020])
    assert harness.load_reader(metric)(ctx) == pytest.approx(want)
    # unscoped ops (the parent's program) or no map: nothing to read
    unscoped = {k: "jit(f)/jit(conv2d_same)/transpose" for k in SCOPES}
    for scopes in (unscoped, None):
        ctx["op_scopes"] = scopes
        assert harness.load_reader(metric)(ctx) is None


def test_layer_times_and_table():
    ev = _cnn_hand_made()
    lo, hi = trace.window(ev)
    t = attribution.layer_times(ev, lo, hi, SCOPES, 1)
    assert t["kernel"] == pytest.approx({"conv1": 0.030, "conv2": 0.040})
    assert t["other"] == pytest.approx({"conv1": 0.010, "conv2": 0.005,
                                        "pool3": 0.005})
    assert (t["unscoped"], t["total"]) == pytest.approx((0.005, 0.095))
    shares = attribution.layer_rooflines(t, [0.003, 0.020])
    assert shares == pytest.approx({"conv1": 10.0, "conv2": 50.0})
    assert attribution.layer_rooflines(t, [0.003]) is None
    table = attribution.layer_table(t, shares)
    assert [r.split()[0] for r in table[1:-1]] == ["conv1", "conv2", "pool3"]
    assert table[-1].startswith("unscoped: 5.000 ms, 5.263%")


def test_op_scopes_reads_the_entry_computation():
    hlo = "\n".join([
        "%fused_computation.1 (p: bf16[2]) -> bf16[2] {",
        '  %inner.1 = bf16[2] negate(%p), metadata={op_name="jit(f)/conv9/neg"}',
        "}",
        "ENTRY %main.2 (x: bf16[2]) -> bf16[2] {",
        '  %x = bf16[2] parameter(0), metadata={op_name="x"}',
        "  %copy-start.1 = (bf16[2], u32[]) copy-start(%x)",
        '  %conv2d_rows.13 = bf16[2]{0:T(8,128)} custom-call(%x), '
        'custom_call_target="tpu_custom_call", backend_config={"a":{"b":1}}, '
        'metadata={op_name="jit(f)/conv15/jit(conv2d_same)/conv2d_rows/'
        'pallas_call" stack_frame_id=3}',
        '  ROOT %fusion.2 = bf16[2] fusion(%conv2d_rows.13), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(f)/conv15/'
        'jit(relu)/max"}',
        "}"])
    scopes = attribution.op_scopes(hlo)
    assert scopes == {
        "x": "x",
        "conv2d_rows.13": "jit(f)/conv15/jit(conv2d_same)/conv2d_rows/"
                          "pallas_call",
        "fusion.2": "jit(f)/conv15/jit(relu)/max"}
    assert attribution.layer_of(scopes["conv2d_rows.13"]) == "conv15"
    assert attribution.layer_of("jit(f)/jit(conv2d_same)/conv2d_rows") is None
    assert attribution.conv_layers(["conv15", "pool3", "conv2", "conv11"]) \
        == ["conv2", "conv11", "conv15"]


def _old_ctx(events):
    """What the readers that were there before read, besides events."""
    return _ctx(events, images_per_s=690.0, flops_per_image=3.07e10,
                roofline_s=0.2, peak={"bf16_flops": 197e12},
                spans={"screen.jax": [0.016], "store.append": [0.001]},
                search_s=[0.012])


OLD_READERS = ["cnn.mfu", "conv2d_roofline", "conv2d.busy_share",
               "cnn.idle_share", "dse.screen_ms", "dse.search_ms",
               "dse.store_append_ms", "dse.idle_share"]


@pytest.mark.parametrize("metric", OLD_READERS)
def test_old_readers_unchanged_beside_program_spans(metric):
    """The recorded vgg16 trace kept as before, and kept with program
    spans besides (as a traced run of this program records them): each
    reader that was there before, and the breakdown, read the same."""
    raw = trace.load_events(str(VGG_FIXTURE))
    lo, hi = trace.window(raw)
    extra = [Event(HOST, "python", n, lo + i * MS, MS)
             for i, n in enumerate(sorted(attribution.PROGRAM_SPANS))]
    old, new = trace.keep(raw + extra), attribution.keep(raw + extra)
    assert len(new) == len(old) + len(extra)
    read = harness.load_reader(metric)
    assert read(_old_ctx(new)) == read(_old_ctx(old))
    assert trace.top_ops(new, lo, hi) == trace.top_ops(old, lo, hi)
    assert trace.idle_gaps(new, lo, hi) == trace.idle_gaps(old, lo, hi)


def _sweep_busy_within(events, name, lo, hi):
    """First chip's busy time while a ``name`` span is open, by a sweep
    over sorted start/end points counting open ops and open spans:
    another way to the intersection than merging."""
    chip = min(e.plane for e in events if trace.is_device_op(e))
    points = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            continue
        if trace.is_device_op(e) and e.plane == chip:
            points += [(s, 0, 1), (t, 0, -1)]
        elif e.name == name and e.plane.startswith(HOST):
            points += [(s, 1, 1), (t, 1, -1)]
    depth, total, last = [0, 0], 0.0, None
    for x, kind, d in sorted(points):
        if depth[0] > 0 and depth[1] > 0:
            total += x - last
        depth[kind] += d
        last = x
    return total / 1e9


def test_recorded_dse_trace():
    ev = trace.load_events(str(DSE_FIXTURE))
    assert ev == attribution.keep(ev)
    ctx = _ctx(ev)
    lo, hi = ctx["window"]
    calls = attribution.host_spans(ev, "screen.call", lo, hi)
    campaigns = attribution.host_spans(ev, "campaign", lo, hi)
    assert len(calls) == len(campaigns) >= 1
    device = harness.load_reader("dse.screen_device_ms")(ctx)
    assert device == pytest.approx(
        1e3 * _sweep_busy_within(ev, "screen.call", lo, hi) / len(calls),
        rel=1e-9)
    tables = harness.load_reader("dse.screen_tables_ms")(ctx)
    screen = attribution.host_spans(ev, "screen.jax", lo, hi)
    assert 0 < tables and 0 < device
    assert tables + device <= 1e3 * sum(s.dur_ns for s in screen) / 1e9 \
        / len(screen)
    full = harness.load_reader("dse.full_eval_ms")(ctx)
    cells = attribution.host_spans(ev, "cell.eval", lo, hi)
    assert 0 < full <= 1e3 * sum(c.dur_ns for c in cells) / 1e9 / len(cells)
    # every annotation of the campaign nests inside one campaign span
    for e in ev:
        if e.name in attribution.PROGRAM_SPANS - {"campaign"}:
            assert any(c.start_ns <= e.start_ns and e.end_ns <= c.end_ns
                       for c in campaigns), e
