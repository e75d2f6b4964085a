"""Run one benchmark cell once on the TPU and print its result line.

    python3 chipbench/run.py --workload vgg16.224 --seed 7 --seconds 30 --trace 0

One process owns the chip. The run enables JAX's persistent compilation
cache (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
makes the cell's weights and inputs on the device from ``--seed``, warms
the cell's own shapes (all of that is ``setup_s``), measures for
``--seconds``, then checks what the window produced against the plain
reference. With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` it traces the window with the JAX profiler and reports
the per-layer metrics, the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness, trace  # noqa: E402


def run_cell(spec, seed: int, seconds: float, traced: bool, devices, peak,
             t_start: float) -> dict:
    """Set up, measure and check one cell on ``devices``; the result
    line."""
    import jax
    t_cell = time.perf_counter()
    cell = harness.load_driver(spec).Cell(spec, seed, devices, peak)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    print(f"setup: {t_cell - t_start:.3f} s to the cell (imports, devices, "
          f"cache), {setup_s - (t_cell - t_start):.3f} s in its set-up",
          file=sys.stderr, flush=True)

    # every jit trace the window triggers: there should be none
    traces = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: traces.append(event)
        if event == "/jax/core/compile/jaxpr_trace_duration" else None)
    events = None
    if traced:
        logdir = ROOT / "results" / "chipbench" / spec.name / "trace"
        shutil.rmtree(logdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # it would slow the host's work
        options.host_tracer_level = 1       # keeps the TraceAnnotations
        jax.profiler.start_trace(str(logdir), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                cell.run_window(seconds, jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        events = trace.keep(trace.load(str(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
    else:
        cell.run_window(seconds, None)
    compiles = len(traces)
    if hasattr(cell, "window_split"):
        print(f"window: {cell.window_split()}", file=sys.stderr, flush=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(memory_peak_bytes(d)
                                       for d in devices)}
    e2e = cell.end_to_end()
    e2e["setup_s"] = setup_s
    breakdown = None
    if traced:
        lo, hi = trace.window(events)
        ctx = cell.context()
        ctx.update(events=events, window=(lo, hi), window_s=(hi - lo) / 1e9,
                   busy_s=trace.busy_s(events, lo, hi, len(devices)),
                   n_devices=len(devices), peak=peak, end_to_end=e2e)
        device.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        metrics = {}
        for m in spec.per_layer:
            value = harness.load_reader(m["name"], spec.root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": trace.top_ops(events, lo, hi),
                     "idle_gaps": trace.idle_gaps(events, lo, hi)}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
    attempted, failed = cell.attempted_failed()
    cell.release()
    line = harness.result_line(
        checks=cell.checks(), attempted=attempted, failed=failed,
        metrics=metrics, device=device, breakdown=breakdown)
    return {"compiles_in_window": compiles, **line}


def memory_peak_bytes(device) -> int:
    """What the chip held at its peak: the buffers in use, plus what the
    runtime reserved for the programs' temporaries, which the TPU keeps
    apart from the buffers in use."""
    stats = device.memory_stats() or {}
    print(f"memory_stats: {stats}", file=sys.stderr, flush=True)
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved",
                           stats.get("bytes_reserved", 0)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_cell(args.workload)
    try:
        devices = harness.tpu_devices(spec.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.cache import enable_compilation_cache
    enable_compilation_cache()
    # cache every program, however fast it compiles, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    peak = harness.peaks(devices[0].device_kind)
    harness.print_result(run_cell(spec, args.seed, args.seconds,
                                  bool(args.trace), devices, peak, T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
