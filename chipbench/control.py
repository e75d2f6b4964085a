"""Readings that set the limits of a cell's comparison: the compared
numbers of the program over many seeds (the lower reading is their
largest), and of the control, the plain reference in the precision below
the configuration's put in the program's place (the upper reading is its
smallest). All seeds run in one process, each after a short window at
the cell's own load.

    python3 chipbench/control.py --workload vgg16.224 --seconds 3 \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

Prints one JSON line per seed. The benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
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
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peak = harness.peaks(devices[0].device_kind)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = harness.load_driver(spec).Cell(spec, seed, devices, peak)
        cell.setup()
        cell.run_window(args.seconds, None)
        cell.release()
        line = {"workload": spec.name, "seed": seed,
                "program": {c.name: c.value for c in cell.checks()}}
        if seed in controls:
            line["control"] = cell.control()
        line["s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
