"""Helpers of the benchmark's CPU tests: the real cells cut to a size a
test run holds, run through the harness with the chip check skipped."""
import time

import jax

from chipbench import harness
from chipbench.run import run_cell

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}


def small_spec(cell: str) -> harness.CellSpec:
    spec = harness.load_cell(cell)
    if spec.config["driver"] == "cnn":
        spec.traffic.update(height=32, width=32, batch=2, reference_rows=1,
                            input_batches=2)
    else:
        spec.traffic.update(inputs=spec.traffic["inputs"][:3],
                            fpgas=spec.traffic["fpgas"][:1])
    return spec


def run_small(cell: str, seed: int = 2**31 + 99, seconds: float = 1.0,
              traced: bool = False) -> dict:
    """One run of ``cell`` on the CPU at a small size; the result line."""
    return run_cell(small_spec(cell), seed, seconds, traced,
                    jax.devices()[:1], PEAK, time.perf_counter())


def small_cell(cell: str, seed: int = 5, seconds: float = 1.0):
    """A cell that has run its window and been released, for its checks
    and its control."""
    spec = small_spec(cell)
    c = harness.load_driver(spec).Cell(spec, seed, jax.devices()[:1], PEAK)
    c.setup()
    c.run_window(seconds, None)
    c.release()
    return c, spec.traffic["limits"]
