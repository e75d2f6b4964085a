"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the peaks table, the statistics of a window, and the
result line.

A cell is one entry of ``workloads``. Its configuration file is the one
``configs`` names for it, its traffic is ``traffic/<traffic>.json``, its
driver is ``drivers/<driver>.py`` (the configuration's ``driver``), and
each per-layer metric is read by ``metrics/<metric name>.py``. A later
cell, configuration, traffic mix or metric is therefore added by adding
files alone.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class CellSpec:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with its limit; the run is correct only when
    every number is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str, e2e_names: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric.get("moves") in e2e_names


def load_cell(name: str, root: Path = ROOT) -> CellSpec:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "chipbench" / "traffic" / f"{wl['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return CellSpec(name, wl, config, traffic, e2e, per_layer, root)


def load_driver(spec: CellSpec):
    """The driver module of the cell's configuration."""
    return importlib.import_module(f"chipbench.drivers.{spec.config['driver']}")


def load_reader(metric: str, root: Path = ROOT):
    """``read(ctx) -> float | None`` of one per-layer metric, from
    ``chipbench/metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    mod_name = "chipbench_metric_" + metric.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip; an unknown device is an error."""
    with open(root / "chipbench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def tpu_devices(chips: int):
    """The chips the cell runs on; :class:`NoChip` where JAX finds no TPU
    or fewer than ``chips``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


# ---------------------------------------------------------------------------
# Statistics over all samples of a window
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of all ``values``, interpolated
    linearly between order statistics (NumPy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    """Work completed per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


class Reservoir:
    """A uniform sample of ``size`` items of a stream of unknown length,
    drawn from a seeded generator (Algorithm R)."""

    def __init__(self, size: int, rng):
        self.size, self.rng = size, rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if self.seen < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


def seed_words(seed: int, *salt: int) -> int:
    """A 32-bit seed derived from the run's seed (any size) and ``salt``."""
    import numpy as np
    return int(np.random.SeedSequence([seed, *salt]).generate_state(1)[0])


# ---------------------------------------------------------------------------
# The result line
# ---------------------------------------------------------------------------


def result_line(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: dict | None = None) -> dict:
    out = {"correct": bool(checks) and all(c.ok for c in checks),
           "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # a value that is not finite fails; JSON carries it as the largest float
    out["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                              else sys.float_info.max, "limit": c.limit}
                     for c in checks}
    return out


def print_result(line: dict) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result as the last line on standard
    output."""
    for name, c in line["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
