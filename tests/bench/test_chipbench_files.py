"""The benchmark is driven by its files: a cell, configuration, traffic
mix or per-layer metric is found by the name in ``BENCHMARK.json``, and
adding one takes files alone. ``BENCHMARK.json`` keeps to the contract's
shape."""
import json
import re
import shutil
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's own files, to add files to."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    spec = harness.load_cell(cell)
    assert spec.config["name"] == spec.workload["config"]
    assert harness.load_driver(spec).Cell
    assert {m["name"] for m in spec.end_to_end} >= {"setup_s"}
    assert len(spec.end_to_end) >= 2 and spec.per_layer
    for m in spec.per_layer:
        assert callable(harness.load_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in spec.end_to_end}
    assert spec.traffic["limits"] and spec.traffic["why"]


def test_new_cell_config_traffic_and_metric_are_files_alone(tree):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    cfg = json.loads((tree / "chipbench/configs/vgg16.json").read_text())
    cfg["name"] = "vgg16-wide"
    (tree / "chipbench/configs/vgg16-wide.json").write_text(json.dumps(cfg))
    traffic = json.loads((tree / "chipbench/traffic/img224.json").read_text())
    traffic.update(height=320, width=320, batch=8)
    (tree / "chipbench/traffic/img320.json").write_text(json.dumps(traffic))
    (tree / "chipbench/metrics/cnn.batch_ms.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx['busy_s']\n")
    bench["configs"].append({"name": "vgg16-wide", "source": "x",
                             "file": "chipbench/configs/vgg16-wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "vgg16-wide.320", "config":
                               "vgg16-wide", "traffic": "img320",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("vgg16-wide.320")
    bench["per_layer"].append({"name": "cnn.batch_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "images_per_s"})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.load_cell("vgg16-wide.320", root=tree)
    assert spec.traffic["height"] == 320 and spec.config["name"] == "vgg16-wide"
    assert [m["name"] for m in spec.end_to_end] == ["images_per_s", "setup_s"]
    names = [m["name"] for m in spec.per_layer]
    assert "cnn.batch_ms" in names      # no workloads key: every cell of its metric
    assert "conv2d_roofline" not in names   # listed for other cells only
    assert harness.load_reader("cnn.batch_ms", root=tree)({"busy_s": 2.0}) \
        == 2000.0
    assert "cnn.batch_ms" in [m["name"] for m in
                              harness.load_cell("vgg16.224", root=tree).per_layer]
    assert "cnn.batch_ms" not in [m["name"] for m in
                                  harness.load_cell("dse.zoo", root=tree).per_layer]


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert all(k in cfg for k in c["reduced"])
        names.add(c["name"])
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
        used.add(w["config"])
    assert used == names
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    every = [*BENCH["configs"], *BENCH["workloads"], *BENCH["end_to_end"],
             *BENCH["per_layer"]]
    assert all(NAME.match(e["name"]) for e in every)
    assert all(UNIT.match(m["unit"]) for m in
               BENCH["end_to_end"] + BENCH["per_layer"])
    assert len({e["name"] for e in every}) == len(every)
