"""Tests for backend-agnostic campaigns: the backend protocol (fpga
byte-compat + tpu cells), crowding-distance frontier diversity, and the
Markdown report generator."""
import json
import math

import pytest

from repro.configs import SHAPES, get_config
from repro.core.tpu_planner import plan_arch
from repro.dse import (CampaignReport, crowding_distance, canonical_vector,
                       dominates, run_campaign, scalarize_values,
                       select_diverse)
from repro.dse.backends import (BACKENDS, TPUCell, get_backend,
                                record_backend, run_cell_by_backend)
from repro.dse.campaign import CampaignCell, run_cell
from repro.dse.cli import main as cli_main
from repro.dse.objectives import OBJECTIVES
from repro.dse.report import fixture_records, render_report
from repro.dse.report import main as report_main
from repro.dse.store import ResultStore

_FAST = dict(population=6, iterations=4)


# ---------------------------------------------------------------------------
# crowding distance / diverse selection
# ---------------------------------------------------------------------------


def test_crowding_distance_boundaries_are_infinite():
    vecs = [(0.0, 10.0), (5.0, 5.0), (10.0, 0.0)]
    cd = crowding_distance(vecs)
    assert cd[0] == math.inf and cd[2] == math.inf
    assert 0.0 < cd[1] < math.inf
    assert crowding_distance([(1.0, 2.0)]) == [math.inf]
    assert crowding_distance([]) == []


def test_crowding_distance_ranks_lonely_above_clumped():
    # b is in the middle of a clump; c sits alone in a gap
    vecs = [(0.0, 1.0), (0.48, 0.52), (0.5, 0.5), (0.52, 0.48), (1.0, 0.0)]
    cd = crowding_distance(vecs)
    clump_mid = cd[2]
    assert all(cd[i] == math.inf for i in (0, 4))
    assert cd[1] > clump_mid and cd[3] > clump_mid  # clump edges less crowded


def test_crowding_distance_degenerate_objective_ignored():
    vecs = [(0.0, 7.0), (0.5, 7.0), (1.0, 7.0)]
    cd = crowding_distance(vecs)
    assert cd[0] == cd[2] == math.inf
    assert cd[1] < math.inf  # dim 1 (constant) contributed nothing


def test_degenerate_objective_does_not_shield_interior_points():
    """A constant objective (e.g. a campaign run at a single --chips
    value) must not hand out spurious inf and let an interior point
    outlive a true extreme under truncation."""
    vecs = [(0.5, 0.5, 7.0), (0.0, 1.0, 7.0), (1.0, 0.0, 7.0)]
    cd = crowding_distance(vecs)
    assert cd[1] == cd[2] == math.inf
    assert cd[0] < math.inf
    assert set(select_diverse(vecs, 2)) == {1, 2}, \
        "both true extremes must survive k=2 truncation"
    # identical duplicates are equally (finitely) crowded
    assert crowding_distance([(1.0, 1.0), (1.0, 1.0)]) == [0.0, 0.0]


def test_select_diverse_returns_spread_not_clump():
    # first front: two extremes + a 3-point clump near the middle
    front = [(0.0, 10.0), (5.0, 5.0), (5.05, 4.95), (4.95, 5.05),
             (10.0, 0.0)]
    picked = select_diverse(front, 3)
    assert 0 in picked and 4 in picked, "extremes must survive truncation"
    assert len([i for i in picked if i in (1, 2, 3)]) == 1, \
        "only one member of the clump should survive"


def test_select_diverse_rank_ties_broken_by_spread_then_index():
    # duplicated clump points have identical crowding -> index breaks tie
    front = [(0.0, 1.0), (0.5, 0.5), (0.5, 0.5), (1.0, 0.0)]
    picked = select_diverse(front, 4)
    assert picked[:2] in ([0, 3], [0, 1]) or picked[0] == 0
    assert picked == select_diverse(front, 4)  # deterministic
    # crowding order puts the inf-distance extremes before the clump
    assert set(picked[:2]) == {0, 3}
    assert picked[2:] == [1, 2]  # equal crowding -> input order


def test_select_diverse_fills_from_later_fronts():
    vecs = [(1.0, 1.0), (0.0, 0.0), (0.5, 0.5)]  # fronts: [0], [2], [1]
    assert select_diverse(vecs, 2) == [0, 2]
    assert select_diverse(vecs, 3) == [0, 2, 1]
    assert select_diverse(vecs, 0) == []
    assert select_diverse(vecs, 99) == [0, 2, 1]


# ---------------------------------------------------------------------------
# generic objective helpers
# ---------------------------------------------------------------------------


def test_canonical_vector_and_scalarize_values_generic():
    be = get_backend("tpu")
    obj = {"step_time_s": 2.0, "mfu": 0.5, "hbm_gib": 4.0, "chips": 8.0,
           "feasible": True}
    assert canonical_vector(obj, be.objectives) == (-2.0, 0.5, -4.0, -8.0)
    assert be.scalarize(obj) == -2.0  # default weights: step_time_s only
    assert be.scalarize(obj, {"mfu": 2.0}) == 1.0
    assert scalarize_values({**obj, "feasible": False}, be.objectives) == 0.0
    with pytest.raises(KeyError):
        be.scalarize(obj, {"gops": 1.0})  # fpga objective, wrong backend


# ---------------------------------------------------------------------------
# CLI axis parsing (edge cases)
# ---------------------------------------------------------------------------


def test_parse_inputs_edge_cases():
    from repro.dse.backends import parse_inputs
    assert parse_inputs("224") == [(224, 224)]
    assert parse_inputs("320x480") == [(320, 480)]
    assert parse_inputs(" 224 ,  320x480 ") == [(224, 224), (320, 480)]
    assert parse_inputs("320 x 480") == [(320, 480)]  # int() strips spaces
    assert parse_inputs("224x") == [(224, 224)]       # trailing x: square
    assert parse_inputs("") == []
    assert parse_inputs(", ,") == []
    for bad in ("abc", "x224", "320xx480", "320x480x640", "3.5"):
        with pytest.raises(ValueError, match="bad input size"):
            parse_inputs(bad)


def test_parse_weights_edge_cases():
    from repro.dse.backends import parse_weights
    assert parse_weights("") is None
    assert parse_weights("a=1,b=2.5") == {"a": 1.0, "b": 2.5}
    assert parse_weights(" a = 1 ") == {"a": 1.0}  # whitespace stripped
    # empty value and bare name both mean weight 1.0
    assert parse_weights("mfu=") == {"mfu": 1.0}
    assert parse_weights("mfu") == {"mfu": 1.0}
    assert parse_weights("step_time_s=-2") == {"step_time_s": -2.0}
    with pytest.raises(ValueError, match="bad weight token"):
        parse_weights("=5")
    with pytest.raises(ValueError, match="bad weight value"):
        parse_weights("mfu=fast")


# ---------------------------------------------------------------------------
# registry + fpga byte-compat
# ---------------------------------------------------------------------------


def test_backend_registry():
    assert set(BACKENDS) == {"fpga", "tpu", "cuda"}
    assert get_backend("fpga") is BACKENDS["fpga"]
    assert get_backend(BACKENDS["tpu"]) is BACKENDS["tpu"]
    with pytest.raises(KeyError):
        get_backend("npu")
    assert record_backend({"backend": "tpu"}) == "tpu"
    assert record_backend({}) == "fpga"  # legacy PR-1 records


def test_fpga_backend_is_byte_compatible_with_module_functions():
    be = get_backend("fpga")
    assert be.objectives is OBJECTIVES
    cell = CampaignCell("vgg16", 64, 64, "zc706", 16, 1)
    drop_time = lambda r: {k: v for k, v in r.items()
                           if k != "search_time_s"}
    via_backend = be.run_cell(cell, **_FAST)
    via_module = run_cell(cell, **_FAST)
    assert drop_time(via_backend) == drop_time(via_module)
    assert "backend" not in via_backend, \
        "fpga records must stay byte-compatible with PR-1 stores"
    assert be.search_config(base_seed=0, weights=None, **_FAST) == \
        via_backend["search"] == via_module["search"]
    assert drop_time(run_cell_by_backend("fpga", cell, 0, 6, 4, None)) == \
        drop_time(via_module)


# ---------------------------------------------------------------------------
# tpu backend
# ---------------------------------------------------------------------------


def test_tpu_expand_cells_axes_and_collapse():
    be = get_backend("tpu")
    cells = be.expand_cells(archs=["starcoder2-3b"],
                            shapes=["train_4k", "decode_32k"],
                            chips=[8, 16], remats=("full", "none"),
                            microbatches=(1, 2))
    keys = [c.key for c in cells]
    assert len(keys) == len(set(keys))
    # train: 2 chips x 2 remats x 2 mb = 8; decode collapses to (none, 1)
    assert sum(c.shape == "train_4k" for c in cells) == 8
    decode = [c for c in cells if c.shape == "decode_32k"]
    assert len(decode) == 2
    assert all(c.remat == "none" and c.microbatches == 1 for c in decode)


def test_tpu_expand_cells_skips_spec_disabled_combos():
    be = get_backend("tpu")
    # full attention at 500k context is disabled per spec; xlstm (ssm) runs
    cells = be.expand_cells(archs=["starcoder2-3b", "xlstm-350m"],
                            shapes=["long_500k"], chips=[8])
    assert {c.arch for c in cells} == {"xlstm-350m"}


def test_tpu_expand_cells_validation():
    be = get_backend("tpu")
    with pytest.raises(KeyError):
        be.expand_cells(archs=["notanarch"], shapes=["train_4k"], chips=[8])
    with pytest.raises(KeyError):
        be.expand_cells(archs=["xlstm-350m"], shapes=["noshape"], chips=[8])
    with pytest.raises(ValueError):
        be.expand_cells(archs=["xlstm-350m"], shapes=["train_4k"],
                        chips=[12])  # not a power of two
    with pytest.raises(ValueError):
        be.expand_cells(archs=["xlstm-350m"], shapes=["train_4k"],
                        chips=[8], remats=("sometimes",))
    # expand_cells can be bypassed (TPUCell is public); run_cell must not
    # silently evaluate inconsistent dp x tp splits of a non-2^k count
    with pytest.raises(ValueError):
        be.run_cell(TPUCell("xlstm-350m", "train_4k", 12, "full", 1))


def test_tpu_run_cell_schema_and_determinism():
    be = get_backend("tpu")
    cell = TPUCell("starcoder2-3b", "train_4k", 16, "full", 2)
    rec = be.run_cell(cell)
    assert rec["backend"] == "tpu"
    assert rec["cell_key"] == cell.key
    assert rec["cell"] == {"arch": "starcoder2-3b", "shape": "train_4k",
                           "chips": 16, "remat": "full", "microbatches": 2}
    assert set(rec["objectives"]) == {"step_time_s", "mfu", "hbm_gib",
                                      "chips", "feasible"}
    assert rec["plan"]["dp"] * rec["plan"]["tp"] == 16
    assert rec["evaluations"] > 0
    assert rec["search"] == {"weights": None, "calibration": None}
    json.dumps(rec)  # JSONL-serializable
    rec2 = be.run_cell(cell)
    for k in ("objectives", "plan", "cell_key", "search", "fitness"):
        assert rec2[k] == rec[k]


def test_tpu_run_cell_picks_planner_best_mapping():
    """The cell's chosen dp x tp must match the exhaustive planner's best
    plan for the same (chips, remat, microbatches) slice."""
    cfg, shape = get_config("starcoder2-3b"), SHAPES["train_4k"]
    cell = TPUCell("starcoder2-3b", "train_4k", 16, "full", 1)
    rec = get_backend("tpu").run_cell(cell)
    slice_ = [p for p in plan_arch(cfg, shape, max_chips=16)
              if p.n_chips == 16 and p.remat == "full"
              and p.microbatches == 1]
    assert slice_, "planner slice must be non-empty"
    top = slice_[0]  # plan_arch sorts feasible-first, then step*chips
    assert (rec["plan"]["dp"], rec["plan"]["tp"]) == (top.dp, top.tp)
    assert rec["objectives"]["step_time_s"] == \
        pytest.approx(top.predicted_step_s)
    assert rec["objectives"]["feasible"] == top.fits


def test_tpu_campaign_resume_and_weight_invalidation(tmp_path):
    be = get_backend("tpu")
    store = tmp_path / "t.jsonl"
    cells = be.expand_cells(archs=["xlstm-350m"], shapes=["train_4k"],
                            chips=[8, 16], remats=("full",),
                            microbatches=(1,))
    r1 = run_campaign(cells, str(store), backend="tpu")
    assert r1.new_cells == len(cells) and r1.new_evaluations > 0
    r2 = run_campaign(cells, str(store), backend="tpu")
    assert r2.new_cells == 0 and r2.new_evaluations == 0
    # re-weighting changes the per-cell mapping choice -> re-runs
    r3 = run_campaign(cells, str(store), backend="tpu",
                      weights={"hbm_gib": 1.0})
    assert r3.new_cells == len(cells)
    # pso knobs are irrelevant to the deterministic planner -> still reused
    r4 = run_campaign(cells, str(store), backend="tpu",
                      weights={"hbm_gib": 1.0}, population=99, iterations=7)
    assert r4.new_cells == 0


def test_tpu_campaign_workers_match_serial(tmp_path):
    be = get_backend("tpu")
    cells = be.expand_cells(archs=["xlstm-350m"], shapes=["decode_32k"],
                            chips=[8, 16], remats=("none",),
                            microbatches=(1,))
    serial = run_campaign(cells, str(tmp_path / "a.jsonl"), backend="tpu")
    pooled = run_campaign(cells, str(tmp_path / "b.jsonl"), backend="tpu",
                          workers=2)
    for a, b in zip(serial.records, pooled.records):
        assert a["objectives"] == b["objectives"]
        assert a["plan"] == b["plan"]


def test_store_backend_filter(tmp_path):
    s = ResultStore(tmp_path / "m.jsonl")
    s.put({"cell_key": "a", "objectives": {}})                    # legacy fpga
    s.put({"cell_key": "b", "backend": "tpu", "objectives": {}})
    assert s.backends() == ["fpga", "tpu"]
    assert [r["cell_key"] for r in s.iter_records("fpga")] == ["a"]
    assert [r["cell_key"] for r in s.iter_records("tpu")] == ["b"]
    assert len(list(s.iter_records())) == 2


# ---------------------------------------------------------------------------
# CampaignReport.frontier(k)
# ---------------------------------------------------------------------------


def _tpu_report_from(records):
    return CampaignReport(cells=[], records=records, reused_cells=0,
                          new_cells=0, new_evaluations=0, wall_time_s=0.0,
                          backend=get_backend("tpu"))


def _tpu_rec(key, step, mfu, hbm=1.0, chips=8.0, feasible=True):
    return {"cell_key": key,
            "objectives": {"step_time_s": step, "mfu": mfu, "hbm_gib": hbm,
                           "chips": chips, "feasible": feasible}}


def test_frontier_k_returns_diverse_spread():
    recs = [
        _tpu_rec("fast", 1.0, 0.1),
        _tpu_rec("clump1", 5.0, 0.50),
        _tpu_rec("clump2", 5.01, 0.501),
        _tpu_rec("clump3", 4.99, 0.499),
        _tpu_rec("efficient", 10.0, 0.9),
        _tpu_rec("dominated", 11.0, 0.05),
        _tpu_rec("infeasible", 0.1, 0.99, feasible=False),
    ]
    rep = _tpu_report_from(recs)
    full = rep.frontier()
    assert {r["cell_key"] for r in full} >= {"fast", "efficient"}
    assert all(r["cell_key"] != "infeasible" for r in full)
    assert all(r["cell_key"] != "dominated" for r in full)
    top3 = rep.frontier(k=3)
    keys = [r["cell_key"] for r in top3]
    assert len(keys) == 3
    assert "fast" in keys and "efficient" in keys, \
        "extremes must survive k-truncation"
    assert sum(k.startswith("clump") for k in keys) <= 1, \
        "frontier(k) must thin the clump, not return it"
    # mutual non-domination within the selected front members
    be = get_backend("tpu")
    vecs = [be.canonical(r["objectives"]) for r in top3]
    for i, a in enumerate(vecs):
        assert not any(dominates(b, a) for j, b in enumerate(vecs) if j != i)


def test_frontier_k_tops_up_from_later_fronts():
    recs = [_tpu_rec("best", 1.0, 0.9), _tpu_rec("second", 2.0, 0.8),
            _tpu_rec("third", 3.0, 0.7)]
    rep = _tpu_report_from(recs)
    assert len(rep.frontier()) == 1
    assert [r["cell_key"] for r in rep.frontier(k=3)] == \
        ["best", "second", "third"]


# ---------------------------------------------------------------------------
# CLI end-to-end (tpu)
# ---------------------------------------------------------------------------


def test_cli_tpu_end_to_end(tmp_path, capsys):
    store = tmp_path / "tpu.jsonl"
    argv = ["--backend", "tpu", "--archs", "xlstm-350m",
            "--shapes", "train_4k", "--chips", "8,16",
            "--remats", "full,none", "--microbatches", "1",
            "--store", str(store),
            "--frontier-json", str(tmp_path / "front.json")]
    report = cli_main(argv)
    out = capsys.readouterr().out
    assert "campaign[tpu]" in out and "Pareto frontier" in out
    assert store.exists()
    front = json.loads((tmp_path / "front.json").read_text())
    assert front and all(r["backend"] == "tpu" for r in front)
    report2 = cli_main(argv)
    assert report2.new_evaluations == 0
    assert report2.reused_cells == len(report.cells)


# ---------------------------------------------------------------------------
# report generation
# ---------------------------------------------------------------------------


def test_render_report_from_fixture_store(tmp_path):
    store = ResultStore(tmp_path / "fix.jsonl")
    for rec in fixture_records():
        store.put(rec)
    out = tmp_path / "report.md"
    rc = report_main([str(store.path), "--out", str(out),
                      "--title", "fixture report"])
    assert rc == 0
    md = out.read_text()
    assert md.startswith("# fixture report")
    for section in ("## Backend `fpga`", "## Backend `tpu`",
                    "### Pareto frontier", "### Per-workload winners",
                    "### Objective trade-offs"):
        assert section in md
    # markdown tables must escape the cell-key axis separator
    assert "net=vgg16\\|in=" in md
    assert "| --- |" in md


def test_render_report_with_bench_appendix(tmp_path):
    bench = {"benchmarks": {"fig10": [
        {"name": "fig10_gops_224x224", "us_per_call": 123.4,
         "derived": "gops=4220(paper=4218)"}]}}
    bench_path = tmp_path / "bench.json"
    bench_path.write_text(json.dumps(bench))
    store = ResultStore(tmp_path / "fix.jsonl")
    for rec in fixture_records():
        store.put(rec)
    out = tmp_path / "r.md"
    assert report_main([str(store.path), "--bench", str(bench_path),
                        "--out", str(out)]) == 0
    md = out.read_text()
    assert "## Benchmark appendix" in md
    assert "fig10_gops_224x224" in md


def test_report_selftest():
    assert report_main(["--selftest"]) == 0


def test_report_requires_store(tmp_path):
    with pytest.raises(SystemExit):
        report_main([])
    with pytest.raises(SystemExit):
        report_main([str(tmp_path / "missing.jsonl")])


def test_render_report_marks_unknown_backend():
    md = render_report([{"cell_key": "x", "backend": "npu",
                         "objectives": {"feasible": True}}])
    assert "unknown backend" in md
