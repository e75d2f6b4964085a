"""Tests for repro.dse: Pareto properties, multi-objective evaluation,
the JSONL store, and campaign resume/memoization accounting."""
import json

import pytest

from repro.core import KU115, RAV, ZC706, evaluate_rav
from repro.core.netinfo import vgg16
from repro.dse import (CampaignCell, Objectives, ResultStore, cell_seed,
                       expand_cells, non_dominated, nondominated_sort,
                       pareto_front, rav_hash, run_campaign, run_cell,
                       scalarized_objective)
from repro.dse.campaign import build_net
from repro.dse.cli import main as cli_main, parse_inputs, parse_weights
from repro.dse.pareto import dominates

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Pareto
# ---------------------------------------------------------------------------


def test_dominates_basic():
    assert dominates((2.0, 2.0), (1.0, 2.0))
    assert not dominates((1.0, 2.0), (2.0, 1.0))   # incomparable
    assert not dominates((1.0, 1.0), (1.0, 1.0))   # needs a strict win


def test_non_dominated_keeps_duplicates_and_order():
    vecs = [(1.0, 1.0), (2.0, 0.0), (1.0, 1.0), (0.0, 0.0)]
    assert non_dominated(vecs) == [0, 1, 2]


def test_pareto_front_maps_items():
    items = ["a", "b", "c"]
    vecs = [(1.0, 0.0), (0.0, 1.0), (0.0, 0.5)]
    assert pareto_front(items, vecs) == ["a", "b"]


if HAVE_HYPOTHESIS:

    vec_lists = st.lists(
        st.tuples(st.integers(0, 5), st.integers(0, 5), st.integers(0, 5)),
        min_size=1, max_size=24)

    @given(vec_lists)
    @settings(max_examples=200, deadline=None)
    def test_frontier_is_mutually_nondominated(vecs):
        front = non_dominated(vecs)
        assert front, "frontier of a nonempty set is nonempty"
        for i in front:
            for j in front:
                assert not dominates(vecs[i], vecs[j])

    @given(vec_lists)
    @settings(max_examples=200, deadline=None)
    def test_dominated_points_are_excluded_and_covered(vecs):
        front = set(non_dominated(vecs))
        for i, v in enumerate(vecs):
            if i in front:
                continue
            # every excluded point is dominated by some frontier point
            assert any(dominates(vecs[j], v) for j in front)

    @given(vec_lists)
    @settings(max_examples=100, deadline=None)
    def test_nondominated_sort_partitions(vecs):
        fronts = nondominated_sort(vecs)
        flat = [i for f in fronts for i in f]
        assert sorted(flat) == list(range(len(vecs)))
        for k, front in enumerate(fronts[1:], start=1):
            for i in front:
                assert any(dominates(vecs[j], vecs[i])
                           for j in fronts[k - 1])


# ---------------------------------------------------------------------------
# Objectives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rav", [
    RAV(0, 1, 0.0, 0.0, 0.0),
    RAV(3, 1, 0.4, 0.4, 0.4),
    RAV(6, 2, 0.5, 0.5, 0.5),
    RAV(13, 1, 0.95, 0.95, 0.95),
])
def test_default_scalarization_equals_old_scalar_path(rav):
    """Multi-objective evaluate_rav + default weights == the old
    throughput-only fitness, bit for bit."""
    d = evaluate_rav(vgg16(64), ZC706, rav)
    o = Objectives.from_design(d)
    assert o.scalarize() == d.fitness
    assert scalarized_objective()(d) == d.fitness


def test_objectives_roundtrip_and_canonical_signs():
    d = evaluate_rav(vgg16(64), KU115, RAV(6, 1, 0.5, 0.5, 0.5))
    o = Objectives.from_design(d)
    assert Objectives.from_dict(o.as_dict()) == o
    canon = o.canonical()
    assert canon[0] == o.throughput_ips          # maximized: unchanged
    assert canon[2] == -o.latency_s              # minimized: negated
    assert canon[4] == -o.bram_used
    assert o.latency_s > 0


def test_scalarize_rejects_unknown_objective():
    o = Objectives(1.0, 1.0, 1.0, 1.0, 1.0)
    with pytest.raises(KeyError):
        o.scalarize({"nope": 1.0})


# ---------------------------------------------------------------------------
# Store
# ---------------------------------------------------------------------------


def test_store_roundtrip_and_torn_line(tmp_path):
    p = tmp_path / "s.jsonl"
    s = ResultStore(p)
    s.put({"cell_key": "a", "x": 1})
    s.put({"cell_key": "b", "x": 2})
    s.put({"cell_key": "a", "x": 3})  # last wins
    with p.open("a") as f:
        f.write('{"cell_key": "c", "x":')  # killed mid-append
    s2 = ResultStore(p)
    assert len(s2) == 2
    assert s2.get("a")["x"] == 3
    assert s2.get("b")["x"] == 2
    assert "c" not in s2


def test_rav_hash_matches_pso_cache_resolution():
    a = rav_hash(RAV(3, 1, 0.501, 0.5, 0.5))
    b = rav_hash(RAV(3, 1, 0.499, 0.5, 0.5))
    c = rav_hash(RAV(3, 1, 0.6, 0.5, 0.5))
    assert a == b
    assert a != c


# ---------------------------------------------------------------------------
# Campaign
# ---------------------------------------------------------------------------

_FAST = dict(population=6, iterations=4)


def _small_cells():
    return expand_cells(["vgg16"], [(64, 64)], ["zc706"], [16, 8], [1, 2])


def test_expand_cells_cross_product_and_native_inputs():
    cells = expand_cells(["vgg16", "alexnet"], [(64, 64), (128, 128)],
                         ["ku115"], [16], [1])
    keys = [c.key for c in cells]
    assert len(keys) == len(set(keys))
    # vgg16 crosses with both inputs; alexnet is fixed-topology -> native
    assert sum(c.net == "vgg16" for c in cells) == 2
    assert [c for c in cells if c.net == "alexnet"][0].key == \
        "net=alexnet|in=native|fpga=ku115|prec=16|bmax=1"
    with pytest.raises(KeyError):
        expand_cells(["vgg16"], [(64, 64)], ["nofpga"], [16], [1])
    with pytest.raises(KeyError):
        build_net("notanet")


def test_cell_seed_deterministic_and_distinct():
    cells = _small_cells()
    seeds = [cell_seed(0, c) for c in cells]
    assert seeds == [cell_seed(0, c) for c in cells]
    assert len(set(seeds)) == len(seeds)
    assert cell_seed(1, cells[0]) != cell_seed(0, cells[0])


def test_campaign_resume_does_zero_new_evaluations(tmp_path):
    store = tmp_path / "c.jsonl"
    cells = _small_cells()
    r1 = run_campaign(cells, str(store), **_FAST)
    assert r1.new_cells == len(cells)
    assert r1.new_evaluations > 0
    assert all(rec is not None for rec in r1.records)

    # Re-running a finished campaign is pure memoization.
    r2 = run_campaign(cells, str(store), **_FAST)
    assert r2.new_cells == 0
    assert r2.new_evaluations == 0
    assert r2.reused_cells == len(cells)
    assert r2.records == r1.records


def test_campaign_config_change_invalidates_stored_cells(tmp_path):
    """A store must not serve results searched under different PSO settings
    or objective weights as if they answered the new request."""
    store = tmp_path / "c.jsonl"
    cells = _small_cells()[:2]
    run_campaign(cells, str(store), **_FAST)

    deeper = run_campaign(cells, str(store), population=8, iterations=6)
    assert deeper.new_cells == len(cells)
    assert deeper.new_evaluations > 0

    reweighted = run_campaign(cells, str(store), population=8, iterations=6,
                              weights={"dsp_eff": 1.0})
    assert reweighted.new_cells == len(cells)

    # matching config again -> pure reuse
    again = run_campaign(cells, str(store), population=8, iterations=6,
                         weights={"dsp_eff": 1.0})
    assert again.new_cells == 0
    assert again.new_evaluations == 0


def test_campaign_killed_and_rerun_reuses_partial_store(tmp_path):
    store = tmp_path / "c.jsonl"
    cells = _small_cells()
    # "killed" campaign: only the first two cells finished
    run_campaign(cells[:2], str(store), **_FAST)
    evals_done = sum(r["evaluations"] for r in ResultStore(store))
    r = run_campaign(cells, str(store), **_FAST)
    assert r.reused_cells == 2
    assert r.new_cells == len(cells) - 2
    total = sum(r["evaluations"] for r in ResultStore(store))
    assert r.new_evaluations == total - evals_done


def test_campaign_workers_match_serial(tmp_path):
    cells = _small_cells()[:2]
    serial = run_campaign(cells, str(tmp_path / "a.jsonl"), **_FAST)
    pooled = run_campaign(cells, str(tmp_path / "b.jsonl"), workers=2, **_FAST)
    for a, b in zip(serial.records, pooled.records):
        assert a["rav"] == b["rav"]
        assert a["objectives"] == b["objectives"]
        assert a["evaluations"] == b["evaluations"]


def test_campaign_store_deterministic_across_worker_counts(tmp_path):
    """Same seed, --workers 1 vs --workers 2: the stores are byte-identical
    modulo record order — pool scheduling may only reorder appends, never
    change a record. Wall-clock (``search_time_s``) is the one volatile
    field and is stripped before comparing."""
    cells = _small_cells()
    run_campaign(cells, str(tmp_path / "w1.jsonl"), base_seed=7, **_FAST)
    run_campaign(cells, str(tmp_path / "w2.jsonl"), base_seed=7, workers=2,
                 **_FAST)

    def canonical(path):
        lines = []
        for rec in ResultStore(path):
            rec.pop("search_time_s", None)
            lines.append(json.dumps(rec, sort_keys=True))
        return sorted(lines)

    assert canonical(tmp_path / "w1.jsonl") == canonical(tmp_path / "w2.jsonl")


def test_run_cell_record_schema(tmp_path):
    cell = CampaignCell("vgg16", 64, 64, "zc706", 16, 1)
    rec = run_cell(cell, **_FAST)
    assert rec["cell_key"] == cell.key
    assert rec["rav_hash"] == rav_hash(RAV(**rec["rav"]))
    assert rec["search"] == {"base_seed": 0, "population": 6,
                             "iterations": 4, "weights": None,
                             "searcher": "pso", "searcher_config": None,
                             "calibration": None}
    assert set(rec["objectives"]) >= {"throughput_ips", "gops", "latency_s",
                                      "dsp_eff", "bram_used", "feasible"}
    json.dumps(rec)  # JSONL-serializable


def test_campaign_report_frontier_and_ranking(tmp_path):
    cells = _small_cells()
    r = run_campaign(cells, str(tmp_path / "c.jsonl"), **_FAST)
    front = r.frontier()
    assert front
    for rec in front:
        assert len(rec["objectives"]) >= 3
    ranked = r.ranked()
    scores = [Objectives.from_dict(x["objectives"]).scalarize()
              for x in ranked]
    assert scores == sorted(scores, reverse=True)
    # frontier members are mutually non-dominated
    vecs = [Objectives.from_dict(x["objectives"]).canonical() for x in front]
    for i, a in enumerate(vecs):
        assert not any(dominates(b, a) for j, b in enumerate(vecs) if j != i)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_parsers():
    assert parse_inputs("224,320x480") == [(224, 224), (320, 480)]
    assert parse_weights("") is None
    assert parse_weights("throughput_ips=1,dsp_eff=500") == {
        "throughput_ips": 1.0, "dsp_eff": 500.0}


def test_cli_end_to_end(tmp_path, capsys):
    store = tmp_path / "cli.jsonl"
    argv = ["--nets", "vgg16", "--inputs", "64", "--fpgas", "zc706",
            "--precisions", "16,8", "--store", str(store),
            "--population", "6", "--iterations", "4",
            "--frontier-json", str(tmp_path / "front.json")]
    report = cli_main(argv)
    out = capsys.readouterr().out
    assert "Pareto frontier" in out
    assert store.exists()
    front = json.loads((tmp_path / "front.json").read_text())
    assert front and all(len(r["objectives"]) >= 3 for r in front)
    # second invocation resumes from the store
    report2 = cli_main(argv)
    assert report2.new_evaluations == 0
    assert report2.reused_cells == len(report.cells)


def _pool_probe(_):
    import os

    import jax
    return os.environ["JAX_PLATFORMS"], jax.default_backend()


def test_pool_workers_are_host_only(monkeypatch):
    """Campaign pool workers never claim the accelerator the parent owns:
    even where the host exports a TPU platform, a worker that touches JAX
    gets the CPU."""
    from repro.dse.campaign import host_pool
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with host_pool(1) as pool:
        assert pool.submit(_pool_probe, 0).result(timeout=300) == \
            ("cpu", "cpu")
