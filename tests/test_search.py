"""Searcher-protocol tests: golden PSO trajectories (bit-identity with
the pre-refactor implementation), cross-engine conformance over both
engines, the engine table's config plumbing, and the resume key.

The golden fixture (``tests/data/pso_golden.json``) was captured from
the monolithic ``pso.optimize`` BEFORE the ask/tell refactor; the
refactored ``PSOSearcher`` + ``run_search`` must reproduce it exactly —
discrete fields bit-identical, floats to 1e-9 relative. Regenerate the
fixture only on an intentional algorithm change.
"""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core import PSOConfig, explore
from repro.core.batch_eval import evaluate_rav_batch
from repro.core.hw_specs import FPGAS
from repro.core.netinfo import vgg16
from repro.core.search import (SEARCHERS, PSOSearcher, SearchSpace,
                               make_searcher, run_search, searcher_names)
from repro.dse.campaign import expand_cells, run_campaign, run_cell
from repro.dse.store import ResultStore, open_store

GOLDEN = Path(__file__).parent / "data" / "pso_golden.json"


def _golden_cases():
    with GOLDEN.open() as f:
        return json.load(f)["cases"]


@pytest.mark.parametrize("case", _golden_cases(),
                         ids=lambda c: f"seed{c['seed']}_{c['fpga']}")
def test_pso_golden_trajectory(case):
    """The refactored PSOSearcher replays pre-refactor trajectories
    bit-for-bit: same RNG draw order, same dedup/memo behavior, same
    stop reason."""
    net = vgg16(case["input"])
    cfg = PSOConfig(population=case["population"],
                    iterations=case["iterations"],
                    patience=case["patience"], seed=case["seed"])
    res = explore(net, FPGAS[case["fpga"]], dw=case["dw"], ww=case["ww"],
                  batch_max=case["batch_max"], cfg=cfg).pso

    assert res.best_rav.sp == case["best_rav"][0]
    assert res.best_rav.batch == case["best_rav"][1]
    for got, want in zip((res.best_rav.dsp_frac, res.best_rav.bram_frac,
                          res.best_rav.bw_frac), case["best_rav"][2:]):
        assert math.isclose(got, want, rel_tol=1e-9), (got, want)
    assert math.isclose(res.best_fitness, case["best_fitness"],
                        rel_tol=1e-9)
    assert res.iterations_run == case["iterations_run"]
    assert res.evaluations == case["evaluations"]
    assert res.cache_hits == case["cache_hits"]
    assert res.stop_reason == case["stop_reason"]
    assert len(res.history) == len(case["history"])
    for got, want in zip(res.history, case["history"]):
        assert math.isclose(got, want, rel_tol=1e-9), (got, want)


# ---------------------------------------------------------------------------
# cross-searcher conformance: both engines honor the protocol
# ---------------------------------------------------------------------------


_NET = vgg16(64)
_FPGA = FPGAS["zc706"]
_BMAX = 2
# tiny budgets so hyperband's screen rung stays cheap under pytest
_OVERRIDES = {"hyperband": {"screen": 256, "survivors": 4}}


def _run(name, seed=5):
    cfg = PSOConfig(population=6, iterations=5, patience=2, seed=seed)
    return explore(_NET, _FPGA, batch_max=_BMAX, cfg=cfg, searcher=name,
                   searcher_config=_OVERRIDES.get(name))


@pytest.mark.parametrize("name", searcher_names())
def test_searcher_conformance(name):
    """Every engine returns a bounds-valid best RAV, stays within its own
    declared evaluation cap, reports a stop reason, and is deterministic
    under a fixed seed."""
    res = _run(name)
    p = res.pso
    sp_max = len(_NET.major_layers)

    r = res.design.rav
    assert 0 <= r.sp <= sp_max
    assert 1 <= r.batch <= _BMAX
    for frac in (r.dsp_frac, r.bram_frac, r.bw_frac):
        assert 0.05 <= frac <= 0.95

    space = SearchSpace(sp_max=sp_max, batch_max=_BMAX)
    engine = make_searcher(
        name, space,
        base=dict(population=6, iterations=5, patience=2, seed=5),
        overrides=_OVERRIDES.get(name))
    assert p.evaluations <= engine.eval_cap(), \
        f"{name}: {p.evaluations} full evals > declared cap"

    assert p.engine == name
    assert p.stop_reason in ("converged", "iteration_cap")
    assert p.iterations_run >= 0
    assert len(p.history) >= 1
    assert math.isclose(p.history[-1], p.best_fitness, rel_tol=1e-9)
    # histories are monotone: each entry is the best-so-far
    assert all(b >= a - 1e-12 for a, b in zip(p.history, p.history[1:]))

    again = _run(name).pso
    assert again.best_fitness == p.best_fitness
    assert again.history == p.history
    assert again.evaluations == p.evaluations


@pytest.mark.parametrize("name", searcher_names())
def test_searcher_store_roundtrip(name, tmp_path):
    """A campaign record produced under any engine survives the JSONL
    store round trip with its convergence trace intact."""
    cell = expand_cells(["vgg16"], [(64, 64)], ["zc706"], [16], [_BMAX])[0]
    rec = run_cell(cell, base_seed=5, population=6, iterations=5,
                   searcher=name, searcher_config=_OVERRIDES.get(name))
    assert rec["trace"]["engine"] == name
    if name == "hyperband":
        assert rec["trace"]["screened"] > 0

    store = ResultStore(tmp_path / "s.jsonl")
    store.put(rec)
    back = ResultStore(tmp_path / "s.jsonl").get(cell.key)
    assert back is not None
    assert back["trace"] == rec["trace"]
    assert back["search"] == rec["search"]
    # the resume key is whole whatever the engine
    assert set(back["search"]) == _RESUME_KEY
    assert back["search"]["searcher"] == name
    assert back["search"]["searcher_config"] == _OVERRIDES.get(name)
    assert back["search"]["calibration"] is None


_RESUME_KEY = {"base_seed", "population", "iterations", "weights",
               "searcher", "searcher_config", "calibration"}


def test_registry_and_config_plumbing():
    names = searcher_names()
    assert names == ["hyperband", "pso"]
    assert set(names) == set(SEARCHERS)

    space = SearchSpace(sp_max=10, batch_max=2)
    with pytest.raises(ValueError):
        make_searcher("no_such_engine", space)
    with pytest.raises(ValueError):
        make_searcher("pso", space, overrides={"bogus_field": 1})
    # base keys an engine doesn't have are dropped; overrides coerce to
    # the config field's type
    eng = make_searcher("hyperband", space,
                        base=dict(population=4, inertia=0.7),
                        overrides={"screen": "512"})
    assert eng.cfg.population == 4
    assert eng.cfg.screen == 512
    assert not hasattr(eng.cfg, "inertia")


def test_explore_carries_every_pso_field_into_the_engine():
    """A ``searcher_config`` does not drop the caller's other PSO fields:
    the swarm runs with ``cfg``'s inertia and the override's patience."""
    cfg = PSOConfig(population=8, iterations=8, inertia=0.5, c_local=1.2,
                    seed=5)
    got = explore(_NET, _FPGA, batch_max=_BMAX, cfg=cfg, searcher="pso",
                  searcher_config={"patience": 3}).pso
    space = SearchSpace(sp_max=len(_NET.major_layers), batch_max=_BMAX)
    want = run_search(
        PSOSearcher(space, dataclasses.replace(cfg, patience=3)),
        batch_fitness_fn=lambda ravs: [
            d.fitness for d in evaluate_rav_batch(_NET, _FPGA, ravs, 16, 16)])
    assert got.best_rav == want.best_rav
    assert got.best_fitness == want.best_fitness
    assert got.evaluations == want.evaluations
    assert got.history == want.history


def test_explore_rejects_screen_fits_of_another_length():
    """Precomputed rung-0 fitnesses come from the same config as the
    engine; a block of another length is a bug, not a reason to screen
    again."""
    with pytest.raises(ValueError, match="screen_fits"):
        explore(_NET, _FPGA, batch_max=_BMAX,
                cfg=PSOConfig(population=6, iterations=5, seed=5),
                searcher="hyperband", searcher_config=_OVERRIDES["hyperband"],
                screen_fits=np.zeros(_OVERRIDES["hyperband"]["screen"] - 1))


def test_resume_reruns_a_partial_key_and_reuses_the_whole_one(tmp_path):
    """A record whose ``search`` lacks the engine and calibration keys
    (the form stores took before every key was written) re-runs; a record
    with the whole key is reused."""
    cells = expand_cells(["vgg16"], [(64, 64), (96, 96)], ["zc706"], [16],
                         [1])
    path = str(tmp_path / "s.jsonl")
    kw = dict(population=6, iterations=4)
    assert run_campaign(cells, path, **kw).new_cells == 2
    store = open_store(path)
    old = store.get(cells[0].key)
    old["search"] = {k: v for k, v in old["search"].items()
                     if k not in ("searcher", "searcher_config",
                                  "calibration")}
    store.put(old)

    again = run_campaign(cells, path, **kw)
    assert (again.reused_cells, again.new_cells) == (1, 1)
    assert again.records[0]["cell_key"] == cells[0].key
    assert set(open_store(path).get(cells[0].key)["search"]) == _RESUME_KEY
