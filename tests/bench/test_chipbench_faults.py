"""A run whose timed path is broken underneath comes out not correct: the
harness is driven as on the chip (its look for a chip skipped) on the CPU
at a small size, with one fault planted in the program at a time."""
import dataclasses

import jax
import pytest

from bench_cells import run_small
from chipbench.drivers import cnn


def test_sound_runs_are_correct():
    for cell in ("vgg16.224", "dse.table3"):
        line = run_small(cell)
        assert line["correct"], line["checks"]
        assert list(line)[-1] == "checks"
        assert line["compiles_in_window"] == 0


def _faulty_step(fault):
    def program_step(self, net, plan):
        from repro.models.cnn import hybrid_forward
        return jax.jit(lambda p, x: fault(hybrid_forward(
            p, net, x, plan, use_pallas=True)))
    return program_step


@pytest.mark.parametrize("fault", [
    # an answer altered where it is produced: one image's features
    lambda y: y.at[0, :8].multiply(1.25),
    # half of the batch left out
    lambda y: y.at[y.shape[0] // 2:].set(0),
], ids=["answer_altered", "half_batch_left_out"])
def test_cnn_faults_are_not_correct(monkeypatch, fault):
    monkeypatch.setattr(cnn.Cell, "program_step", _faulty_step(fault))
    assert not run_small("vgg16.224")["correct"]


def test_dse_answer_altered_is_not_correct(monkeypatch):
    from repro.dse import backends
    program = backends.run_cell_by_backend

    def altered(*args, **kw):
        rec = program(*args, **kw)
        rec["objectives"]["throughput_ips"] *= 1 + 1e-6
        return rec
    monkeypatch.setattr(backends, "run_cell_by_backend", altered)
    line = run_small("dse.table3")
    assert not line["correct"]
    assert line["checks"]["record_max_rel_err"]["value"] > 0


def test_dse_half_the_screen_left_out_is_not_correct(monkeypatch):
    from repro.core import screen_jax
    program = screen_jax.screen_cells

    def half(stacked, positions):
        out = program(stacked, positions).copy()
        out[:, out.shape[1] // 2:] = 0.0
        return out
    monkeypatch.setattr(screen_jax, "screen_cells", half)
    line = run_small("dse.table3")
    assert not line["correct"]
    assert line["checks"]["screen_off_share"]["value"] > 0.4


def test_dse_store_that_drops_records_is_not_correct(monkeypatch):
    from repro.dse.store import CampaignStore
    program = CampaignStore.put
    calls = []

    def lossy(self, record):
        calls.append(record["cell_key"])
        if len(calls) % 2:
            program(self, record)
    monkeypatch.setattr(CampaignStore, "put", lossy)
    line = run_small("dse.table3")
    assert not line["correct"]
    assert line["checks"]["store_lost"]["value"] > 0


def _fewer_iterations(monkeypatch):
    from repro.core import explorer
    program = explorer.make_searcher

    def make(*args, **kw):
        s = program(*args, **kw)
        s.cfg = dataclasses.replace(s.cfg, iterations=1)
        return s
    monkeypatch.setattr(explorer, "make_searcher", make)


def _fewer_survivors(monkeypatch):
    from repro.core import explorer
    program = explorer.make_searcher

    def make(*args, **kw):
        s = program(*args, **kw)
        s.cfg = dataclasses.replace(s.cfg, survivors=2)
        return s
    monkeypatch.setattr(explorer, "make_searcher", make)


def _first_candidate(monkeypatch):
    from repro.core import explorer
    program = explorer.run_search

    def first(searcher, **kw):
        res = program(searcher, **kw)
        res.best_rav = searcher.space.to_rav(searcher.space.canonical()[0])
        return res
    monkeypatch.setattr(explorer, "run_search", first)


@pytest.mark.parametrize("plant", [_fewer_iterations, _fewer_survivors,
                                   _first_candidate],
                         ids=["fewer_iterations", "fewer_survivors",
                              "first_candidate_returned"])
def test_dse_degraded_search_is_not_correct(monkeypatch, plant):
    """A search that does less than the configuration states returns RAVs
    with honest objectives; the reference search catches it."""
    plant(monkeypatch)
    line = run_small("dse.table3")
    assert not line["correct"]
    assert line["checks"]["search_off_share"]["value"] > \
        line["checks"]["search_off_share"]["limit"]
    assert line["checks"]["record_max_rel_err"]["value"] == 0
