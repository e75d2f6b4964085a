"""Cross-cell jax screening: BIT-equivalence against the NumPy
reference (:func:`repro.core.batch_eval.screen_rav_batch`) and campaign
parity with ``jax_screen=True``.

Exact equality (``np.array_equal``, not allclose) is the contract: the
jax kernel mirrors the reference operation-for-operation in
float64/int64, so any drift means a real divergence in the port, and
the ``screen_fits`` handoff into the hyperband searcher would silently
change search trajectories.
"""
import numpy as np
import pytest

from repro.core import screen_jax
from repro.core.batch_eval import screen_rav_batch
from repro.core.hw_specs import FPGAS
from repro.core.search import (SearchSpace, hyperband_rung0,
                               searcher_config_for)
from repro.dse.campaign import (build_net, cell_seed, expand_cells,
                                prescreen_cells_jax, run_campaign)

# A deliberately heterogeneous cell mix: different table lengths
# (vgg16 vs alexnet vs vgg19), precisions (alpha 2 vs 4), and boards —
# so the padded stacking is actually exercised.
CASES = [("vgg16", 224, 224, "ku115", 16),
         ("alexnet", 0, 0, "zcu102", 8),
         ("vgg19", 320, 320, "vu9p", 16),
         ("vgg16", 128, 128, "zc706", 8)]


def _spaces_and_tables():
    tables, spaces = [], []
    for net_name, h, w, fp, prec in CASES:
        net = build_net(net_name, h, w)
        spaces.append(SearchSpace(sp_max=len(net.major_layers), batch_max=8))
        tables.append(screen_jax.cell_tables(net, FPGAS[fp], prec, prec))
    return spaces, tables


def test_bit_equivalence_vs_numpy_reference():
    spaces, tables = _spaces_and_tables()
    rng = np.random.default_rng(11)
    blocks = [rng.uniform(sp.lo(), sp.hi(), size=(311, 5)) for sp in spaces]
    out = screen_jax.screen_cells(screen_jax.stack_cells(tables),
                                  np.stack(blocks))
    assert out.shape == (len(CASES), 311)
    for i, (net_name, h, w, fp, prec) in enumerate(CASES):
        ref = screen_rav_batch(build_net(net_name, h, w), FPGAS[fp],
                               blocks[i], prec, prec)
        assert np.array_equal(out[i], ref), f"cell {i} diverged"


def test_boundary_positions_bit_equal():
    """Degenerate candidates — sp=0 (no pipeline), full split, zero-ish
    bandwidth fractions — hit every where-guard in the kernel."""
    spaces, tables = _spaces_and_tables()
    blocks = []
    for sp in spaces:
        lo, hi = sp.lo(), sp.hi()
        blocks.append(np.stack([lo, hi, sp.canonical()[1],
                                [0.4, 1.0, 0.05, 0.05, 0.05],
                                [hi[0], hi[1], 0.95, 0.95, 0.05]]))
    out = screen_jax.screen_cells(screen_jax.stack_cells(tables),
                                  np.stack(blocks))
    for i, (net_name, h, w, fp, prec) in enumerate(CASES):
        ref = screen_rav_batch(build_net(net_name, h, w), FPGAS[fp],
                               blocks[i], prec, prec)
        assert np.array_equal(out[i], ref)


def test_prescreen_matches_searcher_rung0():
    """prescreen_cells_jax must score the EXACT block the hyperband
    searcher will ask for — same config construction, same rng draws."""
    cells = expand_cells(["vgg16"], [(224, 224)], ["ku115"], [16, 8], [1])
    overrides = {"screen": 256, "survivors": 4}
    fits = prescreen_cells_jax(cells, base_seed=3, population=6,
                               iterations=3, searcher_config=overrides)
    assert set(fits) == {c.key for c in cells}
    for c in cells:
        net = build_net(c.net, c.h, c.w)
        cfg = searcher_config_for(
            "hyperband",
            base=dict(population=6, iterations=3, patience=2,
                      seed=cell_seed(3, c)),
            overrides=overrides)
        space = SearchSpace(sp_max=len(net.major_layers),
                            batch_max=c.batch_max)
        block = hyperband_rung0(space, cfg)
        ref = screen_rav_batch(net, FPGAS[c.fpga], block,
                               c.precision, c.precision)
        assert np.array_equal(fits[c.key], ref)


def test_campaign_jax_screen_record_parity(tmp_path):
    cells = expand_cells(["vgg16"], [(224, 224)], ["ku115", "zcu102"],
                         [16], [1])
    kw = dict(searcher="hyperband",
              searcher_config={"screen": 256, "survivors": 4},
              population=6, iterations=3)
    plain = run_campaign(cells, str(tmp_path / "np.jsonl"), **kw)
    jaxed = run_campaign(cells, str(tmp_path / "jx.jsonl"),
                         jax_screen=True, **kw)
    for a, b in zip(plain.records, jaxed.records):
        sa = {k: v for k, v in a.items() if k != "search_time_s"}
        sb = {k: v for k, v in b.items() if k != "search_time_s"}
        assert sa == sb
    # and the two stores resume each other: same search config
    resumed = run_campaign(cells, str(tmp_path / "jx.jsonl"), **kw)
    assert resumed.reused_cells == len(cells)


def test_jax_screen_rejected_off_hyperband(tmp_path):
    cells = expand_cells(["vgg16"], [(224, 224)], ["ku115"], [16], [1])
    with pytest.raises(ValueError, match="hyperband"):
        run_campaign(cells, str(tmp_path / "x.jsonl"), jax_screen=True)


def test_screen_cells_shape_validation():
    _, tables = _spaces_and_tables()
    stacked = screen_jax.stack_cells(tables)
    with pytest.raises(ValueError, match=r"\(cells, n, 5\)"):
        screen_jax.screen_cells(stacked, np.zeros((2, 7)))
    with pytest.raises(ValueError, match="stacked cells"):
        screen_jax.screen_cells(stacked, np.zeros((1, 7, 5)))
