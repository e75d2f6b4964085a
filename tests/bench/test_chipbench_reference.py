"""The plain references against the system they stand in for, on the CPU
at small sizes: the FPGA model and screen decision for decision, the
frozen net tables against the program's nets, the VGG forward against
the program's lax.conv forward."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.reference import fpga

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "chipbench/configs/dse-fpga-hyperband.json")
                 .read_text())
CASES = [("vgg16", 224, 224, "ku115", 16), ("vgg16", 32, 32, "zc706", 8),
         ("vgg16", 720, 1280, "vu9p", 16), ("googlenet", 0, 0, "zcu102", 8),
         ("mobilenetv2", 0, 0, "ku115", 16), ("resnet50", 0, 0, "zc706", 16),
         ("vgg19", 224, 224, "zcu102", 16)]


def _program(net, h, w, part):
    from repro.core.hw_specs import FPGAS
    from repro.dse.campaign import build_net
    return build_net(net, h, w), FPGAS[part]


@pytest.mark.parametrize("net,h,w,part,bits", CASES)
def test_net_tables_equal_the_programs(net, h, w, part, bits):
    pnet, _ = _program(net, h, w, part)
    want = [[l.kind, l.h, l.w, l.c, l.k, l.r, l.s, l.stride, l.groups]
            for l in pnet.layers]
    got = [[l.kind, l.h, l.w, l.c, l.k, l.r, l.s, l.stride, l.groups]
           for l in fpga.net_layers(CFG, net, h, w)]
    assert got == want


@pytest.mark.parametrize("net,h,w,part,bits", CASES)
def test_evaluate_equals_the_programs_model(net, h, w, part, bits):
    from repro.core.local_opt import RAV, evaluate_rav
    from repro.dse.objectives import Objectives
    pnet, ppart = _program(net, h, w, part)
    layers = fpga.net_layers(CFG, net, h, w)
    rpart = fpga.Part(**CFG["parts"][part])
    rng = np.random.default_rng(len(layers))
    n_major = len(pnet.major_layers)
    for _ in range(40):
        rav = dict(sp=int(rng.integers(0, n_major + 1)),
                   batch=int(rng.integers(1, 9)),
                   dsp_frac=float(rng.uniform(0.05, 0.95)),
                   bram_frac=float(rng.uniform(0.05, 0.95)),
                   bw_frac=float(rng.uniform(0.05, 0.95)))
        want = Objectives.from_design(evaluate_rav(
            pnet, ppart, RAV(**rav), bits, bits)).as_dict()
        assert fpga.evaluate(layers, rpart, rav, bits, bits) == want


@pytest.mark.parametrize("net,h,w,part,bits", CASES)
def test_screen_equals_the_programs_screen(net, h, w, part, bits):
    from repro.core.batch_eval import screen_rav_batch
    pnet, ppart = _program(net, h, w, part)
    n_major = len(pnet.major_layers)
    rng = np.random.default_rng(7)
    pos = rng.uniform([0, 1, 0.01, 0.01, 0.01], [n_major, 8, 0.99, 0.99,
                                                 0.99], size=(512, 5))
    want = screen_rav_batch(pnet, ppart, pos, bits, bits)
    got = fpga.screen(fpga.net_layers(CFG, net, h, w),
                      fpga.Part(**CFG["parts"][part]), pos, bits, bits)
    np.testing.assert_array_equal(got, want)


def test_vgg_reference_matches_the_programs_forward():
    import jax
    import jax.numpy as jnp
    from chipbench.reference import cnn
    from repro.core.netinfo import vgg16
    from repro.models.cnn import forward
    vgg = json.loads((ROOT / "chipbench/configs/vgg16.json").read_text())
    weights = cnn.init_weights(jax.random.key(0), vgg["convs"], jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 3, 32, 32), jnp.float32)
    it = iter(weights)
    net = vgg16(32, 32)
    params = [None if l.kind == "pool" else next(it) for l in net.layers]
    with jax.default_matmul_precision("highest"):
        want = forward(params, net, x)
    got = cnn.forward(weights, set(vgg["pools_after"]), x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("net,h,w,part,bits", CASES[:5])
def test_search_finds_the_programs_rav(net, h, w, part, bits):
    from chipbench.reference import search
    from repro.dse.campaign import CampaignCell, run_cell
    cell = CampaignCell(net, h, w, part, bits, 1)
    for base_seed in (3, 2**31 + 11):
        rec = run_cell(cell, base_seed, CFG["population"], CFG["iterations"],
                       searcher=CFG["searcher"],
                       searcher_config=CFG["searcher_config"])
        c = dict(net=net, h=h, w=w, fpga=part, precision=bits, batch_max=1)
        assert search.cell_key(c) == cell.key
        got = search.Search(CFG, fpga.net_layers(CFG, net, h, w),
                            fpga.Part(**CFG["parts"][part]), c,
                            base_seed).run()
        assert got["rav"] == rec["rav"]
        assert got["fitness"] == pytest.approx(rec["fitness"], rel=1e-12)
        assert (got["evaluations"], got["iterations"], got["screened"]) == \
            (rec["evaluations"], rec["iterations"], rec["trace"]["screened"])
