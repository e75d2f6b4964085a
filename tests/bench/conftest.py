"""Each of the benchmark's CPU tests keeps what its cells write in a
directory of its own: the test files run in parallel workers, and a DSE
cell empties its directory as it sets up, so cells of two tests sharing
``results/chipbench/<cell>`` would tear each other's stores. Runs on the
chip keep writing under ``results/chipbench/``."""
import pytest

from chipbench.drivers import dse


@pytest.fixture(autouse=True)
def own_results_dir(tmp_path, monkeypatch):
    init = dse.Cell.__init__

    def __init__(self, spec, *args, **kw):
        init(self, spec, *args, **kw)
        self.dir = tmp_path / "results" / spec.name
    monkeypatch.setattr(dse.Cell, "__init__", __init__)
