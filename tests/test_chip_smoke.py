"""``chip_smoke.py`` rehearsed on the CPU at small sizes: its phases run
end to end here, and the script itself refuses to report without a TPU.
On the CPU the Pallas kernels are interpreted, so the cnn phase's own
gate (a compiled kernel) cannot pass here and is not run; the pipeline
phase needs four devices (tests/test_serving.py runs its path on
virtual ones)."""
import json

import chip_smoke
from repro.configs import get_config


def test_smoke_refuses_without_tpu(capsys, tmp_path):
    assert chip_smoke.main(["--out", str(tmp_path)]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs 1 TPU chip" in out.err


def test_dse_phase_gates_hold_on_cpu(tmp_path):
    res = chip_smoke.phase_dse(tmp_path)
    assert res["ok"], res
    # CPU float64 is IEEE: the device screen is bit-equal to NumPy here
    assert res["screen_bit_equal_cells"] == res["cells"] == 12
    assert res["worker_backends"] == ["cpu"]
    json.dumps(res)


def test_train_phase_sizes_then_steps(tmp_path):
    cfg = get_config("xlstm-350m").reduced()
    res = chip_smoke.phase_train(tmp_path, cfg=cfg, seq=32, batches=(4, 2),
                                 budget=1e12)
    assert res["ok"] and res["batch"] == 4 and len(res["losses"]) == 3
    # a budget below every candidate refuses them all
    res = chip_smoke.phase_train(tmp_path, cfg=cfg, seq=32, batches=(2,),
                                 budget=1.0)
    assert not res["ok"] and res["tried"][0]["batch"] == 2

