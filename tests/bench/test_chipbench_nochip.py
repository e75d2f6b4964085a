"""The command refuses to measure without a TPU: it exits nonzero and
prints no result line, also in a checkout that holds only the
benchmark's files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["chipbench/run.py", "--workload", "vgg16.224", "--seed",
        str(2**31 + 7), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu_device():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
