"""The command refuses a host without the chip, and a checkout without
the program, with a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "human-batch",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_result():
    p = _run(ROOT)
    assert p.returncode == 3
    assert p.stdout.strip() == ""


def test_benchmark_alone_exits_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
