"""Fixtures for the benchmark's own tests (``python -m pytest bench/tests``,
with ``JAX_PLATFORMS=cpu``): a copy of the benchmark whose cells run at
sizes the CPU holds, with the Pallas kernel in interpret mode."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: Per-file overrides that shrink every cell to a CPU-sized rehearsal.
TINY = {
    "configs/ecg.json": {"ref_size": 700, "query_size": 16,
                         "num_queries": 8},
    "configs/human.json": {"ref_size": 300, "query_size": 12,
                           "num_queries": 16},
    "traffic/offline-spans.json": {"batches": 2, "planted": 2},
    "traffic/offline-plain.json": {"batches": 2, "planted": 2},
    "traffic/served-open.json": {"rate_per_s": 20, "query_pool": 2048},
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding ``BENCHMARK.json`` and ``bench/`` at tiny sizes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, over in TINY.items():
        path = tmp_path / "bench" / rel
        path.write_text(json.dumps({**json.loads(path.read_text()),
                                    **over}))
    return tmp_path
