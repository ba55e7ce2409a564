"""The four-replica served cell, rehearsed on the CPU at a tiny size on four
host devices, in a subprocess (the test process holds one device): 20
requests of one query of 12 against 300 samples, in one second."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

CELL = "human-served-4rep"
TINY = {"rate_per_s": 20, "query_pool": 2048}

SCRIPT = """
import json, sys
from pathlib import Path
import numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import run
load = run.load_module

def load_with_fault(path):
    mod = load(path)
    if path.stem == "served_open" and sys.argv[5] == "1":
        window = mod.Driver.window

        def faulty(self, seconds):
            res = window(self, seconds)
            i = min(self.answers)
            self.answers[i] = np.asarray(self.answers[i]) + 1
            return res

        mod.Driver.window = faulty
    return mod

run.load_module = load_with_fault
out = run.run_cell(Path(sys.argv[2]), sys.argv[3], 2**31 + 7, 1.0,
                   sys.argv[4] == "1", require_tpu=False)
spec = run.load_cell(Path(sys.argv[2]), sys.argv[3])
print(json.dumps({"out": out, "chips": spec["chips"],
                  "e2e": [m["name"] for m in spec["end_to_end"]],
                  "layer": [m["name"] for m in spec["per_layer"]]}))
"""


def _run(root, trace, fault):
    path = root / "bench" / "traffic" / "served-open-4rep.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TINY}))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(ROOT), str(root), CELL,
         str(int(trace)), str(int(fault))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_on_four_devices(tiny_root, trace):
    res = _run(tiny_root, trace, fault=False)
    out = res["out"]
    assert res["chips"] == 4
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] == 20 and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == set(res["e2e"]) == {"served_p50_ms",
                                                          "setup_s"}
        return
    assert set(res["layer"]) == {
        "device.idle_pct.serve", "serve.router_ms.mean",
        "serve.queue_ms.p50", "engine.host_ms.serve",
        "serve.worker_busy_pct", "kernel.ms_per_launch.serve"}
    # the CPU's trace holds no kernel operation to read
    assert set(out["metrics"]) == set(res["layer"]) - {
        "kernel.ms_per_launch.serve"}


def test_altered_answer_is_not_correct(tiny_root):
    out = _run(tiny_root, False, fault=True)["out"]
    assert out["correct"] is False
    assert out["checks"]["dist_wrong"]["value"] > 0
