"""A new configuration, traffic driver, mix, cell and per-layer metric
are found from new files and new ``BENCHMARK.json`` entries alone."""
import json

from bench import run

DRIVER = '''
from bench.traffic.offline_batch import Driver as Batch


class Driver(Batch):
    """Offline batch that marks each call, to show that it ran."""

    def window(self, seconds):
        res = super().window(seconds)
        for c in res["calls"]:
            c["marker"] = 7
        return res
'''

METRIC = '''
def read(rec):
    return sum(c.get("marker", 0) for c in rec["calls"][:1])
'''


def test_new_cell_from_new_files(tiny_root):
    b = tiny_root / "bench"
    (b / "configs" / "tiny.json").write_text(json.dumps(
        {"ref_size": 200, "query_size": 10, "num_queries": 4}))
    (b / "traffic" / "marked_batch.py").write_text(DRIVER)
    (b / "traffic" / "marked.json").write_text(json.dumps(
        {"driver": "marked_batch", "spans": True, "batches": 1,
         "planted": 1}))
    (b / "metrics" / "marker.py").write_text(METRIC)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-marked", "config": "tiny",
                              "traffic": "marked", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny-marked")
    spec["per_layer"].append({"name": "marker", "unit": "n",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "cells_per_s",
                              "workloads": ["tiny-marked"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    plain = run.run_cell(tiny_root, "tiny-marked", 1, 0.5, False,
                         require_tpu=False)
    assert plain["correct"]
    assert set(plain["metrics"]) == {"cells_per_s", "setup_s"}
    traced = run.run_cell(tiny_root, "tiny-marked", 1, 0.5, True,
                          require_tpu=False)
    assert traced["metrics"]["marker"]["value"] == 7
