"""Every cell, rehearsed end to end on the CPU at a tiny size."""
import pytest

from bench import run

CELLS = ["ecg-offline-spans", "human-batch", "human-served"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(tiny_root, cell, trace):
    out = run.run_cell(tiny_root, cell, 2**31 + 7, 1.0, trace,
                       require_tpu=False)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1
    spec = run.load_cell(tiny_root, cell)
    want = spec["per_layer"] if trace else spec["end_to_end"]
    assert set(out["metrics"]) <= {m["name"] for m in want}
    if not trace:
        assert set(out["metrics"]) == {m["name"] for m in want}
        assert out["metrics"]["setup_s"]["value"] > 0
