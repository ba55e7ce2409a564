"""The control, the int16 reference in the program's place, fails the
checks that the program passes (at a size a CPU run holds; on the chip
it runs at each cell's own size, see ``bench/control.py``)."""
import json

import pytest

from bench import control


@pytest.mark.parametrize("cell", ["ecg-offline-spans", "human-batch",
                                  "human-served"])
def test_control_fails_program_passes(tiny_root, cell):
    # queries long enough that anomaly bursts push sums past int16
    for name in ("ecg", "human"):
        path = tiny_root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        path.write_text(json.dumps(dict(cfg, query_size=64)))
    mix = tiny_root / "bench" / "traffic" / "served-open.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   query_pool=512)))
    program, ctrl, _ = control.readings(tiny_root, cell, 3, 1.0,
                                        require_tpu=False)
    assert all(c["value"] <= c["limit"] for c in program.values()), program
    assert any(c["value"] > c["limit"] for c in ctrl.values()), ctrl
