"""The block sweep's phases and one tiny phase in interpret mode."""
import jax

from bench import block_sweep


def test_phases_are_well_formed():
    names = [name for name, _ in block_sweep.phases()]
    assert names[:2] == ["cells", "grid"]
    for _, specs in block_sweep.phases():
        for s in specs:
            assert s["variant"] in block_sweep.VARIANTS
            assert s["bm"] % 128 == 0 and s["bq"] % 8 == 0
            assert s["m"] >= s["bm"] and s["rt"] in (1, 2, 8)


def test_tiny_phase_rows():
    rows = []
    specs = [block_sweep._spec("cells", v, 12, 10, 300, bq, 128, 2)
             for v in ("plain", "spans") for bq in (8, 16)]
    block_sweep.run_phase("cells", specs, True, jax.devices()[0],
                          rows.append, workers=2)
    assert len(rows) == 4
    for row in rows:
        assert "error" not in row, row
        assert row["cells_per_s"] > 0 and row["us_per_tile_row"] > 0
        assert row["same_answers"] is True
