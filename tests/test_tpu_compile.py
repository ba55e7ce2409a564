"""The sDTW kernel compiles for a TPU v5e, without one attached.

Mosaic (the TPU kernel compiler) refuses constructs that Pallas interpret
mode accepts — dynamic slices, lane slices not provably 128-aligned,
zero-width vectors — so interpret-mode tests alone cannot show that the
kernel runs on the chip. These tests compile it for a *described* v5e
topology (``jax.experimental.topologies``) at the paper's Table V query
lengths, with the TPU default block and with the autotuner's TPU pick.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file. Keep these tests in this one file.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import platforms
from repro.kernels.sdtw import interpret_mode, sdtw_pallas
from repro.launch.roofline import chip_peaks
from repro.tune import canonical_backend, clear_tuning_cache, cost, \
    tuned_blocks

from oracle import sdtw_span

#: Table V reference length per query length (Seismology, ECG, Power).
TABLE_V = {64: 1_727_990, 512: 1_800_000, 1536: 1_754_985}
BATCH = 16
VARIANTS = {
    "plain": {},
    "spans": {"return_spans": True},
    "lastrow": {"return_lastrow": True, "track_start": True},
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_picks(topo):
    """The autotuner's TPU block pick per (N, span), priced with the cost
    constants of the described chip's ``device_kind``. The tuner's process
    caches are emptied before and after, so no other test sees TPU
    decisions made on a CPU host."""
    kind = topo.devices[0].device_kind
    picks = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(platforms, "tpu_device_kind", lambda: kind)
        mp.setattr(cost, "_MODELS", {})
        clear_tuning_cache()
        try:
            for n, m in TABLE_V.items():
                for span in (False, True):
                    picks[n, span] = tuned_blocks(BATCH, m, n=n,
                                                  backend="tpu", span=span)
        finally:
            clear_tuning_cache()
    return picks


def _compile(one_chip, n, m, **kw):
    q = jax.ShapeDtypeStruct((BATCH, n), jnp.int32, sharding=one_chip)
    r = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q, r: sdtw_pallas(q, r, interpret=False, tune="off",
                                          **kw))
    return fn.lower(q, r).compile()


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("n", sorted(TABLE_V))
@pytest.mark.parametrize("block", ["default", "tuned"])
def test_kernel_compiles_for_v5e(one_chip, tpu_picks, block, n, variant):
    kw = dict(VARIANTS[variant])
    if block == "tuned":
        bq, bm, scheme, rt = tpu_picks[n, variant != "plain"]
        assert scheme == "shift", "the TPU tuner offered a scheme Mosaic " \
                                  "cannot lower"
        kw.update(block_q=bq, block_m=bm, scan_scheme=scheme, row_tile=rt)
    compiled = _compile(one_chip, n, TABLE_V[n], **kw)
    # A Mosaic kernel, not an interpreted (XLA-lowered) grid.
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_assoc_scan_is_refused_on_tpu(one_chip):
    with pytest.raises(ValueError, match="assoc"):
        _compile(one_chip, 64, 4096, scan_scheme="assoc")


def test_interpret_mode_is_decided_once():
    assert interpret_mode("cpu") is True
    assert interpret_mode("tpu") is False
    with pytest.raises(RuntimeError, match="no lowering"):
        interpret_mode("gpu")
    with pytest.raises(ValueError, match="no tuning backend"):
        canonical_backend("gpu")


def test_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(platforms, "tpu_device_kind",
                        lambda: "TPU v99 unreleased")
    with pytest.raises(ValueError, match="device_kind"):
        platforms.backend_model("tpu")
    with pytest.raises(ValueError, match="device_kind"):
        chip_peaks("TPU v99 unreleased")
    assert chip_peaks("TPU v5 lite")["hbm_bw"] == 819e9
    assert platforms.backend_model("interpret") is \
        platforms.INTERPRET_BACKEND


def test_row_blocks_crossing_a_lane_window():
    """N > 128 puts the queries and the boundary column in two 128-lane
    windows; with ``row_tile=3`` the row block 126..128 straddles the
    window edge, and the short queries end inside (129) or just past it
    (130). Several reference tiles make every boundary entry cross the
    scratch. Bitwise against the oracle."""
    row_tile = 3
    rng = np.random.default_rng(11)
    n, m = 200, 90
    q = rng.integers(-50, 50, (3, n)).astype(np.int32)
    r = rng.integers(-50, 50, (m,)).astype(np.int32)
    qlens = np.array([200, 130, 129], np.int32)
    d, s, e = sdtw_pallas(jnp.asarray(q), jnp.asarray(r), jnp.asarray(qlens),
                          block_q=2, block_m=16, row_tile=row_tile,
                          scan_scheme="shift", return_spans=True)
    plain = sdtw_pallas(jnp.asarray(q), jnp.asarray(r), jnp.asarray(qlens),
                        block_q=2, block_m=16, row_tile=row_tile,
                        scan_scheme="shift")
    for i in range(3):
        want = sdtw_span(q[i, :qlens[i]], r)
        got = (float(d[i]), int(s[i]), int(e[i]))
        assert got == want, (i, got, want)
        assert float(plain[i]) == want[0]
