"""The sDTW kernel compiles for a TPU v5e, without one attached.

Mosaic (the TPU kernel compiler) refuses constructs that Pallas interpret
mode accepts — dynamic slices, lane slices not provably 128-aligned,
zero-width vectors — so interpret-mode tests alone cannot show that the
kernel runs on the chip. These tests compile it for a *described* v5e
topology (``jax.experimental.topologies``) at the paper's Table V query
lengths, with the TPU default block and with the autotuner's TPU pick
for a batch of 16 and for the batch sizes of the two batch cells (128
and 16,384 queries), where the pick is a tall block; and at the
384-lane block a monitoring tick of 360 samples runs as.

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
#: The block each case compiles: the kernel's TPU default, or the tuner's
#: pick for a batch of that many queries.
BLOCKS = {"default": None, "tuned": BATCH, "tuned-b128": 128,
          "tuned-b16384": 16384}
VARIANTS = {
    "plain": {},
    "spans": {"return_spans": True},
    "lastrow": {"return_lastrow": True, "track_start": True},
}
#: The last-row capture returns a (batch, M) row, so a large batch gets a
#: slice of the reference (as the search and stream paths hand the kernel
#: slices): at most this many cells per returned row array.
LASTROW_CELLS = 1 << 28


def _ref_len(batch, n, variant):
    if variant == "lastrow":
        return min(TABLE_V[n], LASTROW_CELLS // batch)
    return TABLE_V[n]


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
    """The autotuner's TPU block pick per (batch, N, variant), priced with
    the cost constants of the described chip's ``device_kind``. The
    tuner's process caches are emptied before and after, so no other test
    sees TPU decisions made on a CPU host."""
    kind = topo.devices[0].device_kind
    picks = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(platforms, "tpu_device_kind", lambda: kind)
        mp.setattr(cost, "_MODELS", {})
        clear_tuning_cache()
        try:
            for b in set(BLOCKS.values()) - {None}:
                for n in TABLE_V:
                    for variant in VARIANTS:
                        picks[b, n, variant] = tuned_blocks(
                            b, _ref_len(b, n, variant), n=n, backend="tpu",
                            span=variant != "plain",
                            lastrow=variant == "lastrow")
        finally:
            clear_tuning_cache()
    return picks


def _compile(one_chip, n, m, batch=BATCH, **kw):
    q = jax.ShapeDtypeStruct((batch, n), jnp.int32, sharding=one_chip)
    r = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q, r: sdtw_pallas(q, r, interpret=False, tune="off",
                                          **kw))
    return fn.lower(q, r).compile()


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("n", sorted(TABLE_V))
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_kernel_compiles_for_v5e(one_chip, tpu_picks, block, n, variant):
    kw = dict(VARIANTS[variant])
    batch = BLOCKS[block] or BATCH
    if BLOCKS[block] is not None:
        bq, bm, scheme, rt = tpu_picks[batch, n, variant]
        assert scheme == "shift", "the TPU tuner offered a scheme Mosaic " \
                                  "cannot lower"
        if batch > BATCH:
            assert bq >= 32, "a batch this large should get a tall block"
        kw.update(block_q=bq, block_m=bm, scan_scheme=scheme, row_tile=rt)
    compiled = _compile(one_chip, n, _ref_len(batch, n, variant), batch,
                        **kw)
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


def test_fleet_tile_step_compiles_for_v5e(one_chip, topo):
    """A many-stream session's tile step at the ECG monitoring
    deployment's shape — 1,024 streams × 16 templates of 512, ticks of
    360 samples, alerts reduced on the device — compiles as one program
    around one Mosaic kernel, with the tuner's block for a per-row
    reference: a tall block that packs rows of several streams."""
    from repro.kernels.sdtw import ops
    from repro.stream import session
    streams, templates, n, tick = 1024, 16, 512, 360
    rows = streams * templates

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(platforms, "tpu_device_kind",
                   lambda: topo.devices[0].device_kind)
        mp.setattr(ops, "interpret_mode", lambda backend=None: False)
        mp.setattr(cost, "_MODELS", {})
        clear_tuning_cache()
        try:
            bq, bm, _, _ = tuned_blocks(rows, tick, n=n, backend="tpu",
                                        lastrow=True, rowref=True)
            compiled = session._exact_step.lower(
                spec(rows, n), spec(streams, tick), spec(rows),
                (spec(rows, n), spec(rows), spec(rows)), spec(), spec(),
                spec(rows), spec(rows), spec(rows), spec(), impl="pallas",
                metric="abs_diff", block_q=None, block_m=None,
                scan_scheme=None, row_tile=None, k=1,
                excl_span=False, track=False, want_lastrow=True,
                with_heap=False, alert=True).compile()
        finally:
            clear_tuning_cache()
    assert bq >= 2 * templates and bm >= tick
    # a 360-sample tick runs as three whole vregs, not a 512-lane tile
    assert bm == 384
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("m", [360, 1000])
@pytest.mark.parametrize("variant", sorted(VARIANTS) + ["rowref"])
def test_384_lane_block_compiles_for_v5e(one_chip, variant, m):
    """A (128, 384) block — a reference tile of three vregs, not a power
    of two, whose scan still runs 9 shift steps — lowers with Mosaic in
    every kernel variant, over one tile (a 360-sample tick) and three."""
    batch, n = 128, 512
    kw = dict(VARIANTS.get(variant, {}), block_q=batch, block_m=384,
              scan_scheme="shift", row_tile=2)
    q = jax.ShapeDtypeStruct((batch, n), jnp.int32, sharding=one_chip)
    r = jax.ShapeDtypeStruct((batch, m) if variant == "rowref" else (m,),
                             jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q, r: sdtw_pallas(q, r, interpret=False, tune="off",
                                          **kw))
    compiled = fn.lower(q, r).compile()
    assert "tpu_custom_call" in compiled.as_text()
