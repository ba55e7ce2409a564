"""Many streams in one session: ``engine.stream(..., streams=S)``.

A fleet session stacks S aligned streams that share the query set and
advances them all with one device step per tile, through a kernel launch
whose rows read each their own stream (a per-row reference). Each
stream's distances, spans and alerts must equal those of its own
one-stream session and of the numpy oracle, bitwise (int32).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stream
from repro.kernels.sdtw import sdtw_pallas
from repro.stream import StreamSession
from repro.tune import cost

from oracle import sdtw_last_row, tile_alerts

S = 3
CHUNK = 16
#: Uneven feed pieces over 150 samples: tile-aligned, single samples and
#: runs that straddle tile edges.
PIECES = [20, 1, 3, 50, 16, 60]
THRESHOLD = 60


def _fleet_inputs(rng):
    qs = [rng.integers(-20, 20, n).astype(np.int32) for n in (5, 11, 7)]
    streams = rng.integers(-20, 20, (S, sum(PIECES))).astype(np.int32)
    streams[1, 40:51] = qs[1]                  # one exact match
    return qs, streams


def _feed(session, data):
    off = 0
    for p in PIECES:
        session.feed(data[..., off:off + p])
        off += p
    return session.flush()


@pytest.mark.parametrize("impl", ["rowscan", "pallas"])
def test_fleet_equals_single_sessions_and_oracle(impl, rng):
    qs, data = _fleet_inputs(rng)
    kw = dict(chunk=CHUNK, impl=impl, return_spans=True,
              alert_threshold=THRESHOLD)
    fleet = _feed(stream(qs, streams=S, **kw), data)
    got = fleet.results()
    assert np.asarray(got.distances).shape == (S, len(qs))
    for s in range(S):
        single = _feed(stream(qs, **kw), data[s])
        want = single.results()
        for field in ("distances", "starts", "positions"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field))[s],
                np.asarray(getattr(want, field)), err_msg=field)
        mine = [dataclasses.replace(e, stream=0) for e in fleet.alerts
                if e.stream == s]
        assert mine == single.alerts
        for qi, q in enumerate(qs):
            row = sdtw_last_row(q, data[s])
            assert np.asarray(got.distances)[s, qi] == row.min()
            assert np.asarray(got.positions)[s, qi] == np.argmin(row)
            alerts = [(e.tile_start, e.hits, e.distance, e.end)
                      for e in mine if e.query == qi]
            assert alerts == tile_alerts(row, THRESHOLD, CHUNK)
    assert any(e.stream == 1 and e.query == 1 and e.distance == 0
               and e.tile_start == 48 and e.start == 40
               for e in fleet.alerts)
    c = fleet.counters()
    tiles = -(-data.shape[1] // CHUNK)
    assert c["tiles"] == tiles
    assert c["launches"] == tiles * len(fleet._buckets)
    assert c["streams_advanced"] == tiles * S
    assert c["alerts"] == len(fleet.alerts) > 0


@pytest.mark.parametrize("variant", ["plain", "spans", "lastrow"])
def test_per_row_reference_block_equals_per_stream_calls(variant, rng):
    """Rows of three streams packed into kernel blocks (5 query rows per
    stream, blocks of 4 rows: the last block partly filled; several
    reference tiles, so the carry crosses them) give each row what a
    call over its own stream gives, carry and last row included."""
    n, m, per = 9, 40, 5
    q = rng.integers(-30, 30, (per, n)).astype(np.int32)
    qlens = np.array([9, 4, 7, 9, 1], np.int32)
    refs = rng.integers(-30, 30, (S, m)).astype(np.int32)
    kw = dict(block_q=4, block_m=16, return_carry=True, ref_offset=7,
              ref_len=37, return_spans=variant == "spans",
              track_start=variant == "lastrow",
              return_lastrow=variant == "lastrow")
    packed = sdtw_pallas(jnp.asarray(np.tile(q, (S, 1))),
                         jnp.asarray(np.repeat(refs, per, axis=0)),
                         jnp.asarray(np.tile(qlens, S)), **kw)
    for s in range(S):
        one = sdtw_pallas(jnp.asarray(q), jnp.asarray(refs[s]),
                          jnp.asarray(qlens), **kw)
        rows = slice(s * per, (s + 1) * per)
        for a, b in zip(_leaves(packed), _leaves(one)):
            np.testing.assert_array_equal(np.asarray(a)[rows],
                                          np.asarray(b))


def _leaves(tree):
    if isinstance(tree, (tuple, list)):
        return [x for t in tree for x in _leaves(t)]
    return [tree]


def test_per_row_reference_needs_one_row_per_query(rng):
    q = rng.integers(-5, 5, (4, 6)).astype(np.int32)
    with pytest.raises(ValueError, match="one row per query"):
        sdtw_pallas(jnp.asarray(q), jnp.zeros((3, 20), jnp.int32))


@pytest.mark.parametrize("impl", ["rowscan", "pallas"])
def test_fleet_snapshot_restore_continues_bitwise(impl, rng):
    qs, data = _fleet_inputs(rng)
    kw = dict(chunk=CHUNK, impl=impl, top_k=2, alert_threshold=THRESHOLD)
    whole = stream(qs, streams=S, **kw).feed(data).flush()
    first = stream(qs, streams=S, **kw).feed(data[:, :70])
    snap = first.snapshot()
    assert int(np.asarray(snap["buffer"]).shape[0]) == S
    again = StreamSession.restore(snap).feed(data[:, 70:]).flush()
    for a, b in zip((whole.results().distances, whole.results().positions),
                    (again.results().distances, again.results().positions)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert again.alerts == [e for e in whole.alerts if e.tile_start >= 64]


def test_version_one_snapshot_restores_as_one_stream(rng):
    import json
    q = rng.integers(-9, 9, (2, 6)).astype(np.int32)
    r = rng.integers(-9, 9, 50).astype(np.int32)
    s = stream(q, chunk=8).feed(r[:30])
    snap = s.snapshot()
    meta = json.loads(str(snap["meta"]))
    assert meta["version"] == 2 and meta["streams"] == 1
    del meta["streams"]
    meta["version"] = 1
    snap["meta"] = np.array(json.dumps(meta))
    old = StreamSession.restore(snap).feed(r[30:])
    np.testing.assert_array_equal(np.asarray(old.results().distances),
                                  np.asarray(s.feed(r[30:]).results()
                                             .distances))


@pytest.mark.parametrize("kw,match", [
    (dict(prune=True, top_k=1), "prune"),
    (dict(excl_lo=0, excl_hi=2), "exclusion bands"),
    (dict(mesh_shape=(1, 1)), "streams= stacks"),
    (dict(impl="sharded"), "streams= stacks"),
])
def test_fleet_rejects_what_it_does_not_support(kw, match, rng):
    q = rng.integers(-5, 5, (2, 6)).astype(np.int32)
    with pytest.raises(ValueError, match=match):
        stream(q, streams=2, **kw)


@pytest.mark.parametrize("streams", [0, -1, 1.5])
def test_streams_must_be_a_positive_int(streams, rng):
    q = rng.integers(-5, 5, (2, 6)).astype(np.int32)
    with pytest.raises(ValueError, match="positive int"):
        stream(q, streams=streams)


def test_fleet_feed_takes_one_row_per_stream(rng):
    q = rng.integers(-5, 5, (2, 6)).astype(np.int32)
    s = stream(q, streams=2, chunk=8)
    with pytest.raises(ValueError, match="one row per stream"):
        s.feed(np.zeros(8, np.int32))
    with pytest.raises(ValueError, match="one row per stream"):
        s.feed(np.zeros((3, 8), np.int32))
    with pytest.raises(ValueError, match="1-D chunk"):
        stream(q, chunk=8).feed(np.zeros((1, 8), np.int32))


@pytest.mark.parametrize("span", [False, True])
def test_tpu_candidates_fit_vmem_with_the_per_row_tile(span):
    """The per-row reference adds a double-buffered (block_q, block_m)
    tile to the kernel's VMEM; every candidate the TPU model offers for
    a fleet's tile step fits with it counted."""
    from repro.core import platforms
    model = cost.KernelCostModel(platforms.backend_model("interpret"))
    plain = model.vmem_words(128, 512, 512, span, lastrow=True)
    assert model.vmem_words(128, 512, 512, span, lastrow=True,
                            rowref=True) == plain + 2 * 128 * 512
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(platforms, "tpu_device_kind", lambda: "TPU v5 lite")
        tpu = cost.KernelCostModel("tpu")
        budget = tpu.backend.vmem_budget_words
        for (bq, bm, _, _), _ in tpu.pallas_candidates(
                16384, 512, 512, span=span, lastrow=True, rowref=True):
            assert tpu.vmem_words(bq, bm, 512, span, True, True) <= budget


def test_fleet_of_360_sample_ticks_at_a_384_lane_block(rng):
    """The monitoring tick's shape in small: streams fed 360-sample ticks
    through the kernel at the 384-lane block the TPU tuner picks for
    them (forced here), each stream equal to its own one-stream session
    and to the oracle; the lane counters read the 24 padding lanes of
    each launch."""
    tick, ticks = 360, 3
    qs = [rng.integers(-20, 20, n).astype(np.int32) for n in (6, 9)]
    data = rng.integers(-20, 20, (S, tick * ticks)).astype(np.int32)
    data[2, 400:409] = qs[1]                   # one exact match
    kw = dict(chunk=tick, impl="pallas", block_m=384,
              alert_threshold=THRESHOLD)
    fleet = stream(qs, streams=S, **kw)
    for t in range(ticks):
        fleet.feed(data[:, t * tick:(t + 1) * tick])
    got = np.asarray(fleet.results().distances)
    for s in range(S):
        single = stream(qs, **kw).feed(data[s])
        np.testing.assert_array_equal(
            got[s], np.asarray(single.results().distances))
        mine = [dataclasses.replace(e, stream=0) for e in fleet.alerts
                if e.stream == s]
        assert mine == single.alerts
        for qi, q in enumerate(qs):
            row = sdtw_last_row(q, data[s])
            assert got[s, qi] == row.min()
            alerts = [(e.tile_start, e.hits, e.distance, e.end)
                      for e in mine if e.query == qi]
            assert alerts == tile_alerts(row, THRESHOLD, tick)
    assert any(e.stream == 2 and e.query == 1 and e.distance == 0
               for e in fleet.alerts)
    c = fleet.counters()
    assert (c["launches"], c["lanes_fed"], c["lanes_launched"]) == \
        (ticks * len(fleet._buckets), ticks * len(fleet._buckets) * tick,
         ticks * len(fleet._buckets) * 384)
