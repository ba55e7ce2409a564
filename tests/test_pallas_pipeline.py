"""Device-resident Pallas chunk pipeline: differential suite.

The three execution shapes of ``impl='pallas'`` + ``chunk=`` — the fused
single-launch grid, the device-side ``lax.scan`` over static slices, and
the legacy host-side launch loop — must be *bitwise-identical* (int32) to
each other and to the chunked rowscan path across metric × dtype × spans ×
top-K × carry-resume, for any partition of the reference. Also covers the
single-compile guarantee (the ragged-tail recompile bugfix), the in-kernel
last-row capture against the rowscan candidate row, and the scan-scheme /
row-tile / block-shape invariances of the optimized kernel interior.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import sdtw_ref

from repro.core import sdtw
from repro.core.engine import (_pallas_host_loop, _pallas_scan_streamed,
                               _pallas_streamed)
from repro.core.sdtw import sdtw_rowscan_chunk
from repro.kernels.sdtw import pallas_carry_init, resolve_blocks, sdtw_pallas

B, N, M = 3, 9, 151      # M = 9*16 + 7: ragged tail at chunk=16


def _mk(rng, dtype, b=B, n=N, m=M):
    q = rng.integers(-40, 40, (b, n)).astype(dtype)
    r = rng.integers(-40, 40, m).astype(dtype)
    return jnp.asarray(q), jnp.asarray(r), q, r


def _run_path(path, q, r, chunk, **kw):
    if path == "fused":
        return sdtw(q, r, impl="pallas", chunk=chunk, **kw)
    if path == "scan":
        return _pallas_scan_streamed(
            q, r, None, kw.pop("metric", "abs_diff"), chunk=chunk,
            block_q=None, block_m=None,
            return_positions=kw.get("return_positions", False),
            return_spans=kw.get("return_spans", False))
    return _pallas_host_loop(
        q, r, None, kw.pop("metric", "abs_diff"), chunk,
        return_positions=kw.get("return_positions", False),
        return_spans=kw.get("return_spans", False))


@pytest.mark.parametrize("metric", ["abs_diff", "square_diff"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_three_paths_match_chunked(metric, dtype, rng):
    qj, rj, q, r = _mk(rng, dtype)
    want = np.asarray(sdtw(qj, rj, impl="chunked", chunk=16, metric=metric))
    oracle = np.array([sdtw_ref(q[i], r, metric) for i in range(B)])
    for path in ("fused", "scan", "host"):
        got = np.asarray(_run_path(path, qj, rj, 16, metric=metric))
        if dtype == np.int32:
            np.testing.assert_array_equal(got, want, err_msg=path)
            np.testing.assert_array_equal(got, oracle, err_msg=path)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, err_msg=path)


def test_three_paths_spans_positions_bitwise(rng):
    qj, rj, _, _ = _mk(rng, np.int32)
    d0, s0, e0 = (np.asarray(x) for x in
                  sdtw(qj, rj, impl="chunked", chunk=16, return_spans=True))
    for path in ("fused", "scan", "host"):
        d, s, e = (np.asarray(x) for x in
                   _run_path(path, qj, rj, 16, return_spans=True))
        np.testing.assert_array_equal(d, d0, err_msg=path)
        np.testing.assert_array_equal(s, s0, err_msg=path)
        np.testing.assert_array_equal(e, e0, err_msg=path)
        dp, ep = (np.asarray(x) for x in
                  _run_path(path, qj, rj, 16, return_positions=True))
        np.testing.assert_array_equal(dp, d0, err_msg=path)
        np.testing.assert_array_equal(ep, e0, err_msg=path)


def test_chunk_partition_invariance(rng):
    """Any chunk size — including chunk=1, chunk > M, and random ragged
    partitions via the carry — gives the same bits on every path."""
    qj, rj, q, r = _mk(rng, np.int32, m=97)
    want = np.asarray(sdtw(qj, rj, impl="chunked", chunk=8192))
    for chunk in (1, 7, 16, 97, 1024):
        for path in ("fused", "scan", "host"):
            got = np.asarray(_run_path(path, qj, rj, chunk))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"{path} c={chunk}")
    # random partitions via explicit carry-resume through the kernel
    for seed in range(3):
        prng = np.random.default_rng(seed)
        cuts = np.sort(prng.choice(np.arange(1, 97), size=4, replace=False))
        parts = np.split(r, cuts)
        carry = pallas_carry_init(B, N, np.int32)
        off = 0
        width = max(len(p) for p in parts)
        for p in parts:
            pad = np.zeros((width,), p.dtype)
            pad[:len(p)] = p
            _, carry = sdtw_pallas(qj, jnp.asarray(pad), None, "abs_diff",
                                   carry=carry, ref_offset=off,
                                   ref_len=len(p), return_carry=True)
            off += len(p)
        np.testing.assert_array_equal(np.asarray(carry[1]), want,
                                      err_msg=f"partition {cuts}")


def test_carry_resume_track_matches_offline(rng):
    """Span-mode carry-resume across slices == offline spans (int32)."""
    qj, rj, _, r = _mk(rng, np.int32)
    d0, s0, e0 = (np.asarray(x) for x in
                  sdtw(qj, rj, impl="chunked", chunk=16, return_spans=True))
    carry = pallas_carry_init(B, N, np.int32, track_start=True)
    for off in range(0, M, 64):
        sl = r[off:off + 64]
        cl = len(sl)
        sl = np.pad(sl, (0, 64 - cl))
        _, carry = sdtw_pallas(qj, jnp.asarray(sl), None, "abs_diff",
                               carry=carry, ref_offset=off, ref_len=cl,
                               return_carry=True, track_start=True)
    _, _, d, e, s = (np.asarray(x) for x in carry)
    np.testing.assert_array_equal(d, d0)
    np.testing.assert_array_equal(s, s0)
    np.testing.assert_array_equal(e, e0)


def test_host_loop_single_compile(rng):
    """The ragged-tail bugfix: the per-slice loop pads the tail to the
    static chunk shape and passes the traced ref_len, so an M with a
    ragged tail compiles the kernel exactly once (the old code recompiled
    per distinct tail length)."""
    # unique shapes so earlier tests cannot have warmed this cache entry
    q = jnp.asarray(rng.integers(-40, 40, (2, 11)).astype(np.int32))
    r = jnp.asarray(rng.integers(-40, 40, 83).astype(np.int32))
    base = sdtw_pallas._cache_size()
    got = np.asarray(_pallas_host_loop(q, r, None, "abs_diff", 16))
    assert sdtw_pallas._cache_size() - base == 1
    # a second, differently-ragged reference reuses the same executable
    r2 = jnp.asarray(rng.integers(-40, 40, 69).astype(np.int32))
    _pallas_host_loop(q, r2, None, "abs_diff", 16)
    assert sdtw_pallas._cache_size() - base == 1
    want = np.asarray(sdtw(q, r, impl="chunked", chunk=16))
    np.testing.assert_array_equal(got, want)


def test_scan_path_single_compile(rng):
    """The device-side scan is one jitted program per call shape — calling
    it again (even with different data) adds no compiles."""
    q = jnp.asarray(rng.integers(-40, 40, (2, 13)).astype(np.int32))
    r = jnp.asarray(rng.integers(-40, 40, 107).astype(np.int32))
    base = _pallas_scan_streamed._cache_size()
    _pallas_scan_streamed(q, r, None, "abs_diff", chunk=16, block_q=None,
                          block_m=None, return_positions=False,
                          return_spans=False)
    assert _pallas_scan_streamed._cache_size() - base == 1
    r2 = jnp.asarray(rng.integers(-40, 40, 107).astype(np.int32))
    _pallas_scan_streamed(q, r2, None, "abs_diff", chunk=16, block_q=None,
                          block_m=None, return_positions=False,
                          return_spans=False)
    assert _pallas_scan_streamed._cache_size() - base == 1


def test_fused_dispatcher_thresholds(rng):
    """The pallas+chunk dispatcher: device-resident refs take the fused
    single-launch path, oversize refs the device-side scan — same bits."""
    import repro.core.engine as eng
    qj, rj, _, _ = _mk(rng, np.int32)
    want = np.asarray(_pallas_streamed(qj, rj, None, "abs_diff", 16, None,
                                       None, False))
    old = eng.PALLAS_FUSED_MAX
    try:
        eng.PALLAS_FUSED_MAX = 8     # force the scan path
        got = np.asarray(_pallas_streamed(qj, rj, None, "abs_diff", 16,
                                          None, None, False))
    finally:
        eng.PALLAS_FUSED_MAX = old
    np.testing.assert_array_equal(got, want)


def test_lastrow_matches_rowscan_chunk(rng):
    """In-kernel last-row capture == the rowscan candidate row, plain and
    span-tracked, with a chunk carry and a masked window."""
    qj, rj, q, r = _mk(rng, np.int32, m=70)
    qlens = np.array([N, 3, 7], np.int32)
    res, lrow, lstart = sdtw_pallas(qj, rj, jnp.asarray(qlens),
                                    track_start=True, return_lastrow=True)
    for i in range(B):
        bc, bs, be, lr, ls = sdtw_rowscan_chunk(
            jnp.asarray(q[i]), rj, jnp.full((N,), 2 ** 29, jnp.int32),
            jnp.int32(2 ** 29), qlen=int(qlens[i]), return_lastrow=True,
            bstart=jnp.full((N,), 2 ** 31 - 1, jnp.int32))
        np.testing.assert_array_equal(np.asarray(lr), np.asarray(lrow)[i])
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lstart)[i])


def test_lastrow_lead_and_len_window(rng):
    """ref_lead / ref_len mask the candidate row exactly like the rowscan
    global-position ban (the pruned-search halo contract)."""
    qj, rj, q, r = _mk(rng, np.int32, m=64)
    res, lrow = sdtw_pallas(qj, rj, return_lastrow=True, ref_lead=10,
                            ref_len=50)
    lrow = np.asarray(lrow)
    assert (lrow[:, :10] >= 2 ** 29).all()
    assert (lrow[:, 50:] >= 2 ** 29).all()
    assert (lrow[:, 10:50] < 2 ** 29).any()
    # columns 10..50 must carry exactly the DP of the sub-reference
    # r[10:50] started fresh (a banned leading band behaves like the
    # implicit BIG columns before the reference starts)
    for i in range(B):
        want = np.asarray(sdtw_pallas(qj[i:i + 1], rj[10:50],
                                      return_lastrow=True)[1])[0]
        np.testing.assert_array_equal(lrow[i, 10:50], want)


@pytest.mark.parametrize("scheme", ["shift", "assoc"])
@pytest.mark.parametrize("row_tile", [1, 2, 4, 9])
def test_scheme_row_tile_invariance(scheme, row_tile, rng):
    """The kernel interior knobs (scan scheme, row unrolling, block shape)
    must never change the int32 bits — they only change the schedule."""
    qj, rj, q, r = _mk(rng, np.int32, m=70)
    want = np.asarray(sdtw_pallas(qj, rj))            # auto config
    got = np.asarray(sdtw_pallas(qj, rj, block_q=2, block_m=16,
                                 scan_scheme=scheme, row_tile=row_tile))
    np.testing.assert_array_equal(got, want)
    d, s, e = sdtw_pallas(qj, rj, return_spans=True)
    d2, s2, e2 = sdtw_pallas(qj, rj, return_spans=True, block_q=2,
                             block_m=32, scan_scheme=scheme,
                             row_tile=row_tile)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e2))


@pytest.mark.parametrize("nq", [5, 70])
def test_tall_block_bitwise(nq, rng):
    """A tall (64-query) block gives the int32 distances and spans of the
    (8, ...) block, bitwise, also when the batch leaves the block (or its
    second block) mostly padding."""
    n, m = 20, 300
    q = jnp.asarray(rng.integers(-60, 60, (nq, n)).astype(np.int32))
    r = jnp.asarray(rng.integers(-60, 60, (m,)).astype(np.int32))
    qlens = jnp.asarray(rng.integers(n // 2, n + 1, (nq,)).astype(np.int32))
    kw = dict(block_m=128, scan_scheme="shift", row_tile=2)
    for extra in ({}, {"return_spans": True}):
        small = sdtw_pallas(q, r, qlens, block_q=8, **kw, **extra)
        tall = sdtw_pallas(q, r, qlens, block_q=64, **kw, **extra)
        for a, b in zip(jax.tree_util.tree_leaves(small),
                        jax.tree_util.tree_leaves(tall)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resolve_blocks_contract():
    """Auto-tuning fits the batch off-TPU and keeps aligned TPU defaults."""
    bq, bm, scheme, rt = resolve_blocks(4, 1 << 18, None, None, None, None,
                                        interpret=True)
    assert bq == 4 and scheme == "assoc" and rt == 1
    assert bm >= 512 and bm * bq <= (1 << 21)
    # the working-set budget must hold for non-power-of-two batches too
    for b in (3, 6, 24, 31):
        bq, bm, _, _ = resolve_blocks(b, 1 << 22, None, None, None, None,
                                      interpret=True)
        assert bm * bq <= (1 << 21), (b, bq, bm)
    bq, bm, scheme, rt = resolve_blocks(4, 1 << 18, None, None, None, None,
                                        interpret=False)
    assert (bq, bm, scheme, rt) == (8, 512, "shift", 8)
    # explicit values pass through untouched
    assert resolve_blocks(4, 100, 2, 64, "shift", 3, True) == (2, 64,
                                                              "shift", 3)


def test_resolve_blocks_budget_property():
    """The interpret working-set budget holds for EVERY explicit block_q,
    not just the auto-fitted ones: the old code floored the per-row
    quotient at 512, so block_q > 4096 pushed ``block_q * block_m`` past
    ``INTERPRET_ELEM_BUDGET`` (an 8 MB live-row array became 16+ MB)."""
    from repro.kernels.sdtw.ops import INTERPRET_ELEM_BUDGET
    for bq in (1, 2, 3, 7, 32, 100, 1024, 4096, 4097, 8192,
               1 << 15, 1 << 17):
        for m in (16, 100, 4096, 1 << 18, 1 << 22):
            got_bq, bm, _, _ = resolve_blocks(bq, m, bq, None, None, None,
                                              True)
            assert got_bq == bq
            assert bm >= 16
            assert bq * bm <= INTERPRET_ELEM_BUDGET, (bq, m, bm)
            assert bm & (bm - 1) == 0, (bq, m, bm)   # power of two


def test_search_pallas_engine_matches_rowscan(rng):
    """Pruned top-K search scored on the kernel's last-row capture ==
    rowscan survivors, bitwise, with genuine pruning happening."""
    from repro.search import search_topk
    from repro.search.cache import EnvelopeCache
    n, m = 16, 2048
    # piecewise level-shifted noise — the regime envelope pruning targets
    levels = rng.integers(-1500, 1500, m // 128)
    r = np.concatenate([lvl + rng.normal(0, 40, 128)
                        for lvl in levels]).astype(np.int32)
    q = np.stack([r[200:200 + n], r[700:700 + n] + 1]).astype(np.int32)
    qj, rj = jnp.asarray(q), jnp.asarray(r)
    a = search_topk(qj, rj, k=2, chunk=64, engine_impl="rowscan",
                    cache=EnvelopeCache(), ref_key="a")
    b = search_topk(qj, rj, k=2, chunk=64, engine_impl="pallas",
                    cache=EnvelopeCache(), ref_key="b")
    assert a.chunks_pruned > 0 and b.chunks_pruned > 0
    np.testing.assert_array_equal(np.asarray(a.distances),
                                  np.asarray(b.distances))
    np.testing.assert_array_equal(np.asarray(a.positions),
                                  np.asarray(b.positions))
    np.testing.assert_array_equal(np.asarray(a.starts),
                                  np.asarray(b.starts))
    with pytest.raises(ValueError, match="exclusion"):
        search_topk(qj, rj, engine_impl="pallas", excl_lo=0, excl_hi=4)


def test_stream_pallas_heap_alerts_prune(rng):
    """Pallas stream sessions (top-K, alerts, pruning) == rowscan sessions
    == the offline chunked heap, bitwise."""
    from repro.core import stream
    from repro.core.sdtw import sdtw_chunked
    from repro.search.cache import EnvelopeCache
    n, m, tile = 12, 512, 64
    levels = rng.integers(-800, 800, m // 64)
    r = np.concatenate([lvl + rng.normal(0, 30, 64)
                        for lvl in levels]).astype(np.int32)
    q = np.stack([r[300:300 + n],                      # planted: alerts fire
                  rng.integers(-40, 40, n).astype(np.int32)])
    qj = jnp.asarray(q)

    def feed_all(s):
        for off in range(0, m, 48):                    # unaligned arrivals
            s.feed(r[off:off + 48])
        return s

    for kw in (dict(top_k=3), dict(top_k=2, excl_mode="span"),
               dict(top_k=2, return_spans=True)):
        ra = feed_all(stream(qj, chunk=tile, impl="rowscan", **kw)).results()
        rb = feed_all(stream(qj, chunk=tile, impl="pallas", **kw)).results()
        np.testing.assert_array_equal(np.asarray(ra.distances),
                                      np.asarray(rb.distances))
        np.testing.assert_array_equal(np.asarray(ra.positions),
                                      np.asarray(rb.positions))

    sa = feed_all(stream(qj, chunk=tile, impl="rowscan", alert_threshold=0))
    sb = feed_all(stream(qj, chunk=tile, impl="pallas", alert_threshold=0))
    sa.flush(), sb.flush()
    assert sa.alerts and sa.alerts == sb.alerts        # the planted query

    sa = feed_all(stream(qj, chunk=tile, impl="rowscan", top_k=2,
                         prune=True, cache=EnvelopeCache(), ref_key="k"))
    sb = feed_all(stream(qj, chunk=tile, impl="pallas", top_k=2,
                         prune=True, cache=EnvelopeCache(), ref_key="k"))
    ra, rb = sa.results(), sb.results()
    assert ra.tiles_pruned == rb.tiles_pruned
    np.testing.assert_array_equal(np.asarray(ra.distances),
                                  np.asarray(rb.distances))
    np.testing.assert_array_equal(np.asarray(ra.positions),
                                  np.asarray(rb.positions))

    # offline equality for the pallas heap
    s = feed_all(stream(qj, chunk=tile, impl="pallas", top_k=3)).flush()
    out = s.results()
    kd, kp = sdtw_chunked(qj, jnp.asarray(r), chunk=tile, top_k=3)
    np.testing.assert_array_equal(np.asarray(out.distances), np.asarray(kd))
    np.testing.assert_array_equal(np.asarray(out.positions), np.asarray(kp))


# ---------------------------------------------------------------------------
# Property: random chunk partitions (hypothesis when available; the body is
# also swept manually above in test_chunk_partition_invariance).
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 8), st.integers(2, 60),
           st.integers(1, 61), st.integers(0, 1000))
    def test_hyp_any_chunk_any_path(b, n, m, chunk, seed):
        prng = np.random.default_rng(seed)
        q = prng.integers(-30, 30, (b, n)).astype(np.int32)
        r = prng.integers(-30, 30, m).astype(np.int32)
        qj, rj = jnp.asarray(q), jnp.asarray(r)
        want = np.array([sdtw_ref(q[i], r) for i in range(b)])
        for path in ("fused", "scan", "host"):
            got = np.asarray(_run_path(path, qj, rj, chunk))
            np.testing.assert_array_equal(got, want, err_msg=path)
