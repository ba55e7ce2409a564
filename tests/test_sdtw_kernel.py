"""Pallas sDTW kernel: interpret-mode allclose sweeps vs the test oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev dependency")
from hypothesis import given, settings, strategies as st

from oracle import sdtw_last_row, sdtw_ref, sdtw_span

from repro.core.distances import INT_BIG
from repro.kernels.sdtw import sdtw_pallas

SHAPES = [
    # (B, N, M, block_q, block_m) — covers single/multi tile, odd sizes,
    # padding in both grid dimensions.
    (1, 1, 1, 1, 8),
    (3, 5, 17, 2, 8),
    (4, 9, 70, 2, 16),
    (5, 12, 257, 4, 64),
    (8, 33, 1030, 8, 256),
]


@pytest.mark.parametrize("b,n,m,bq,bm", SHAPES)
@pytest.mark.parametrize("metric", ["abs_diff", "square_diff"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_kernel_shape_dtype_sweep(b, n, m, bq, bm, metric, dtype, rng):
    q = rng.integers(-40, 40, (b, n)).astype(dtype)
    r = rng.integers(-40, 40, m).astype(dtype)
    got = np.asarray(sdtw_pallas(jnp.asarray(q), jnp.asarray(r),
                                 metric=metric, block_q=bq, block_m=bm))
    want = np.array([sdtw_ref(q[i], r, metric) for i in range(b)])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_kernel_bf16_inputs(rng):
    q = rng.integers(-8, 8, (2, 6)).astype(np.float32)
    r = rng.integers(-8, 8, 40).astype(np.float32)
    got = np.asarray(sdtw_pallas(jnp.asarray(q, jnp.bfloat16),
                                 jnp.asarray(r, jnp.bfloat16),
                                 block_q=2, block_m=16))
    want = np.array([sdtw_ref(q[i], r) for i in range(2)])
    np.testing.assert_allclose(got, want, rtol=2e-2)


def test_kernel_variable_qlens(rng):
    q = rng.integers(-40, 40, (6, 12)).astype(np.int32)
    r = rng.integers(-40, 40, 53).astype(np.int32)
    qlens = np.array([12, 1, 5, 7, 3, 9], np.int32)
    got = np.asarray(sdtw_pallas(jnp.asarray(q), jnp.asarray(r),
                                 jnp.asarray(qlens), block_q=2, block_m=16))
    want = np.array([sdtw_ref(q[i, :qlens[i]], r) for i in range(6)])
    np.testing.assert_allclose(got, want)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 5), st.integers(1, 9), st.integers(1, 40),
       st.integers(0, 1000))
def test_hyp_kernel_matches_oracle(b, n, m, seed):
    rng = np.random.default_rng(seed)
    q = rng.integers(-30, 30, (b, n)).astype(np.int32)
    r = rng.integers(-30, 30, m).astype(np.int32)
    got = np.asarray(sdtw_pallas(jnp.asarray(q), jnp.asarray(r),
                                 block_q=2, block_m=8))
    want = np.array([sdtw_ref(q[i], r) for i in range(b)])
    np.testing.assert_allclose(got, want)


def test_kernel_block_shape_invariance(rng):
    """Tiling must not change the result (boundary-carry correctness)."""
    q = rng.integers(-40, 40, (4, 10)).astype(np.int32)
    r = rng.integers(-40, 40, 96).astype(np.int32)
    outs = [np.asarray(sdtw_pallas(jnp.asarray(q), jnp.asarray(r),
                                   block_q=bq, block_m=bm))
            for bq, bm in [(1, 8), (2, 16), (4, 32), (4, 96), (2, 128)]]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# A reference tile of whole 128-lane vregs that is not a power of two: the
# TPU tuner launches a 360-sample monitoring tick as a 384-lane block.
# ---------------------------------------------------------------------------

#: Reference layouts at ``block_m=384``: (array width, true columns).
LANE_REFS = {
    "tick": (360, 360),           # padded to one 384 tile, its tail masked
    "padded-tick": (384, 360),    # a right-padded tile; ref_len masks it
    "three-tiles": (1000, 1000),  # the boundary column crosses 3 tiles
}


def _lane_block_call(q, reference, qlens, rlen, variant, block_m):
    return sdtw_pallas(
        jnp.asarray(q), jnp.asarray(reference), jnp.asarray(qlens),
        block_q=2, block_m=block_m, scan_scheme="shift", row_tile=2,
        ref_len=None if rlen == reference.shape[-1] else rlen,
        return_spans=variant == "spans",
        track_start=variant == "lastrow",
        return_lastrow=variant == "lastrow")


@pytest.mark.parametrize("layout", sorted(LANE_REFS))
@pytest.mark.parametrize("variant", ["plain", "spans", "lastrow", "rowref"])
def test_384_lane_block_matches_oracle(variant, layout, rng):
    """Plain, spans, last-row capture and per-row reference at a
    (2, 384) block, bitwise against the oracle and against the (2, 512)
    block: columns past the true length are masked inside the tile, and
    over several tiles the boundary column carries across them."""
    width, rlen = LANE_REFS[layout]
    q = rng.integers(-30, 30, (3, 10)).astype(np.int32)
    qlens = np.array([10, 4, 7], np.int32)
    refs = rng.integers(-30, 30, (3, width)).astype(np.int32)
    reference = refs if variant == "rowref" else refs[0]
    out = _lane_block_call(q, reference, qlens, rlen, variant, 384)
    wide = _lane_block_call(q, reference, qlens, rlen, variant, 512)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(wide)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for i in range(3):
        qi = q[i, :qlens[i]]
        ri = (refs[i] if variant == "rowref" else refs[0])[:rlen]
        if variant == "spans":
            got = tuple(float(np.asarray(x)[i]) for x in out)
            assert got == sdtw_span(qi, ri), i
        elif variant == "lastrow":
            dist, row, _ = (np.asarray(x) for x in out)
            np.testing.assert_array_equal(row[i, :rlen],
                                          sdtw_last_row(qi, ri))
            assert np.all(row[i, rlen:] == INT_BIG)
            assert dist[i] == sdtw_ref(qi, ri)
        else:
            assert np.asarray(out)[i] == sdtw_ref(qi, ri), i

