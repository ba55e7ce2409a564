"""The plain reference against nested loops, and its narrower control."""
import numpy as np
import pytest

from bench import reference
from bench.control import INT16_BIG


@pytest.mark.parametrize("alphabet", [3, 200])
def test_wavefront_equals_loops(alphabet):
    """Small alphabets force value ties, which the start rule settles."""
    rng = np.random.default_rng(alphabet)
    for _ in range(12):
        n = int(rng.integers(1, 9))
        m = int(rng.integers(n, 40))
        q = rng.integers(0, alphabet, (3, n)).astype(np.int32)
        r = rng.integers(0, alphabet, m).astype(np.int32)
        d, s, e = reference.sdtw(q, r, spans=True)
        d2, e2 = reference.sdtw(q, r, spans=False)
        for i in range(3):
            want = reference.sdtw_loops(q[i], r)
            assert (d[i], s[i], e[i]) == want
            assert (d2[i], e2[i]) == (want[0], want[2])


def test_blocks_pad_the_last():
    rng = np.random.default_rng(5)
    q = rng.integers(-50, 50, (7, 6)).astype(np.int32)
    r = rng.integers(-50, 50, 30).astype(np.int32)
    whole = reference.sdtw(q, r, spans=True)
    blocked = reference.sdtw(q, r, spans=True, block=3)
    for a, b in zip(whole, blocked):
        np.testing.assert_array_equal(a, b)


def test_int16_control_saturates_large_distances():
    """The control agrees below int16's ceiling and saturates above it."""
    q = np.full((2, 8), 4000, np.int32)
    q[1] = 10
    r = np.zeros(20, np.int32)
    d, _, _ = reference.sdtw(q, r, spans=True)
    c, _, _ = reference.sdtw(q, r, spans=True, acc="int16", big=INT16_BIG)
    assert d[1] == c[1] == 80
    assert d[0] == 32000 and c[0] == INT16_BIG
