"""Plain subsequence DTW, written from the recurrence and nothing else.

The yardstick the benchmark compares the program's answers with. It
imports nothing of the program. For a query ``q`` (length N) and a
reference ``r`` (length M), with ``d(i, j) = |q[i] - r[j]|``:

    S[0, j] = d(0, j)                          (a match may start anywhere)
    S[i, j] = d(i, j) + min(S[i-1, j-1], S[i, j-1], S[i-1, j])

with cells outside the matrix at +infinity. The distance is the least
value of the last row; its end is the leftmost column that attains it;
its start is the smallest row-0 column among the least-cost paths into
that cell (value ties break toward the smaller start). Integer sums
saturate at ``BIG`` = 2**29, the ceiling the configurations state.

The matrix is swept one anti-diagonal at a time (cells with equal
``i + j``), which depends only on the two previous diagonals, so a whole
diagonal of every query in a block is one vector step: ``M + N - 1``
steps of a ``lax.scan``. Queries go in blocks of ``block`` rows so that
the state fits beside whatever else is on the device.

``acc`` and ``big`` select the accumulator: int32 with ``BIG`` is the
reference; a narrower type is the control (see ``control.py``).
"""
from __future__ import annotations

import functools

import numpy as np

BIG = 2 ** 29
FAR = 2 ** 31 - 1


@functools.lru_cache(maxsize=None)
def _sweep(n: int, m: int, spans: bool, acc: str, big: float, unroll: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(acc)
    big_v = jnp.asarray(big, dt)
    far = jnp.int32(FAR)
    rows = jnp.arange(n, dtype=jnp.int32)

    def down(x, fill):
        """Row i takes row i-1's entry; row 0 takes ``fill``."""
        return jnp.concatenate(
            [jnp.full((x.shape[0], 1), fill, x.dtype), x[:, :-1]], axis=1)

    def lexmin(v1, t1, v2, t2):
        take = (v2 < v1) | ((v2 == v1) & (t2 < t1))
        return jnp.where(take, v2, v1), jnp.where(take, t2, t1)

    def run(q, r):
        b = q.shape[0]
        qa = q.astype(dt)
        # diagonal k reads r[k - i] in row i: the diagonal's reference
        # samples shift down one row per step, r[k] entering at row 0
        r_in = jnp.concatenate([r, jnp.zeros((n - 1,), r.dtype)]).astype(dt)
        ks = jnp.arange(m + n - 1, dtype=jnp.int32)
        full_big = jnp.full((b, n), big_v, dt)
        full_far = jnp.full((b, n), far, jnp.int32)
        init = dict(d1=full_big, d2=full_big, rd=jnp.zeros((n,), dt),
                    best=jnp.full((b,), big_v, dt),
                    end=jnp.full((b,), -1, jnp.int32))
        if spans:
            init.update(t1=full_far, t2=full_far,
                        start=jnp.full((b,), -1, jnp.int32))

        def step(c, x):
            rk, k = x
            rd = jnp.concatenate([rk[None], c["rd"][:-1]])
            j = k - rows
            valid = ((j >= 0) & (j < m))[None, :]
            dist = jnp.abs(qa - rd[None, :])
            up, left, diag = down(c["d1"], big_v), c["d1"], down(c["d2"],
                                                                 big_v)
            if spans:
                v, t = lexmin(diag, down(c["t2"], far), left, c["t1"])
                v, t = lexmin(v, t, up, down(c["t1"], far))
            else:
                v = jnp.minimum(jnp.minimum(diag, left), up)
            s = jnp.minimum(dist + v, big_v)
            s = jnp.where(rows[None, :] == 0, dist, s)
            s = jnp.where(valid, s, big_v)
            out = dict(d1=s, d2=c["d1"], rd=rd)
            last_ok = (k >= n - 1)
            better = last_ok & (s[:, n - 1] < c["best"])
            out["best"] = jnp.where(better, s[:, n - 1], c["best"])
            out["end"] = jnp.where(better, k - (n - 1), c["end"])
            if spans:
                t = jnp.where(rows[None, :] == 0, k, t)
                t = jnp.where(valid, t, far)
                out.update(t1=t, t2=c["t1"],
                           start=jnp.where(better, t[:, n - 1], c["start"]))
            return out, None

        c, _ = lax.scan(step, init, (r_in, ks), unroll=unroll)
        if spans:
            return c["best"], c["start"], c["end"]
        return c["best"], c["end"]

    return jax.jit(run)


def sdtw(queries, reference, *, spans: bool = True, acc: str = "int32",
         big: float = BIG, block: int = 16384, unroll: int = 8):
    """Distances (and spans) of every row of ``queries`` (B, N) against
    ``reference`` (M,), on JAX's default device, ``block`` rows at a time
    (the last block padded, so that any B compiles one program).

    Returns numpy arrays: ``(dist, start, end)`` with ``spans``, else
    ``(dist, end)``. ``dist`` is float64 so that answers of any
    accumulator compare exactly with the program's int32."""
    import jax.numpy as jnp
    q = np.asarray(queries)
    r = np.asarray(reference)
    b, n = q.shape
    rows = min(block, b)            # every block has this many rows, so
    fn = _sweep(n, r.shape[0], spans, acc, float(big), unroll)  # one compile
    r_dev = jnp.asarray(r)
    outs = []
    for i0 in range(0, b, rows):
        blk = np.zeros((rows, n), q.dtype)
        blk[:min(rows, b - i0)] = q[i0:i0 + rows]
        outs.append([np.asarray(x) for x in fn(jnp.asarray(blk), r_dev)])
    cols = [np.concatenate(c)[:b] for c in zip(*outs)]
    cols[0] = cols[0].astype(np.float64)
    return tuple(cols)


def sdtw_loops(query, reference):
    """The same answer by nested Python loops over the full matrix: the
    check of ``sdtw`` itself, at sizes where loops are affordable.
    Returns ``(dist, start, end)``."""
    q = np.asarray(query, np.int64)
    r = np.asarray(reference, np.int64)
    n, m = len(q), len(r)
    inf = float("inf")
    S = [[inf] * m for _ in range(n)]
    T = [[FAR] * m for _ in range(n)]
    for j in range(m):
        S[0][j] = abs(q[0] - r[j])
        T[0][j] = j
    for i in range(1, n):
        for j in range(m):
            cands = [(S[i - 1][j], T[i - 1][j])]
            if j > 0:
                cands += [(S[i - 1][j - 1], T[i - 1][j - 1]),
                          (S[i][j - 1], T[i][j - 1])]
            v, t = min(cands)
            S[i][j] = min(abs(q[i] - r[j]) + v, BIG)
            T[i][j] = t
    end = min(range(m), key=lambda j: (S[n - 1][j], j))
    return float(S[n - 1][end]), int(T[n - 1][end]), int(end)
