"""Plain stream monitoring: the cost of the best match of every template
ending at every sample of every stream.

The yardstick of the many-stream monitoring cells. It imports nothing of
the program. For a template ``q`` (length N) and a stream ``r`` (length
T), with ``d(i, j) = |q[i] - r[j]|``:

    S[0, j] = d(0, j)                          (a match may start anywhere)
    S[i, j] = d(i, j) + min(S[i-1, j-1], S[i, j-1], S[i-1, j])

with cells outside the matrix at +infinity and sums saturating at
``BIG`` = 2**29; the answer is the last row, ``S[N-1, j]`` for every
column ``j``: what a threshold monitor compares with its threshold, and
whose least value is the stream's distance so far.

Every (stream, template) pair is one row of a batch whose rows each hold
their own stream, swept one anti-diagonal at a time (cells with equal
``i + j`` depend only on the two previous diagonals): ``T + N - 1``
vector steps of a ``lax.scan``, ``block`` pairs at a time.
"""
from __future__ import annotations

import functools

import numpy as np

BIG = 2 ** 29


@functools.lru_cache(maxsize=None)
def _sweep(n: int, t: int, acc: str, big: float, unroll: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(acc)
    big_v = jnp.asarray(big, dt)
    rows = jnp.arange(n, dtype=jnp.int32)

    def down(x):
        """Row i takes row i-1's entry; row 0 takes ``big``."""
        return jnp.concatenate(
            [jnp.full((x.shape[0], 1), big_v, x.dtype), x[:, :-1]], axis=1)

    def run(q, r):
        b = q.shape[0]
        qa = q.astype(dt)
        # diagonal k reads r[k - i] in row i: each pair's stream samples
        # shift down one row per step, r[:, k] entering at row 0
        r_in = jnp.concatenate([r, jnp.zeros((b, n - 1), r.dtype)],
                               axis=1).astype(dt).T
        full_big = jnp.full((b, n), big_v, dt)
        init = (full_big, full_big, jnp.zeros((b, n), dt))

        def step(c, x):
            d1, d2, rd = c
            rk, k = x
            rd = jnp.concatenate([rk[:, None], rd[:, :-1]], axis=1)
            j = k - rows
            valid = ((j >= 0) & (j < t))[None, :]
            dist = jnp.abs(qa - rd)
            v = jnp.minimum(jnp.minimum(down(d2), d1), down(d1))
            s = jnp.minimum(dist + v, big_v)
            s = jnp.where(rows[None, :] == 0, dist, s)
            s = jnp.where(valid, s, big_v)
            return (s, d1, rd), s[:, n - 1]

        ks = jnp.arange(t + n - 1, dtype=jnp.int32)
        _, last = lax.scan(step, init, (r_in, ks), unroll=unroll)
        return last[n - 1:].T                # (b, t): column j at step j+n-1

    return jax.jit(run)


def last_rows(templates, streams, *, acc: str = "int32", big: float = BIG,
              block: int = 16384, unroll: int = 8):
    """``(S, Q, T)`` on JAX's default device: the last DP row of each of
    the ``Q`` templates (``templates`` (Q, N)) against each of the ``S``
    streams (``streams`` (S, T)), in ``acc`` saturating at ``big``.
    Pairs go ``block`` at a time (the last block padded, so that one
    program serves every block)."""
    import jax.numpy as jnp
    q = np.asarray(templates)
    r = np.asarray(streams)
    (nq, n), (ns, t) = q.shape, r.shape
    pairs = ns * nq
    rows = min(block, pairs)
    fn = _sweep(n, t, acc, float(big), unroll)
    q_dev = jnp.asarray(q)
    r_dev = jnp.asarray(r)
    out = []
    for p0 in range(0, pairs, rows):
        idx = np.minimum(np.arange(p0, p0 + rows), pairs - 1)
        sid, qid = jnp.asarray(idx // nq), jnp.asarray(idx % nq)
        out.append(fn(q_dev[qid], r_dev[sid]))
    return jnp.concatenate(out)[:pairs].reshape(ns, nq, t)
