#!/usr/bin/env python3
"""Measured int32 min+add rate of one chip's vector unit.

    python3 bench/vpu_microkernel.py

A Pallas kernel keeps ``CHAINS`` independent (64, 128) int32 arrays in
registers and applies ``x = min(x + a, c)`` to each, ``ITERS`` times per
grid step: two int32 operations per element per iteration, with nothing
read from or written to memory inside the loop. The rate is those
operations over the host-clock time of calls that end in
``block_until_ready``, best of five after a compile call. It is the
check of the derived VPU ceiling in ``peaks.json``: a rate above the
ceiling means the ceiling is too low. Prints one JSON line.
"""
from __future__ import annotations

import json
import sys
import time

ROWS, CHAINS, ITERS, STEPS, UNROLL = 64, 4, 16384, 1024, 8


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"vpu_microkernel: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2

    def kernel(a_ref, o_ref):
        a = a_ref[...]
        cap = jnp.int32(1 << 29)

        def body(_, xs):
            for _ in range(UNROLL):     # Mosaic unrolls only fully or not
                xs = tuple(jnp.minimum(x + a, cap) for x in xs)
            return xs

        xs = lax.fori_loop(0, ITERS // UNROLL, body,
                           tuple(a + c for c in range(CHAINS)))
        acc = xs[0]
        for x in xs[1:]:
            acc = jnp.minimum(acc, x)
        o_ref[...] = acc

    call = jax.jit(pl.pallas_call(
        kernel, grid=(STEPS,),
        in_specs=[pl.BlockSpec((ROWS, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((STEPS * ROWS, 128), jnp.int32)))
    a = jnp.asarray(
        (jnp.arange(STEPS * ROWS * 128, dtype=jnp.int32) % 7 - 3).reshape(
            STEPS * ROWS, 128))
    call(a).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        call(a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    ops = 2 * ROWS * 128 * CHAINS * ITERS * STEPS
    print(json.dumps({"device_kind": dev.device_kind, "int32_ops": ops,
                      "seconds": best, "int32_ops_per_s": ops / best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
