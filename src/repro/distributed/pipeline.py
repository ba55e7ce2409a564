"""GPipe-style pipeline parallelism over a mesh axis (shard_map +
collective_permute).

The layer stack is split into S contiguous stages, one per device along the
``stage`` axis; microbatches stream through with the classic GPipe schedule
(T = n_micro + S − 1 ticks; stage s processes microbatch t − s at tick t).
Activations move between stages with a single ppermute per tick — the
communication pattern maps 1:1 onto TPU ICI neighbours.

This is the optional PP axis of DESIGN.md §6 (the production dry-runs use
DP+TP, which fits every assigned arch); it exists, is tested against the
sequential execution in tests/_distributed_check.py, and composes with the
data-parallel axes (shard_map over ("stage",) while batch dims stay sharded
over dp).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import neighbor_perm, psum_harvest


def split_stages(stacked_params, n_stages: int):
    """Reshape [L, ...] stacked layer params into [S, L/S, ...]."""
    def r(p):
        l = p.shape[0]
        assert l % n_stages == 0, f"{l} layers not divisible by {n_stages}"
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])
    return jax.tree.map(r, stacked_params)


def pipeline_apply(block_fn, stage_params, x_micro, mesh, axis: str = "stage"):
    """Run microbatches through pipeline stages.

    Args:
      block_fn: (layer_params, activation) → activation — one LAYER; each
        stage scans its local layers.
      stage_params: pytree with leading [S, L/S, ...] dims (split_stages).
      x_micro: (n_micro, mb, ...) microbatched input activations.
      mesh: mesh containing ``axis`` of size S.
    Returns: (n_micro, mb, ...) outputs (replicated over the stage axis).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    perm = neighbor_perm(n_stages)

    def stage_body(params_local, xs):
        # params_local: [1, L/S, ...] (shard_map keeps the stage dim), xs
        # replicated (n_micro, mb, ...).
        params_local = jax.tree.map(lambda p: p[0], params_local)
        sid = lax.axis_index(axis)
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            held = carry                       # activation entering my stage
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = lax.dynamic_index_in_dim(xs, mb_idx, keepdims=False)
            inp = jnp.where(sid == 0, fresh, held)

            def layer(x, lp):
                return block_fn(lp, x), None
            out, _ = lax.scan(layer, inp, params_local)
            nxt = lax.ppermute(out, axis, perm)
            return nxt, out

        _, outs = lax.scan(tick, zero, jnp.arange(ticks))   # (ticks, mb,…)
        # Last stage emits microbatch m at tick m + S - 1; harvest its
        # window and replicate to all stages.
        return psum_harvest(outs, axis, n_stages, n_micro)

    in_specs = jax.tree.map(lambda p: P(axis), stage_params)
    return jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(in_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
