"""Token-choice top-k MoE with expert parallelism (EP).

Two communication layouts over the TP ("model") axis, one math:

  * "a2a" (train/prefill): tokens are sharded over (data × model); each shard
    routes its local tokens into per-expert capacity buckets [E, c, d] and a
    tiled ``lax.all_to_all`` over the model axis delivers each expert's
    buckets to its owner shard (experts are sharded over "model"). Expert
    FFNs run as batched einsums; the inverse all_to_all returns outputs to
    the token owners. Communication per token ≈ 2·k·cf·d instead of a full
    gather — the textbook MoE dispatch, expressed in shard_map.

  * "replicated" (decode, S == 1): tokens are sharded over data only; each
    model shard evaluates just its local experts on all its tokens and a
    psum over "model" combines contributions. For tiny token counts this is
    strictly cheaper than a2a.

With mesh=None (CPU smoke tests) the same core runs unsharded (tp=1, a2a =
identity), so the distributed paths are oracle-checked against the local one
by construction.

Capacity semantics: per-(source-shard, expert) capacity
c = ceil(T_local · k · cf / E); overflow slots are dropped (Switch-style,
no gate renormalisation after drop). Gates are top-k-normalised (Qwen3's
norm_topk_prob). Router in fp32 + Switch aux load-balance loss.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .layers import normal_init


def init_moe(key, cfg, dtype=jnp.float32):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": normal_init(ks[0], (d, e), d, dtype),
        "w_gate": normal_init(ks[1], (e, d, ff), d, dtype),
        "w_up": normal_init(ks[2], (e, d, ff), d, dtype),
        "w_down": normal_init(ks[3], (e, ff, d), ff, dtype),
    }


def _route(x, router_w, n_experts, topk):
    """Router: fp32 softmax → top-k (normalised gates) + aux loss terms."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, topk)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * p_e  (f = fraction routed, p = mean prob)
    t = x.shape[0]
    counts = jnp.sum(jax.nn.one_hot(expert_ids, n_experts, dtype=jnp.float32),
                     axis=(0, 1))
    f = counts / jnp.maximum(t * topk, 1)
    p = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(f * p)
    return gate_vals, expert_ids, aux


def _bucketize(x_flat, expert_ids, gate_vals, n_buckets, capacity,
               expert_offset=0):
    """Scatter token slots into per-expert capacity buckets.

    Returns (buckets [n_buckets, c, d], slot refs for the return trip).
    Overflow / out-of-range slots are dropped via masked .add (zero
    contribution; positions are unique per kept slot so .add == .set).
    """
    t, k = expert_ids.shape
    d = x_flat.shape[-1]
    slot_expert = expert_ids.reshape(-1) - expert_offset       # (t*k,)
    slot_token = jnp.repeat(jnp.arange(t), k)
    in_range = (slot_expert >= 0) & (slot_expert < n_buckets)
    e_idx = jnp.where(in_range, slot_expert, 0)
    # Rank of each slot within its expert group (stable, slot-index order).
    counts = jnp.bincount(e_idx * in_range + n_buckets * (~in_range),
                          length=n_buckets + 1)[:n_buckets]
    order = jnp.argsort(jnp.where(in_range, e_idx, n_buckets), stable=True)
    starts = jnp.cumsum(counts) - counts
    sorted_e = e_idx[order]
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)
    keep = in_range & (pos < capacity)
    pos_c = jnp.minimum(pos, capacity - 1)
    contrib = x_flat[slot_token] * keep[:, None].astype(x_flat.dtype)
    buckets = jnp.zeros((n_buckets, capacity, d), x_flat.dtype)
    buckets = buckets.at[e_idx, pos_c].add(contrib)
    return buckets, (e_idx, pos_c, keep, slot_token,
                     gate_vals.reshape(-1))


def _unbucketize(buckets, slot_refs, t):
    e_idx, pos_c, keep, slot_token, slot_gate = slot_refs
    y_slots = buckets[e_idx, pos_c]                            # (t*k, d)
    w = (slot_gate * keep).astype(y_slots.dtype)[:, None]
    return jax.ops.segment_sum(y_slots * w, slot_token, num_segments=t)


def _expert_ffn(xin, w_gate, w_up, w_down):
    """Batched-per-expert SwiGLU: xin (E_local, T_e, d)."""
    dt = xin.dtype
    h = jax.nn.silu(jnp.einsum("etd,edf->etf", xin, w_gate.astype(dt)))
    h = h * jnp.einsum("etd,edf->etf", xin, w_up.astype(dt))
    return jnp.einsum("etf,efd->etd", h, w_down.astype(dt))


def _capacity(t_local: int, topk: int, n_experts: int, cf: float) -> int:
    return max(1, math.ceil(t_local * topk * cf / n_experts))


def moe_mlp(params, cfg, x, axes=None):
    """MoE FF block. x: (B, S, d) → ((B, S, d), aux_loss)."""
    b, s, d = x.shape
    e, k, cf = cfg.n_experts, cfg.topk, cfg.capacity_factor

    if axes is None or axes.mesh is None or axes.tp is None:
        c = _capacity(b * s, k, e, cf)
        x_flat = x.reshape(-1, d)
        gates, ids, aux = _route(x_flat, params["router"], e, k)
        buckets, refs = _bucketize(x_flat, ids, gates, e, c)
        y = _expert_ffn(buckets, params["w_gate"], params["w_up"],
                        params["w_down"])
        out = _unbucketize(y, refs, b * s)
        return out.reshape(b, s, d), aux

    mesh = axes.mesh
    tp = axes.tp
    tp_size = axes.tp_size
    dp_spec = axes.dp if axes.dp else None
    all_axes = tuple(mesh.axis_names)
    if e % tp_size:
        raise ValueError(f"n_experts={e} must divide TP size {tp_size}")
    e_local = e // tp_size
    use_a2a = s % tp_size == 0 and s > 1

    if use_a2a:
        t_local = (b * s) // (_prod(mesh, axes.dp) * tp_size)
        c = _capacity(t_local, k, e, cf)
        x_spec = P(dp_spec, tp, None)
    else:
        t_local = (b * s) // max(1, _prod(mesh, axes.dp))
        c = _capacity(t_local, k, e, cf)
        x_spec = P(dp_spec, None, None)

    w_expert_spec = P(tp, None, None)

    def body(x_l, router_w, w_g, w_u, w_d):
        bl, sl, _ = x_l.shape
        t = bl * sl
        x_flat = x_l.reshape(t, d)
        gates, ids, aux = _route(x_flat, router_w, e, k)
        if use_a2a:
            buckets, refs = _bucketize(x_flat, ids, gates, e, c)
            recv = lax.all_to_all(buckets, tp, split_axis=0, concat_axis=0,
                                  tiled=True)                  # (tp*E_l, c, d)
            xin = (recv.reshape(tp_size, e_local, c, d)
                   .transpose(1, 0, 2, 3).reshape(e_local, tp_size * c, d))
            y = _expert_ffn(xin, w_g, w_u, w_d)
            y = (y.reshape(e_local, tp_size, c, d).transpose(1, 0, 2, 3)
                 .reshape(tp_size * e_local, c, d))
            yback = lax.all_to_all(y, tp, split_axis=0, concat_axis=0,
                                   tiled=True)                 # (E, c, d)
            out = _unbucketize(yback, refs, t)
        else:
            shard = lax.axis_index(tp)
            buckets, refs = _bucketize(x_flat, ids, gates, e_local, c,
                                       expert_offset=shard * e_local)
            y = _expert_ffn(buckets, w_g, w_u, w_d)
            out = _unbucketize(y, refs, t)
            out = lax.psum(out, tp)
        aux = lax.pmean(aux, all_axes)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), w_expert_spec, w_expert_spec, w_expert_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out, aux


def _prod(mesh, names) -> int:
    p = 1
    for n in names:
        p *= mesh.shape[n]
    return p
