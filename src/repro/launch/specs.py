"""Input specs + parameter sharding rules + step builders for every
(architecture × shape × mesh) cell.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, zero device allocation) for every model input; ``state_specs``
does the same for params/optimizer via jax.eval_shape. ``build_cell``
assembles the jitted step function with in/out shardings for the dry-run.

Sharding rules (DESIGN.md §6):
  * train params+optimizer: 2-D "fsdp × tp" sharding — contraction dims over
    the data-parallel axes (ZeRO-3 style; XLA inserts the per-layer
    all-gathers), parallel dims over "model" (Megatron TP).
  * serve params: TP-only (no per-step weight gathers), bf16.
  * KV caches: batch over dp when batch ≥ |data|, else sequence-parallel
    (long_500k: the 500k-token cache is sharded along sequence — SP).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, ShapeSpec
from repro.distributed import Axes
from repro.models import RunConfig, decode_step, init_cache, init_lm, prefill
from repro.models.model import loss_fn
from repro.optim import OptConfig
from repro.train import TrainConfig, init_train_state, make_train_step


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-name based)
# ---------------------------------------------------------------------------

def _axis_size(axes: Axes, handle) -> int:
    if handle is None or axes.mesh is None:
        return 1
    names = handle if isinstance(handle, tuple) else (handle,)
    size = 1
    for n in names:
        size *= axes.mesh.shape[n]
    return size


def _leaf_spec(path_names, shape, axes: Axes, mode: str):
    """PartitionSpec dims for one param leaf, by name + rank.

    Every dim is divisibility-guarded: jit *argument* shardings (unlike
    with_sharding_constraint) hard-require even division, and e.g. mamba2's
    50280-token vocab does not divide a 16-way axis — such dims replicate.
    """
    name = path_names[-1]
    fsdp = (axes.dp if axes.dp else None) if mode == "train" else None
    tp = axes.tp
    stacked = "blocks" in path_names           # leading layer-stack dim
    rank = len(shape) - (1 if stacked else 0)
    dim_shape = shape[1:] if stacked else shape

    def spec(*dims):
        dims = tuple(d if (d is not None and
                           dim_shape[i] % _axis_size(axes, d) == 0) else None
                     for i, d in enumerate(dims))
        dims = (None,) + dims if stacked else dims
        assert len(dims) == len(shape), (path_names, shape, dims)
        return P(*dims)

    if name == "table":                         # [V, d]
        return spec(tp, fsdp)
    if name in ("wq", "wk", "wv"):              # [d, X]
        return spec(fsdp, tp)
    if name in ("bq", "bk", "bv"):              # [X]
        return spec(tp)
    if name == "wo":                            # [X, d]
        return spec(tp, fsdp)
    if name in ("w_gate", "w_up"):
        if rank == 3:                           # MoE [E, d, ff]
            return spec(tp, fsdp, None)
        return spec(fsdp, tp)                   # dense [d, ff]
    if name == "w_down":
        if rank == 3:                           # MoE [E, ff, d]
            return spec(tp, None, fsdp)
        return spec(tp, fsdp)                   # dense [ff, d]
    if name == "router":                        # [d, E]
        return spec(fsdp, None)
    if name == "in_proj":                       # [d, 2di+2n+h]
        return spec(tp, fsdp)
    if name == "out_proj":                      # [di, d]
        return spec(tp, fsdp)
    if name == "conv_x":                        # [w, di]
        return spec(None, tp)
    if name in ("conv_b", "conv_c"):            # [w, n]
        return spec(None, None)
    if name in ("dt_bias", "A_log", "D"):       # [h]
        return spec(tp)
    if name == "norm_w":                        # [di]
        return spec(tp)
    if name in ("ln", "ln1", "ln2", "final_norm"):
        return spec(None)
    if rank == 0:                               # scalars (opt step etc.)
        return P()
    # Fallback: replicate.
    return spec(*([None] * rank))


def _path_names(path):
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def tree_specs(tree, axes: Axes, mode: str):
    """PartitionSpec tree matching an eval_shape'd param/opt pytree."""
    flat, treedef = jax.tree.flatten_with_path(tree)
    specs = [_leaf_spec(_path_names(p), l.shape, axes, mode)
             for p, l in flat]
    return treedef.unflatten(specs)


def tree_shardings(tree, axes: Axes, mode: str):
    if axes.mesh is None:
        return None
    return jax.tree.map(lambda s: NamedSharding(axes.mesh, s),
                        tree_specs(tree, axes, mode))


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeSpec, run: RunConfig):
    """Model inputs for a cell, as ShapeDtypeStructs."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train" or shape.kind == "prefill":
        if cfg.frontend == "stub":
            batch = {"embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                        jnp.bfloat16),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if shape.kind == "prefill":
            batch.pop("labels")
        return batch
    # decode: one new token against a full cache
    return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}


def batch_spec_tree(cfg, shape, axes: Axes):
    dp = axes.dp if axes.dp else None
    def one(leaf_path, sds):
        name = _path_names(leaf_path)[-1]
        if name == "embeddings":
            return P(dp, None, None)
        if name in ("tokens", "labels"):
            return P(dp, None) if len(sds.shape) == 2 else P(dp)
        return P(*([None] * len(sds.shape)))
    flat, treedef = jax.tree.flatten_with_path(
        input_specs(cfg, shape, RunConfig()))
    return treedef.unflatten([one(p, l) for p, l in flat])


def cache_spec_tree(cfg, shape, axes: Axes, cache_tree,
                    kv_layout: str = "dh"):
    """KV/SSM cache shardings. Batch over dp when divisible, else SP over
    the sequence axis; kv-heads over tp when divisible, otherwise either the
    head_dim ("dh", default) or the sequence ("seq") carries the model axis
    — a §Perf lever: dh-sharding psums the full scores row per layer, seq-
    sharding psums only softmax stats + values (distributed flash-decode)."""
    dp = axes.dp if axes.dp else None
    tp = axes.tp
    dp_size = 1
    if axes.mesh is not None:
        for a in (axes.dp or ()):
            dp_size *= axes.mesh.shape[a]
    batch_shardable = shape.global_batch % max(dp_size, 1) == 0 and \
        shape.global_batch >= dp_size

    def one(path, leaf):
        name = _path_names(path)[-1]
        rank = len(leaf.shape)
        if name in ("k", "v", "shared_k", "shared_v"):
            # [L_or_G, B, S, Hkv, Dh]. KV memory is the decode-cell budget
            # driver (qwen1.5 decode_32k: 2.7 TB global) — when kv-heads
            # don't divide TP, shard head_dim instead (always 128·k): the
            # per-step scatter stays shard-local (sequence-dim sharding made
            # the scatter cross-shard → cache-sized partitioner temps) and
            # the scores contraction psums a small [B,H,S] partial.
            tkv = axes.tp_if_divisible(cfg.n_kv_heads)
            tdh = axes.tp_if_divisible(cfg.resolved_head_dim)
            if batch_shardable:
                if tkv:
                    return P(None, dp, None, tkv, None)
                if kv_layout == "seq":
                    return P(None, dp, tp, None, None)
                return P(None, dp, None, None, tdh)
            return P(None, None, axes.sp, tkv,
                     None if tkv else tdh)            # sequence parallel
        if name == "h":                                # [L, B, H, P, N]
            th = axes.tp_if_divisible(cfg.n_ssm_heads)
            if batch_shardable:
                return P(None, dp, th, None, None)
            return P(None, None, th, None, None)
        if name == "conv":                             # [L, B, W-1, ch]
            if batch_shardable:
                return P(None, dp, None, None)
            return P(*([None] * rank))
        if name == "pos":
            return P(dp) if batch_shardable else P(None)
        return P(*([None] * rank))

    flat, treedef = jax.tree.flatten_with_path(cache_tree)
    return treedef.unflatten([one(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# Cell assembly
# ---------------------------------------------------------------------------

def run_config_for(shape: ShapeSpec, overrides: Optional[dict] = None
                   ) -> RunConfig:
    # scan_layers=False: accurate per-layer cost/collective accounting in the
    # dry-run (HloCostAnalysis counts while-loop bodies once — see RunConfig).
    base = dict(compute_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                scan_layers=False)
    if shape.kind == "train":
        base.update(remat="full", attn_mode="chunked", attn_chunk=2048)
    elif shape.kind == "prefill":
        base.update(remat="none", attn_mode="chunked", attn_chunk=1024)
    else:
        base.update(remat="none", attn_mode="dense")
    base.update(overrides or {})
    return RunConfig(**base)


def _maybe_fp8_cache(cfg, shape, axes: Axes, run: RunConfig) -> RunConfig:
    """fp8 KV cache when bf16 would blow the per-chip HBM budget
    (qwen1.5-32b decode_32k: 5.5 TB global KV in bf16 > 4 TB fleet HBM)."""
    if not cfg.n_heads:
        return run
    n_chips = 1 if axes.mesh is None else axes.mesh.devices.size
    n_attn = cfg.n_layers if cfg.family != "hybrid" \
        else cfg.n_layers // cfg.attn_every
    kv_bytes = (2 * n_attn * shape.global_batch * shape.seq_len
                * cfg.n_kv_heads * cfg.resolved_head_dim * 2) / n_chips
    if kv_bytes > 8e9:
        return dataclasses.replace(run, cache_dtype=jnp.float8_e4m3fn)
    return run


@dataclasses.dataclass
class Cell:
    """A lowered/compilable (arch × shape × mesh) unit."""
    fn: Any                    # jitted function
    args: tuple                # ShapeDtypeStructs
    description: str


def _sds_tree(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def build_cell(cfg: ArchConfig, shape: ShapeSpec, axes: Axes,
               run_overrides: Optional[dict] = None,
               tcfg: Optional[TrainConfig] = None,
               serve_param_mode: str = "train",
               kv_layout: str = "dh") -> Cell:
    """serve_param_mode: "train" (2-D fsdp×tp — fits everything, gathers
    weights per step) or "serve" (TP-only — no gathers; §Perf lever for
    models whose TP-sharded bf16 params fit beside the KV cache)."""
    run = run_config_for(shape, run_overrides)
    mesh = axes.mesh
    ns = lambda spec: NamedSharding(mesh, spec) if mesh is not None else None

    if shape.kind == "train":
        tcfg = tcfg or TrainConfig(opt=OptConfig())
        params_sds = jax.eval_shape(
            lambda: init_lm(cfg, jax.random.PRNGKey(0)))
        state_sds = jax.eval_shape(
            lambda: init_train_state(cfg, init_lm(cfg, jax.random.PRNGKey(0)),
                                     tcfg))
        state_spec = tree_specs(state_sds, axes, "train")
        batch_sds = input_specs(cfg, shape, run)
        batch_spec = batch_spec_tree(cfg, shape, axes)
        step = make_train_step(cfg, run, tcfg, axes)
        metric_names = ["ce", "aux", "loss", "grad_norm", "lr"]
        out_spec = (state_spec, {k: P() for k in metric_names})
        fn = jax.jit(
            step,
            in_shardings=(jax.tree.map(ns, state_spec,
                                       is_leaf=lambda x: isinstance(x, P)),
                          jax.tree.map(ns, batch_spec,
                                       is_leaf=lambda x: isinstance(x, P))),
            out_shardings=jax.tree.map(ns, out_spec,
                                       is_leaf=lambda x: isinstance(x, P)),
            donate_argnums=(0,),
        )
        return Cell(fn, (state_sds, batch_sds),
                    f"train_step {cfg.name} {shape.name}")

    # Serving cells use bf16 params. Baseline sharding is 2-D (fsdp × tp),
    # same as training: the 32B-class archs do not fit TP-only next to a
    # 32k-context KV cache (qwen1.5: 4 GB params + 10 GB KV per chip).
    # TP-only ("serve" mode) is the no-per-step-gather variant used by the
    # §Perf hillclimb where memory allows.
    params_sds = jax.eval_shape(lambda: init_lm(cfg, jax.random.PRNGKey(0)))
    params_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype),
        params_sds)
    param_spec = tree_specs(params_sds, axes, serve_param_mode)
    param_sh = jax.tree.map(ns, param_spec, is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "prefill":
        batch_sds = input_specs(cfg, shape, run)
        batch_spec = batch_spec_tree(cfg, shape, axes)
        max_len = shape.seq_len
        cache_sds = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, max_len, run))
        cache_spec = cache_spec_tree(cfg, shape, axes, cache_sds, kv_layout)
        dp = axes.dp if axes.dp else None

        def prefill_step(params, batch):
            return prefill(cfg, params, batch, max_len, axes, run)

        fn = jax.jit(
            prefill_step,
            in_shardings=(param_sh,
                          jax.tree.map(ns, batch_spec,
                                       is_leaf=lambda x: isinstance(x, P))),
            out_shardings=(ns(P(dp, None)),
                           jax.tree.map(ns, cache_spec,
                                        is_leaf=lambda x: isinstance(x, P))),
        )
        return Cell(fn, (params_sds, batch_sds),
                    f"prefill_step {cfg.name} {shape.name}")

    # decode
    run = _maybe_fp8_cache(cfg, shape, axes, run)
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len, run))
    cache_spec = cache_spec_tree(cfg, shape, axes, cache_sds, kv_layout)
    cache_sh = jax.tree.map(ns, cache_spec, is_leaf=lambda x: isinstance(x, P))
    tok_sds = input_specs(cfg, shape, run)["tokens"]
    dp = axes.dp if axes.dp else None
    dp_size = 1
    if axes.mesh is not None:
        for a in (axes.dp or ()):
            dp_size *= axes.mesh.shape[a]
    tok_spec = P(dp) if shape.global_batch % max(dp_size, 1) == 0 and \
        shape.global_batch >= dp_size else P(None)

    def serve_step(params, tokens, cache):
        logits, cache = decode_step(cfg, params, tokens, cache, axes, run)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    fn = jax.jit(
        serve_step,
        in_shardings=(param_sh, ns(tok_spec), cache_sh),
        out_shardings=(ns(tok_spec), cache_sh),
        donate_argnums=(2,),
    )
    return Cell(fn, (params_sds, tok_sds, cache_sds),
                f"serve_step {cfg.name} {shape.name}")
