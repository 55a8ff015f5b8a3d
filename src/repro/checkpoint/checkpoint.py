"""Sharded-pytree checkpointing to .npz (no external deps).

Layout:  <dir>/step_<n>/arrays.npz + meta.json, plus <dir>/latest file
pointing at the most recent step. Keys are '/'-joined tree paths, so a
checkpoint restores into any pytree with the same structure.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding


def _flatten(tree: Any) -> Tuple[Dict[str, np.ndarray], Dict[str, str]]:
    """numpy has no bfloat16: such leaves are stored as uint16 bit patterns
    with the true dtype recorded in meta."""
    flat, dtypes = {}, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        leaf = jax.device_get(leaf)
        if leaf.dtype == jax.numpy.bfloat16:
            dtypes[key] = "bfloat16"
            flat[key] = np.asarray(leaf.view(jax.numpy.uint16))
        else:
            flat[key] = np.asarray(leaf)
    return flat, dtypes


def save_checkpoint(ckpt_dir: str, step: int, tree: Any,
                    extra_meta: Optional[Dict] = None) -> str:
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    os.makedirs(d, exist_ok=True)
    flat, dtypes = _flatten(tree)
    np.savez(os.path.join(d, "arrays.npz"), **flat)
    meta = {"step": step, "n_arrays": len(flat), "dtypes": dtypes}
    if extra_meta:
        meta.update(extra_meta)
    with open(os.path.join(d, "meta.json"), "w") as f:
        json.dump(meta, f)
    with open(os.path.join(ckpt_dir, "latest"), "w") as f:
        f.write(f"step_{step:08d}")
    return d


def latest_step(ckpt_dir: str) -> Optional[int]:
    p = os.path.join(ckpt_dir, "latest")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return int(f.read().strip().split("_")[-1])


def restore_checkpoint(ckpt_dir: str, template: Any,
                       step: Optional[int] = None) -> Tuple[Any, Dict]:
    """Restore into ``template``'s structure (shapes/dtypes must match)."""
    if step is None:
        step = latest_step(ckpt_dir)
        assert step is not None, f"no checkpoint in {ckpt_dir}"
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    data = np.load(os.path.join(d, "arrays.npz"))
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        arr = data[key]
        assert arr.shape == leaf.shape, (key, arr.shape, leaf.shape)
        if meta.get("dtypes", {}).get(key) == "bfloat16":
            arr = arr.view(jax.numpy.bfloat16)
        arr = arr.astype(leaf.dtype)
        # a mesh-sharded leaf goes straight to its shards from host, never
        # through one device
        sharding = getattr(leaf, "sharding", None)
        leaves.append(jax.device_put(arr, sharding)
                      if isinstance(sharding, NamedSharding)
                      else jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves), meta
