"""Atomic, mesh-agnostic checkpointing for pytrees.

Design points for large-scale runs:
- **Atomicity**: write to ``<dir>/tmp.<step>`` then ``os.replace`` — a
  crash mid-write never corrupts the latest checkpoint (restart safety).
- **Mesh-agnostic storage**: arrays are saved as host NumPy, keyed by
  their pytree key-path, so a checkpoint written on one mesh restores
  onto another (or 1 CPU) — placement happens at ``device_put`` time
  in the caller.
- **Retention**: ``CheckpointManager`` keeps the last K checkpoints and
  survives preexisting/partial directories.

On real multi-host pods, process-0 writes after a ``jax.device_get``
(gathered via ``jax.experimental.multihost_utils``); in this container
there is a single process, so the gather is the identity.
"""
from __future__ import annotations

import json
import os
import re

import jax
import numpy as np


def _flatten(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): np.asarray(v) for kp, v in flat}


def save_checkpoint(directory: str, step: int, tree, meta: dict | None = None):
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"tmp.{step}.npz")
    final = os.path.join(directory, f"ckpt_{step:010d}.npz")
    arrays = _flatten(tree)
    with open(tmp, "wb") as f:
        np.savez(f, __meta__=json.dumps(meta or {}), **arrays)
    os.replace(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [int(m.group(1)) for f in os.listdir(directory)
             if (m := re.match(r"ckpt_(\d+)\.npz$", f))]
    return max(steps) if steps else None


def read_checkpoint_meta(directory: str,
                         step: int | None = None) -> dict | None:
    """Read just the ``__meta__`` record of a checkpoint (None if the
    directory holds none).  Lets a consumer decide *how* to restore —
    e.g. which policy architecture to instantiate (specialist vs
    fleet-conditioned generalist) — before building the ``like`` tree
    :func:`restore_checkpoint` needs.
    """
    if step is None:
        step = latest_step(directory)
    if step is None:
        return None
    path = os.path.join(directory, f"ckpt_{step:010d}.npz")
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["__meta__"]))


def restore_checkpoint(directory: str, like, step: int | None = None):
    """Restore into the structure of ``like``. Returns (tree, step, meta).

    ``like`` may live on any mesh/size — only the *structure* and shapes
    are used; placement is the caller's concern.
    """
    if step is None:
        step = latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {directory}")
    path = os.path.join(directory, f"ckpt_{step:010d}.npz")
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        out = []
        for kp, ref in flat:
            key = jax.tree_util.keystr(kp)
            if key not in z:
                raise KeyError(f"checkpoint missing {key}")
            arr = z[key]
            if arr.shape != np.shape(ref):
                raise ValueError(
                    f"{key}: checkpoint shape {arr.shape} != {np.shape(ref)}")
            out.append(arr)
    tree = jax.tree_util.tree_unflatten(treedef, out)
    return tree, step, meta


class CheckpointManager:
    """Retention + convenience wrapper used by the training loops."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    def save(self, step: int, tree, meta: dict | None = None) -> str:
        path = save_checkpoint(self.directory, step, tree, meta)
        self._gc()
        return path

    def restore(self, like, step: int | None = None):
        return restore_checkpoint(self.directory, like, step)

    def latest_step(self):
        return latest_step(self.directory)

    def _gc(self):
        files = sorted(f for f in os.listdir(self.directory)
                       if re.match(r"ckpt_\d+\.npz$", f))
        for f in files[:-self.keep]:
            os.remove(os.path.join(self.directory, f))
