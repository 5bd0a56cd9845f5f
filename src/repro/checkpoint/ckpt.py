from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = jax.tree.flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = np.asarray(leaf)
    return out


def _unflatten_into(template, arrays: Dict[str, np.ndarray], shardings=None):
    flat, treedef = jax.tree.flatten_with_path(template)
    shard_flat = (
        jax.tree.leaves(shardings) if shardings is not None else [None] * len(flat)
    )
    leaves = []
    for (path, leaf), sh in zip(flat, shard_flat):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = arrays[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"leaf {key}: shape {arr.shape} != expected {leaf.shape}")
        arr = arr.astype(leaf.dtype)
        leaves.append(jax.device_put(arr, sh) if sh is not None else arr)
    return jax.tree.unflatten(treedef, leaves)


def save_checkpoint(directory: str, step: int, state: dict, extra: Optional[dict] = None) -> str:
    """Atomic synchronous save. ``state`` is a pytree dict of arrays."""
    path = os.path.join(directory, f"step_{step:08d}")
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    arrays = _flatten(state)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    manifest = {"step": int(step), "extra": extra or {}, "n_leaves": len(arrays)}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(path):
        shutil.rmtree(path)
    os.replace(tmp, path)
    return path


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and not d.endswith(".tmp")
    ]
    return max(steps) if steps else None


def load_checkpoint(
    directory: str, template, step: Optional[int] = None, shardings=None
) -> Tuple[Any, dict]:
    """Restore ``template``-shaped state (onto ``shardings`` if given)."""
    step = latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        arrays = {k: z[k] for k in z.files}
    state = _unflatten_into(template, arrays, shardings)
    return state, manifest


class CheckpointManager:
    """Async checkpointing: serialize on the caller thread is avoided by
    snapshotting to host numpy, then writing on a worker thread."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    def save_async(self, step: int, state: dict, extra: Optional[dict] = None) -> None:
        self.wait()  # bound outstanding writes to one
        snapshot = jax.tree.map(np.asarray, state)  # host copy now

        def _work():
            save_checkpoint(self.directory, step, snapshot, extra)
            self._gc()

        self._thread = threading.Thread(target=_work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = sorted(
            int(d.split("_")[1])
            for d in os.listdir(self.directory)
            if d.startswith("step_") and not d.endswith(".tmp")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"), ignore_errors=True)

    def restore(self, template, step=None, shardings=None):
        return load_checkpoint(self.directory, template, step, shardings)
