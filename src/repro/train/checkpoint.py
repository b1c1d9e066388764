"""Sharded, atomic, elastic checkpointing.

Layout:  <dir>/step_<N>/
            manifest.json      tree structure + shapes + dtypes + mesh
            <leaf-path>.npy    one file per param/opt leaf (host arrays)

Atomicity: written into ``step_<N>.tmp`` then os.rename'd — a crashed
save can never shadow a good checkpoint.  ``latest()`` ignores tmp dirs.

Elasticity: leaves are stored as FULL logical arrays (gathered from the
mesh on save).  Restore re-shards onto whatever mesh/device-count the
resumed job has — a resume after losing a pod (or doubling one) works
by construction.  For multi-host pods where a full gather is infeasible
the same manifest format supports per-shard files (``shard_k`` suffix);
this process-local writer covers the single-controller case used here.

Fault-tolerance integration: train/fault_tolerance.py calls ``save`` on
preemption signals and ``restore_latest`` on restart.
"""
from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path
from typing import Any, Optional, Tuple

import numpy as np
import jax


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    def pstr(path):
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        return "/".join(parts)
    return [(pstr(p), leaf) for p, leaf in flat], treedef


class CheckpointManager:
    def __init__(self, directory, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep

    # ------------------------------------------------------------- save
    def save(self, step: int, tree: Any, extra: Optional[dict] = None):
        tmp = self.dir / f"step_{step}.tmp"
        final = self.dir / f"step_{step}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)

        leaves, _ = _flatten_with_paths(tree)
        manifest = {"step": step, "time": time.time(),
                    "extra": extra or {}, "leaves": {}}
        for name, leaf in leaves:
            arr = np.asarray(jax.device_get(leaf))
            fn = name.replace("/", "__") + ".npy"
            np.save(tmp / fn, arr)
            manifest["leaves"][name] = {
                "file": fn, "shape": list(arr.shape), "dtype": str(arr.dtype)}
        with open(tmp / "manifest.json", "w") as f:
            json.dump(manifest, f, indent=1)
        if final.exists():
            shutil.rmtree(final)
        os.rename(tmp, final)                       # atomic publish
        self._gc()
        return final

    def _gc(self):
        steps = sorted(self.steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)

    # ---------------------------------------------------------- restore
    def steps(self):
        out = []
        for d in self.dir.iterdir():
            if d.is_dir() and d.name.startswith("step_") \
                    and not d.name.endswith(".tmp"):
                try:
                    out.append(int(d.name.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def restore(self, step: int, like: Any, shardings=None) -> Any:
        """Restore into the structure of ``like`` (params/opt_state tree).
        ``shardings``: optional matching tree of NamedSharding — leaves are
        device_put onto them (elastic re-shard)."""
        d = self.dir / f"step_{step}"
        with open(d / "manifest.json") as f:
            manifest = json.load(f)
        leaves, treedef = _flatten_with_paths(like)
        shard_leaves = None
        if shardings is not None:
            shard_leaves = [s for _, s in _flatten_with_paths(shardings)[0]]
        out = []
        for i, (name, leaf) in enumerate(leaves):
            info = manifest["leaves"][name]
            arr = np.load(d / info["file"])
            target_dtype = (leaf.dtype if hasattr(leaf, "dtype")
                            else arr.dtype)
            arr = arr.astype(target_dtype)
            if shard_leaves is not None:
                out.append(jax.device_put(arr, shard_leaves[i]))
            else:
                out.append(jax.numpy.asarray(arr))
        return jax.tree.unflatten(treedef, out), manifest["extra"]

    def restore_latest(self, like: Any, shardings=None
                       ) -> Tuple[Optional[int], Any, dict]:
        s = self.latest()
        if s is None:
            return None, like, {}
        tree, extra = self.restore(s, like, shardings)
        return s, tree, extra
