"""Checkpointing: atomic, retention-managed, mesh-agnostic, async-capable.

Arrays are gathered to host (fully replicated logical values) and written as
an ``.npz`` plus a JSON manifest under a temp name, then atomically renamed —
a crash mid-write never corrupts the latest checkpoint.  Because saved
values are logical (unsharded), a checkpoint can be restored under *any*
mesh (elastic re-scale: see runtime/elastic.py).  A background thread makes
saves non-blocking; ``wait()`` joins it (called before the next save and at
exit).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax


_SEP = "||"


def _flatten(tree) -> Tuple[Dict[str, np.ndarray], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = _SEP.join(_path_str(p) for p in path)
        out[key] = np.asarray(leaf)
    return out, treedef


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"#{p.idx}"
    return str(p)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state, extra: Optional[Dict] = None) -> None:
        self.wait()
        arrays, _ = _flatten(state)
        # pull to host before handing to the writer thread
        arrays = {k: np.asarray(v) for k, v in arrays.items()}
        meta = {"step": int(step), "extra": extra or {}}

        def write():
            tmp = os.path.join(self.dir, f".tmp-{step}")
            os.makedirs(tmp, exist_ok=True)
            np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f)
            final = os.path.join(self.dir, f"step_{step:010d}")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            self._gc()

        if self.async_save:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)

    # -- restore --------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.dir):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like, step: Optional[int] = None,
                shardings=None) -> Tuple[int, Any, Dict]:
        """Restore into the structure of ``like`` (a pytree of arrays or
        ShapeDtypeStructs).  ``shardings``: optional matching pytree of
        NamedShardings — arrays are placed onto devices accordingly (this is
        what makes restore mesh-elastic)."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        path = os.path.join(self.dir, f"step_{step:010d}")
        data = np.load(os.path.join(path, "arrays.npz"))
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        flat, treedef = jax.tree.flatten_with_path(like)
        leaves = []
        shard_flat = jax.tree.leaves(shardings) if shardings is not None \
            else [None] * len(flat)
        for (pth, proto), shard in zip(flat, shard_flat):
            key = _SEP.join(_path_str(p) for p in pth)
            arr = data[key]
            if shard is not None:
                leaves.append(jax.device_put(arr, shard))
            else:
                leaves.append(jax.numpy.asarray(arr))
        return meta["step"], jax.tree.unflatten(treedef, leaves), meta["extra"]
