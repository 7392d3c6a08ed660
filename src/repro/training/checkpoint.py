"""Fault-tolerant checkpointing: atomic, content-hashed, resumable.

Design for 1000+ nodes:
  * every save writes to a temp directory then atomically renames — a
    crash mid-save leaves no partial checkpoint visible;
  * a MANIFEST (json) lists every array file with its sha256; restore
    verifies hashes and refuses corrupt checkpoints, falling back to the
    newest complete one;
  * arrays are saved per-leaf as raw .npy (host-local shards in a real
    multi-host run; device_get here), so restore can re-shard onto a
    DIFFERENT mesh (training/elastic.py) — node failure => shrink the mesh
    and resume;
  * ``keep`` rotates old checkpoints; the manifest records step + RNG fold
    index so the data pipeline resumes deterministically (straggler /
    skip-ahead support).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
from typing import Any, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

# numpy cannot serialize bf16/fp8 natively: store as a same-width unsigned
# view and record the true dtype in the manifest.
_VIEW_DTYPES = {
    "bfloat16": (np.uint16, ml_dtypes.bfloat16),
    "float8_e4m3fn": (np.uint8, ml_dtypes.float8_e4m3fn),
    "float8_e5m2": (np.uint8, ml_dtypes.float8_e5m2),
}


def _leaf_paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "__".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((name, leaf))
    return out


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    # -- save -----------------------------------------------------------------

    def save(self, step: int, state: Any, extra: Optional[dict] = None
             ) -> str:
        tmp = tempfile.mkdtemp(dir=self.dir, prefix=f".tmp_{step}_")
        manifest = {"step": int(step), "files": {}, "extra": extra or {}}
        for name, leaf in _leaf_paths(state):
            arr = np.asarray(jax.device_get(leaf))
            true_dtype = str(arr.dtype)
            if true_dtype in _VIEW_DTYPES:
                arr = arr.view(_VIEW_DTYPES[true_dtype][0])
            fn = f"{name}.npy"
            np.save(os.path.join(tmp, fn), arr, allow_pickle=False)
            with open(os.path.join(tmp, fn), "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            manifest["files"][fn] = {"sha256": digest,
                                     "shape": list(arr.shape),
                                     "dtype": true_dtype}
        with open(os.path.join(tmp, "MANIFEST.json"), "w") as f:
            json.dump(manifest, f)
        final = os.path.join(self.dir, f"step_{step:010d}")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)            # atomic publish
        self._rotate()
        return final

    def _rotate(self) -> None:
        ckpts = self.list_checkpoints()
        for path in ckpts[:-self.keep]:
            shutil.rmtree(path, ignore_errors=True)

    # -- restore ----------------------------------------------------------------

    def list_checkpoints(self) -> list:
        out = []
        for d in sorted(os.listdir(self.dir)):
            full = os.path.join(self.dir, d)
            if d.startswith("step_") and os.path.isdir(full) \
                    and os.path.exists(os.path.join(full, "MANIFEST.json")):
                out.append(full)
        return out

    def _verify(self, path: str) -> Optional[dict]:
        try:
            with open(os.path.join(path, "MANIFEST.json")) as f:
                manifest = json.load(f)
            for fn, meta in manifest["files"].items():
                with open(os.path.join(path, fn), "rb") as f:
                    if hashlib.sha256(f.read()).hexdigest() != meta["sha256"]:
                        return None
            return manifest
        except (OSError, json.JSONDecodeError, KeyError):
            return None

    def restore(self, template: Any, step: Optional[int] = None
                ) -> Tuple[int, Any, dict]:
        """Restore into the structure of ``template`` (its shardings are
        reapplied by the caller via device_put).  Picks the newest VERIFIED
        checkpoint; corrupt/partial ones are skipped.
        Returns (step, state, extra)."""
        ckpts = self.list_checkpoints()
        if step is not None:
            ckpts = [c for c in ckpts if c.endswith(f"step_{step:010d}")]
        for path in reversed(ckpts):
            manifest = self._verify(path)
            if manifest is None:
                continue
            leaves = []
            flat, tdef = jax.tree_util.tree_flatten_with_path(template)
            ok = True
            for ppath, leaf in flat:
                name = "__".join(
                    str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in ppath)
                fn = os.path.join(path, f"{name}.npy")
                if not os.path.exists(fn):
                    ok = False
                    break
                arr = np.load(fn, allow_pickle=False)
                true_dtype = manifest["files"][f"{name}.npy"]["dtype"]
                if true_dtype in _VIEW_DTYPES:
                    arr = arr.view(_VIEW_DTYPES[true_dtype][1])
                leaves.append(arr)
            if not ok:
                continue
            state = jax.tree_util.tree_unflatten(
                tdef, [jax.numpy.asarray(x) for x in leaves])
            return manifest["step"], state, manifest.get("extra", {})
        raise FileNotFoundError(
            f"no complete checkpoint in {self.dir} "
            f"({len(ckpts)} candidates, all failed verification)")
