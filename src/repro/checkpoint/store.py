"""Checkpointing: per-shard npz + JSON metadata, async save thread,
keep-last-k retention, atomic rename, resume with re-sharding.

Layout:  <dir>/step_<n>/shard_<i>.npz + meta.json
A checkpoint directory is only considered complete once `COMMIT` exists
AND the directory has been renamed from its `.tmp` staging name — a
crash mid-save never corrupts the restore path (fault tolerance).
Stale `*.tmp` staging dirs (even ones containing `COMMIT`, from a crash
between the commit mark and the rename) are ignored by `all_steps()`
and garbage-collected on startup.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
import zipfile
from typing import Any

import numpy as np
import jax

from .. import obs

__all__ = ["CheckpointManager"]

_STEP_RE = re.compile(r"^step_(\d+)$")


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(getattr(k, "key", str(getattr(k, "idx", k)))
                       for k in path)
        flat[key] = np.asarray(leaf)
    return flat


def _savez(path: str, flat: dict) -> None:
    """`np.savez_compressed` at zlib level 1: the same standard `.npz`
    (one `<key>.npy` member per array, ZIP_DEFLATED, zip64, pickling as
    `savez` allows it), several times faster to write than at zlib's
    default level 6 and a few percent larger."""
    with zipfile.ZipFile(path, "w", compression=zipfile.ZIP_DEFLATED,
                         compresslevel=1, allowZip64=True) as zf:
        for key, arr in flat.items():
            with zf.open(key + ".npy", "w", force_zip64=True) as f:
                np.lib.format.write_array(f, np.asanyarray(arr),
                                          allow_pickle=True)


def _unflatten_like(template, flat: dict):
    leaves_with_path = jax.tree_util.tree_flatten_with_path(template)
    paths, treedef = (
        [p for p, _ in leaves_with_path[0]], leaves_with_path[1])
    leaves = []
    for path, tmpl in leaves_with_path[0]:
        key = "/".join(getattr(k, "key", str(getattr(k, "idx", k)))
                       for k in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(tmpl.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} "
                f"vs expected {tmpl.shape}")
        leaves.append(arr.astype(tmpl.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointManager:
    """Save/restore train state with retention + async write."""

    def __init__(self, directory: str, keep: int = 3,
                 shard_id: int = 0, num_shards: int = 1):
        self.dir = directory
        self.keep = keep
        self.shard_id = shard_id
        self.num_shards = num_shards
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None
        self._async_exc: BaseException | None = None
        self._gc_stale_tmp()

    def _gc_stale_tmp(self) -> None:
        """Remove `.tmp` staging dirs left by a crash mid-save."""
        for name in os.listdir(self.dir):
            if name.endswith(".tmp") and _STEP_RE.match(name[:-4]):
                shutil.rmtree(os.path.join(self.dir, name),
                              ignore_errors=True)

    # ------------------------------------------------------------------ #
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:08d}")

    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.dir):
            m = _STEP_RE.match(name)
            if m and os.path.exists(
                    os.path.join(self.dir, name, "COMMIT")):
                steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # ------------------------------------------------------------------ #
    def _write(self, step: int, state: dict, meta: dict) -> None:
        """Write one step: the shard, then the commit.

        The shard is a standard `.npz` deflated at zlib level 1
        (`_savez`): `np.load`, `restore` and `restore_flat` read it, and
        shards written by `np.savez_compressed` alike.  Obs spans:
        `store.write` (the shard, with `raw_bytes`, the arrays' summed
        `nbytes` as handed in, and `written_bytes`, the file's size),
        then `store.commit` (meta, COMMIT, rename, retention)."""
        d = self._step_dir(step)
        tmp = d + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        flat = _flatten(state)
        shard = os.path.join(tmp, f"shard_{self.shard_id}.npz")
        with obs.span("store.write",
                      raw_bytes=sum(a.nbytes for a in flat.values())) as sp:
            _savez(shard, flat)
            sp.set(written_bytes=os.path.getsize(shard))
        with obs.span("store.commit"):
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump({**meta, "step": step,
                           "num_shards": self.num_shards}, f)
            open(os.path.join(tmp, "COMMIT"), "w").close()
            if os.path.exists(d):
                shutil.rmtree(d)
            os.rename(tmp, d)
            self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def save(self, step: int, state: Any, meta: dict | None = None,
             blocking: bool = True) -> None:
        state = jax.tree.map(np.asarray, state)  # device -> host copy
        if blocking:
            self._write(step, state, meta or {})
        else:
            self.wait()

            def _run():
                try:
                    self._write(step, state, meta or {})
                except BaseException as e:  # surfaced by wait()
                    self._async_exc = e

            self._thread = threading.Thread(target=_run)
            self._thread.start()

    def wait(self) -> None:
        """Join the async writer; re-raise anything it raised."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        exc, self._async_exc = self._async_exc, None
        if exc is not None:
            raise exc

    # ------------------------------------------------------------------ #
    def restore(self, template: Any, step: int | None = None
                ) -> tuple[Any, dict]:
        """Restore into the structure/dtypes of `template`."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.dir}")
        d = self._step_dir(step)
        z = np.load(os.path.join(d, f"shard_{self.shard_id}.npz"),
                    allow_pickle=False)
        flat = {k: z[k] for k in z.files}
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        return _unflatten_like(template, flat), meta

    def restore_flat(self, step: int | None = None
                     ) -> tuple[dict, dict]:
        """Restore the flat {leaf-key: array} dict without a template.

        For callers (e.g. the plan cache) whose state is already a flat
        dict of arrays and who need no dtype/shape coercion."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.dir}")
        d = self._step_dir(step)
        z = np.load(os.path.join(d, f"shard_{self.shard_id}.npz"),
                    allow_pickle=False)
        flat = {k: z[k] for k in z.files}
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        return flat, meta
