"""Sharded, async, atomically-committed checkpointing.

Layout: <dir>/step_<N>/<leaf-files>.bin + manifest.json. The manifest is written
LAST (fsync'd, then atomically renamed); a checkpoint without a manifest is
invisible to ``latest_step`` — a crash mid-save can never corrupt restartability.
Commit callbacks let the Titchener overwatch record the manifest (the management
plane's "last committed checkpoint" used by the dispatcher for re-dispatch after
pod failure).

On a real multi-host fleet each process writes only its addressable shards; here
(single process) leaves are fetched whole. The on-disk format is dtype-agnostic
raw bytes + a JSON description, so bf16/int8 round-trip without pickle.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.telemetry import LoopSpans

_SEP = "/"


def _flatten_with_names(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        names.append(_SEP.join(parts))
        leaves.append(leaf)
    return names, leaves, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, use_async: bool = True):
        self.directory = directory
        self.keep = keep
        self.use_async = use_async
        self._thread: Optional[threading.Thread] = None
        self._commit_hooks: List[Callable[[int, str], None]] = []
        self.spans = LoopSpans("ckpt")
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------------- hooks
    def on_commit(self, fn: Callable[[int, str], None]) -> None:
        """fn(step, manifest_path) runs after a checkpoint becomes durable."""
        self._commit_hooks.append(fn)

    # -------------------------------------------------------------------------- save
    def save(self, step: int, tree, extra: Optional[dict] = None,
             blocking: bool = False) -> str:
        """Snapshot ``tree`` (+ JSON-serializable ``extra``) at ``step``."""
        self.wait()
        names, leaves, _ = _flatten_with_names(tree)
        host_leaves = [np.asarray(jax.device_get(l)) for l in leaves]
        target = os.path.join(self.directory, f"step_{step:08d}")

        def write():
            tmp = target + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            entries = {}
            for i, (name, arr) in enumerate(zip(names, host_leaves)):
                fname = f"leaf_{i:05d}.bin"
                with open(os.path.join(tmp, fname), "wb") as f:
                    f.write(arr.tobytes())
                entries[name] = {"file": fname, "shape": list(arr.shape),
                                 "dtype": str(arr.dtype)}
            manifest = {"step": step, "leaves": entries, "extra": extra or {}}
            mpath = os.path.join(tmp, "manifest.json")
            with open(mpath + ".tmp", "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.rename(mpath + ".tmp", mpath)           # manifest last = commit point
            # swap the finished tree in WITHOUT a window where no committed
            # checkpoint exists at this step: rename the old tree aside, then
            # the atomic tmp->target rename, then drop the old one. A crash
            # anywhere in the sequence leaves at least one complete,
            # manifest-bearing tree on disk (the .old survivor is ignored by
            # all_steps and reaped by the next save of this step).
            if os.path.exists(target):
                old = target + ".old"
                if os.path.exists(old):
                    shutil.rmtree(old)
                os.rename(target, old)
                os.rename(tmp, target)
                shutil.rmtree(old, ignore_errors=True)
            else:
                os.rename(tmp, target)
            self._gc()
            for hook in self._commit_hooks:
                hook(step, os.path.join(target, "manifest.json"))

        def traced_write():
            # ``repro.ckpt.write``, on the thread that writes
            with self.spans.span("write"):
                write()

        if self.use_async and not blocking:
            self._thread = threading.Thread(target=traced_write, daemon=True)
            self._thread.start()
        else:
            traced_write()
        return target

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # ------------------------------------------------------------------------ restore
    def all_steps(self) -> List[int]:
        out = []
        for d in sorted(os.listdir(self.directory)):
            if not d.startswith("step_"):
                continue
            try:
                step = int(d[5:])       # skips .tmp / .old crash leftovers
            except ValueError:
                continue
            if os.path.exists(os.path.join(self.directory, d,
                                           "manifest.json")):
                out.append(step)
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like, step: Optional[int] = None,
                shardings=None) -> tuple:
        """Restore into the structure of ``like`` (tree of arrays or
        ShapeDtypeStructs). Returns (tree, step, extra)."""
        self.wait()
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        target = os.path.join(self.directory, f"step_{step:08d}")
        mpath = os.path.join(target, "manifest.json")
        if not os.path.exists(mpath):
            raise FileNotFoundError(
                f"checkpoint step {step} has no committed manifest "
                f"(crash left an uncommitted tree?): {mpath}")
        with open(mpath) as f:
            manifest = json.load(f)
        # staleness/integrity validation BEFORE any bytes are materialized: a
        # manifest that disagrees with its directory name, a missing leaf
        # file, or a truncated one (torn write around the commit point) must
        # fail loudly here — not as a reshape error (or worse, silently wrong
        # params) deep inside restore
        if manifest.get("step") != step:
            raise ValueError(
                f"stale checkpoint: directory says step {step} but manifest "
                f"says step {manifest.get('step')}")
        names, leaves, treedef = _flatten_with_names(like)
        for name in names:
            ent = manifest["leaves"].get(name)
            if ent is None:
                raise KeyError(f"checkpoint step {step} has no leaf {name!r}")
            path = os.path.join(target, ent["file"])
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"checkpoint step {step}: leaf file missing: {path}")
            want = (int(np.prod(ent["shape"])) if ent["shape"] else 1) \
                * jnp.dtype(ent["dtype"]).itemsize
            got = os.path.getsize(path)
            if got != want:
                raise ValueError(
                    f"checkpoint step {step}: leaf {name!r} is {got} bytes, "
                    f"expected {want} ({ent['shape']} {ent['dtype']})")
        shard_leaves = (jax.tree_util.tree_leaves(shardings)
                        if shardings is not None else [None] * len(leaves))
        out = []
        for name, leaf, shd in zip(names, leaves, shard_leaves):
            ent = manifest["leaves"][name]
            dtype = jnp.dtype(ent["dtype"])
            with open(os.path.join(target, ent["file"]), "rb") as f:
                arr = np.frombuffer(f.read(), dtype=dtype).reshape(ent["shape"])
            val = jax.device_put(arr, shd) if shd is not None else jnp.asarray(arr)
            out.append(val)
        tree = jax.tree_util.tree_unflatten(treedef, out)
        return tree, manifest["step"], manifest["extra"]
