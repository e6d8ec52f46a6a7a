"""Fault-tolerant checkpointing: atomic, resumable, mesh-elastic.

Checkpoints store *logical* (unsharded) arrays + a JSON manifest, never
device buffers — restore re-shards onto whatever mesh is current, so a job
can come back on a different device count (elastic rescale) or after node
failure.  Writes are tmp-file + atomic rename; a corrupt/partial final
write is detected by the manifest checksum and the previous step is used.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# canonical home is repro.io.atomicdir (jax-free); re-exported here because
# the checkpoint store is where the protocol grew up and callers import it
from repro.io.atomicdir import fsync_path, publish_dir  # noqa: F401


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten(flat: dict, template, prefix: str = ""):
    if isinstance(template, dict):
        return {k: _unflatten(flat, template[k], f"{prefix}{k}/")
                for k in template}
    if isinstance(template, (list, tuple)):
        typ = type(template)
        return typ(_unflatten(flat, t, f"{prefix}{i}/")
                   for i, t in enumerate(template))
    return flat[prefix[:-1]]


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep

    def _step_dir(self, step: int) -> Path:
        return self.dir / f"step_{step:010d}"

    def save(self, step: int, tree, extra_meta: dict | None = None) -> Path:
        """Crash-safe save: everything is staged in a dot-prefixed tmp dir
        (invisible to :meth:`steps`), each file is flushed + fsynced, and
        the step is published by one atomic rename followed by a parent-dir
        fsync — a crash at ANY point leaves either the previous step intact
        or the new one complete, never a half-readable step dir.
        """
        tmp, manifest = self._begin(step, extra_meta)
        self._write_data(tmp, _flatten(jax.device_get(tree)), manifest)
        return self._publish(step, tmp, manifest)

    # -- staged save internals (subclassed by the sharded runtime manager) --
    def _begin(self, step: int, extra_meta: dict | None):
        tmp = self.dir / f".tmp_step_{step:010d}"
        if tmp.exists():
            shutil.rmtree(tmp)             # leftover of a killed save
        tmp.mkdir(parents=True)
        manifest = {"step": step, "arrays": {}}
        if extra_meta:
            manifest["meta"] = extra_meta
        return tmp, manifest

    def _write_data(self, tmp: Path, flat: dict, manifest: dict) -> None:
        with open(tmp / "data.bin", "wb") as f:
            off = 0
            for name, arr in flat.items():
                a = np.asarray(arr)
                raw = a.tobytes()
                f.write(raw)
                manifest["arrays"][name] = {
                    "dtype": str(a.dtype), "shape": list(a.shape),
                    "offset": off, "nbytes": len(raw),
                    "sha1": hashlib.sha1(raw).hexdigest()[:16],
                }
                off += len(raw)
            f.flush()
            os.fsync(f.fileno())

    def _publish(self, step: int, tmp: Path, manifest: dict) -> Path:
        with open(tmp / "manifest.json", "w") as f:
            f.write(json.dumps(manifest))
            f.flush()
            os.fsync(f.fileno())
        final = self._step_dir(step)
        publish_dir(tmp, final)
        self._gc()
        return final

    def _gc(self):
        steps = sorted(self.steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
        for p in self.dir.glob(".trash_step_*"):
            shutil.rmtree(p, ignore_errors=True)   # killed-swap orphans

    def steps(self) -> list[int]:
        """Published steps only: dot-prefixed staging dirs of killed saves
        never match, and a dir missing either file is skipped."""
        out = []
        for p in self.dir.glob("step_*"):
            if (p / "manifest.json").exists() and (p / "data.bin").exists():
                out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def _load_flat(self, step: int, verify: bool = True) -> dict:
        d = self._step_dir(step)
        manifest = json.loads((d / "manifest.json").read_text())
        data = (d / "data.bin").read_bytes()
        flat = {}
        for name, meta in manifest["arrays"].items():
            raw = data[meta["offset"]: meta["offset"] + meta["nbytes"]]
            if verify and hashlib.sha1(raw).hexdigest()[:16] != meta["sha1"]:
                raise IOError(f"checksum mismatch in {name} @ step {step}")
            flat[name] = np.frombuffer(raw, meta["dtype"]).reshape(
                meta["shape"])
        return flat

    def meta(self, step: int) -> dict:
        """The ``extra_meta`` dict stored with a step ({} if none)."""
        d = self._step_dir(step)
        return json.loads((d / "manifest.json").read_text()).get("meta", {})

    def restore(self, template, step: int | None = None, shardings=None):
        """Restore into the structure of ``template``; optionally re-shard
        with a pytree of NamedSharding (elastic restore on a new mesh).
        Falls back to earlier steps on corruption."""
        steps = self.steps() if step is None else [step]
        for s in reversed(steps):
            try:
                flat = self._load_flat(s)
            except (IOError, json.JSONDecodeError, ValueError):
                # truncated data.bin (frombuffer/reshape ValueError),
                # checksum mismatch, unreadable manifest — a torn step dir
                # must fall back, not crash the resume
                continue
            try:
                tree = _unflatten(flat, template)
            except KeyError as e:
                # an intact checkpoint that simply lacks a template field is
                # a structural mismatch, not corruption — falling back would
                # misreport it as "no restorable checkpoint"
                raise KeyError(f"checkpoint step {s} does not match the "
                               f"restore template: missing {e}") from e

            def put(x, t, sh=None):
                arr = jnp.asarray(np.asarray(x), dtype=t.dtype
                                  if hasattr(t, "dtype") else None)
                if sh is not None:
                    arr = jax.device_put(arr, sh)
                return arr

            if shardings is not None:
                tree = jax.tree.map(put, tree, template, shardings)
            else:
                tree = jax.tree.map(put, tree, template)
            return tree, s
        raise FileNotFoundError(f"no restorable checkpoint in {self.dir}")

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None
