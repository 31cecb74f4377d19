"""Checkpointing: checkable, durable write actions on a checkpoint store.

A checkpoint is a *write action* in the LOG.io sense: durable (fsync'd file
with a step id) and checkable (``status`` reads the step id back), so the
recovery protocol guarantees exactly-once commits even if the trainer dies
mid-save. Restart = load latest complete checkpoint + let the LOG.io data
pipeline replay the batches after it (deterministic feed ⇒ bit-identical
resume up to hardware nondeterminism).

Supports elastic re-sharding: checkpoints are stored unsharded (gathered
pytree) and re-split according to the restart mesh.
"""
from __future__ import annotations

import os
import pickle
import tempfile
import threading
from typing import Any, Optional, Tuple

import jax
import numpy as np

from repro.core.metrics import span


class CheckpointStore:
    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self.lock = threading.Lock()

    def _path(self, step: int) -> str:
        return os.path.join(self.dir, f"ckpt_{step:08d}.pkl")

    def save(self, state: Any, step: int) -> str:
        """Durable write: temp file + atomic rename (the 'success response'
        of Sec. 2.2 — once renamed, the write is durable). Spans: the copy
        to the host (``ckpt.pull``), the pickle (``ckpt.write``), the fsync
        and rename (``ckpt.fsync``)."""
        with span("ckpt.pull"):
            host_state = jax.tree.map(np.asarray, state)
        path = self._path(step)
        with self.lock:
            fd, tmp = tempfile.mkstemp(dir=self.dir)
            with os.fdopen(fd, "wb") as f:
                with span("ckpt.write"):
                    pickle.dump({"step": step, "state": host_state}, f)
                    f.flush()
                with span("ckpt.fsync"):
                    os.fsync(f.fileno())
                    f.close()
                    os.replace(tmp, path)
        return path

    def status(self, step: int) -> str:
        """Checkable write action (Alg 8 step 2.a)."""
        return "success" if os.path.exists(self._path(step)) else "unknown"

    def latest(self) -> Tuple[Optional[int], Optional[Any]]:
        with self.lock:
            steps = sorted(int(f[5:13]) for f in os.listdir(self.dir)
                           if f.startswith("ckpt_") and f.endswith(".pkl"))
        if not steps:
            return None, None
        with open(self._path(steps[-1]), "rb") as f:
            d = pickle.load(f)
        return d["step"], d["state"]

    def gc(self, keep: int = 2):
        with self.lock:
            steps = sorted(int(f[5:13]) for f in os.listdir(self.dir)
                           if f.startswith("ckpt_") and f.endswith(".pkl"))
            for s in steps[:-keep]:
                os.remove(self._path(s))
