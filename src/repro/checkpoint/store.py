"""Checkpointing: checkable, durable write actions on a checkpoint store.

A checkpoint is a *write action* in the LOG.io sense: durable (fsync'd file
with a step id) and checkable (``status`` reads the step id back), so the
recovery protocol guarantees exactly-once commits even if the trainer dies
mid-save. Restart = load latest complete checkpoint + let the LOG.io data
pipeline replay the batches after it (deterministic feed ⇒ bit-identical
resume up to hardware nondeterminism).

Supports elastic re-sharding: checkpoints are stored unsharded (gathered
pytree) and re-split according to the restart mesh.

File layout (``ckpt_<step:08d>.ckpt``): the 8-byte magic ``MAGIC``, the
header's length as an 8-byte little-endian integer, the header (a pickled
dict: ``step``, the tree's ``PyTreeDef`` and, per leaf in tree order, its
dtype name, shape, byte offset from the header's end and byte count),
then each leaf's raw C-order bytes at its offset, back to back. The
leaves are written from the host arrays themselves and read back into
arrays of their own, so no leaf's bytes are copied on the host on either
side.
"""
from __future__ import annotations

import contextlib
import os
import pickle
import struct
import tempfile
import threading
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import span

MAGIC = b"LOGIOCK1"
_LEN = struct.Struct("<Q")


def _raw(a: np.ndarray) -> np.ndarray:
    """The bytes of ``a`` as a flat ``uint8`` view (no copy when ``a`` is
    C-contiguous, as a host copy of a device array is)."""
    return a.reshape(-1).view(np.uint8)


class CheckpointStore:
    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self.lock = threading.Lock()

    def _path(self, step: int) -> str:
        return os.path.join(self.dir, f"ckpt_{step:08d}.ckpt")

    def _steps(self) -> List[int]:
        return sorted(int(f[5:13]) for f in os.listdir(self.dir)
                      if f.startswith("ckpt_") and f.endswith(".ckpt"))

    def save(self, state: Any, step: int) -> str:
        """Durable write: temp file + atomic rename (the 'success response'
        of Sec. 2.2 — once renamed, the write is durable). Spans: the copy
        to the host (``ckpt.pull``), the header and the leaves' bytes
        written and flushed (``ckpt.write``, ``bytes=`` the leaves' bytes),
        the fsync and rename (``ckpt.fsync``). A save that raises leaves no
        temp file behind."""
        with span("ckpt.pull"):
            host_state = jax.tree.map(np.asarray, state)
        leaves, treedef = jax.tree.flatten(host_state)
        index, nbytes = [], 0
        for a in leaves:
            index.append((a.dtype.name, a.shape, nbytes, a.nbytes))
            nbytes += a.nbytes
        header = pickle.dumps({"step": step, "treedef": treedef,
                               "leaves": index}, protocol=5)
        path = self._path(step)
        with self.lock:
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    with span("ckpt.write", bytes=nbytes):
                        f.write(MAGIC + _LEN.pack(len(header)) + header)
                        for a in leaves:
                            f.write(_raw(a))
                        f.flush()
                    with span("ckpt.fsync"):
                        os.fsync(f.fileno())
                        f.close()
                        os.replace(tmp, path)
            except BaseException:
                with contextlib.suppress(FileNotFoundError):
                    os.remove(tmp)
                raise
        return path

    def status(self, step: int) -> str:
        """Checkable write action (Alg 8 step 2.a)."""
        return "success" if os.path.exists(self._path(step)) else "unknown"

    def latest(self) -> Tuple[Optional[int], Optional[Any]]:
        with self.lock:
            steps = self._steps()
        if not steps:
            return None, None
        with open(self._path(steps[-1]), "rb") as f:
            if f.read(len(MAGIC)) != MAGIC:
                raise ValueError(f"{f.name} is not a checkpoint file")
            (size,) = _LEN.unpack(f.read(_LEN.size))
            header = pickle.loads(f.read(size))
            start = f.tell()
            leaves = []
            for dtype, shape, offset, count in header["leaves"]:
                a = np.empty(shape, jnp.dtype(dtype))
                f.seek(start + offset)
                if f.readinto(_raw(a)) != count:
                    raise ValueError(f"{f.name} ends inside a leaf")
                leaves.append(a)
        return header["step"], jax.tree.unflatten(header["treedef"], leaves)

    def gc(self, keep: int = 2):
        with self.lock:
            for s in self._steps()[:-keep]:
                os.remove(self._path(s))
