"""The checkpoint store's file: raw leaf bytes after a pickled header,
round-tripped bit for bit, written durably or not at all."""
import os
import struct

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.checkpoint import store as store_mod
from repro.launch.train import check_restorable


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "w": rng.standard_normal((4, 6)).astype(ml_dtypes.bfloat16),
            "layers": [rng.standard_normal(5).astype(np.float32),
                       rng.standard_normal((2, 3)).astype(ml_dtypes.bfloat16)],
        },
        "opt": {"m": rng.integers(-128, 128, (3, 7), dtype=np.int8),
                "scale": rng.standard_normal(3).astype(np.float32),
                "empty": np.zeros((0, 4), np.float32)},
        "step": np.asarray(np.int32(seed + 7)),
    }


def _bits(tree):
    return [(a.dtype, a.shape, a.tobytes()) for a in jax.tree.leaves(tree)]


def _files(d):
    return sorted(os.listdir(d))


@pytest.mark.parametrize("on_device", [False, True],
                         ids=["host_arrays", "device_arrays"])
def test_save_then_latest_round_trips_bit_exactly(tmp_path, on_device):
    state = _state()
    store = CheckpointStore(str(tmp_path))
    store.save(jax.tree.map(jnp.asarray, state) if on_device else state, 7)
    step, back = store.latest()
    assert step == 7
    assert jax.tree.structure(back) == jax.tree.structure(state)
    assert isinstance(back["params"]["layers"], list)
    assert _bits(back) == _bits(state)
    check_restorable(back, jax.eval_shape(lambda: state))
    assert back["params"]["w"].dtype == jnp.bfloat16


def test_each_leaf_is_written_once_after_the_header(tmp_path):
    state = _state()
    path = CheckpointStore(str(tmp_path)).save(state, 3)
    with open(path, "rb") as f:
        assert f.read(8) == store_mod.MAGIC
        (header,) = struct.unpack("<Q", f.read(8))
    nbytes = sum(a.nbytes for a in jax.tree.leaves(state))
    assert os.path.getsize(path) == 16 + header + nbytes


def _raise_on_the_second_leaf(monkeypatch):
    calls = []
    raw = store_mod._raw

    def failing(a):
        calls.append(a)
        if len(calls) == 2:
            raise OSError(28, "No space left on device")
        return raw(a)
    monkeypatch.setattr(store_mod, "_raw", failing)


def _raise_in_fsync(monkeypatch):
    def failing(fd):
        raise OSError(5, "Input/output error")
    monkeypatch.setattr(store_mod.os, "fsync", failing)


@pytest.mark.parametrize("fault", [_raise_on_the_second_leaf,
                                   _raise_in_fsync],
                         ids=["write", "fsync"])
def test_a_save_that_raises_leaves_nothing_behind(tmp_path, monkeypatch,
                                                  fault):
    store = CheckpointStore(str(tmp_path))
    store.save(_state(0), 7)
    before = _files(tmp_path)
    fault(monkeypatch)
    with pytest.raises(OSError):
        store.save(_state(1), 14)
    monkeypatch.undo()
    assert store.status(14) == "unknown"
    assert _files(tmp_path) == before               # no temp file
    step, back = store.latest()
    assert step == 7 and _bits(back) == _bits(_state(0))


def test_gc_keeps_the_newest_two(tmp_path):
    store = CheckpointStore(str(tmp_path))
    assert store.latest() == (None, None)
    for step in (2, 4, 6, 8):
        store.save(_state(step), step)
    store.gc()
    assert [store.status(s) for s in (2, 4, 6, 8)] == [
        "unknown", "unknown", "success", "success"]
    assert len(_files(tmp_path)) == 2
    step, back = store.latest()
    assert step == 8 and _bits(back) == _bits(_state(8))


def test_a_truncated_file_is_refused(tmp_path):
    store = CheckpointStore(str(tmp_path))
    path = store.save(_state(), 5)
    os.truncate(path, os.path.getsize(path) - 1)
    with pytest.raises(ValueError, match="ends inside a leaf"):
        store.latest()
