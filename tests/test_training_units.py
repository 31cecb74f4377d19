"""Units for the training substrate: quantization, optimizer, compression,
data pipeline determinism, checkpoint store."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore
from repro.data.pipeline import SyntheticCorpus, pack_fn
from repro.training import quant
from repro.training.optimizer import (OptHParams, adamw_update,
                                      init_opt_state)


def test_quant_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    for shape in [(100,), (33, 77), (4, 5, 6)]:
        x = jnp.asarray(rng.standard_normal(shape) * 3, jnp.float32)
        q = quant.quant(x)
        back = quant.dequant(q)
        assert back.shape == x.shape
        # per-row scaling: error bounded by each row's max/127
        row_scale = np.abs(np.asarray(x)).max(-1, keepdims=True)
        err = np.abs(np.asarray(back - x))
        assert (err <= row_scale / 127 + 1e-6).all()


def test_quant_shape_preserving():
    q = quant.qzeros_like(jnp.zeros((35, 7168)))
    assert q.q.shape == (35, 7168)          # sharding-compatible with param
    assert q.scale.shape == (35, 1)


@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16", "int8"])
def test_adamw_descends(moment_dtype):
    hp = OptHParams(lr=0.1, warmup=1, weight_decay=0.0,
                    moment_dtype=moment_dtype)
    params = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    opt = init_opt_state(params, hp)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    for _ in range(30):
        g = jax.grad(loss)(params)
        params, opt, gn = adamw_update(params, g, opt, hp)
    assert float(loss(params)) < 1.0


def test_synthetic_corpus_replayable():
    c = SyntheticCorpus(n_shards=8, shard_tokens=64, vocab=100, seed=5)
    a = c.effect("read", 0)
    b = c.effect("read", 3)
    assert len(a) == 8 and len(b) == 5
    np.testing.assert_array_equal(a[3]["tokens"], b[0]["tokens"])


def test_pack_fn_shapes():
    fn = pack_fn(seq_len=16)
    out = fn({"shard": 0, "tokens": np.arange(100, dtype=np.int32)})
    assert out["seqs"].shape == (100 // 17, 17)


def test_checkpoint_store_checkable(tmp_path):
    store = CheckpointStore(str(tmp_path))
    state = {"w": np.arange(10.0), "step": np.int32(7)}
    assert store.status(7) == "unknown"
    store.save(state, 7)
    assert store.status(7) == "success"          # checkable write action
    step, back = store.latest()
    assert step == 7
    np.testing.assert_array_equal(back["w"], state["w"])
    store.save(state, 14)
    store.gc(keep=1)
    assert store.status(7) == "unknown" and store.status(14) == "success"


def test_grad_compression_roundtrip_small_error():
    from repro.training.step import train_step
    from repro.configs import get_config, reduced
    from repro.models import model as M
    cfg = reduced(get_config("internlm2-1.8b"), d_model=64, n_layers=2,
                  vocab=128)
    hp = OptHParams(lr=1e-3)
    rt = M.Runtime(q_chunk=8, remat="none")
    from repro.training.step import init_train_state
    state = init_train_state(jax.random.PRNGKey(0), cfg, hp,
                             dtype=jnp.float32)
    toks = jnp.arange(2 * 2 * 17).reshape(2, 2, 17) % cfg.vocab
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    s1, m1 = train_step(state, batch, cfg=cfg, hp=hp, rt=rt,
                        compress_grads=False)
    s2, m2 = train_step(state, batch, cfg=cfg, hp=hp, rt=rt,
                        compress_grads=True)
    # int8 grad compression perturbs the update only slightly
    w1 = jax.tree.leaves(s1["params"])[1]
    w2 = jax.tree.leaves(s2["params"])[1]
    rel = np.abs(np.asarray(w1 - w2)).max() / (
        np.abs(np.asarray(w1)).max() + 1e-9)
    assert rel < 0.02
    assert np.isfinite(float(m2["loss"]))


def test_stale_checkpoint_of_another_width_is_refused(tmp_path):
    """A ckpt_dir left by a run of another width must not be resumed."""
    from repro.launch.train import run_training
    d = str(tmp_path / "ckpt")
    out = run_training(steps=2, ckpt_every=2, seq_len=16, batch_size=2,
                       ckpt_dir=d, d_model=32, n_layers=1, verbose=False)
    assert out["store"].latest()[0] == 2
    with pytest.raises(ValueError, match="checkpoint leaf"):
        run_training(steps=4, ckpt_every=2, seq_len=16, batch_size=2,
                     ckpt_dir=d, d_model=64, n_layers=1, verbose=False)


def test_checkpoint_of_the_same_configuration_resumes(tmp_path):
    from repro.launch.train import run_training
    d = str(tmp_path / "ckpt")
    kw = dict(ckpt_every=2, seq_len=16, batch_size=2, ckpt_dir=d,
              d_model=32, n_layers=1, verbose=False)
    run_training(steps=2, **kw)
    out = run_training(steps=4, **kw)
    assert len(out["losses"]) == 2 and out["steps"] == 4
    assert len(out["timings"]["restore_s"]) == 1
