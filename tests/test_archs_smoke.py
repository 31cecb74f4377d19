"""Per-architecture smoke tests: reduced same-family configs, one forward +
one train step + one decode step on CPU; shapes + finiteness asserted, and
decode checked against forward. The FULL configs are checked only by their
analytic parameter counts (no allocation)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.models import model as M
from repro.training.optimizer import OptHParams
from repro.training.step import init_train_state, train_step

KEY = jax.random.PRNGKey(0)


def _reduced(name):
    cfg = ARCHS[name]
    n = 2 * len(cfg.block) if len(cfg.block) == 1 else len(cfg.block)
    return reduced(cfg, n_layers=n)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_and_decode(name):
    cfg = _reduced(name)
    params = M.init_params(KEY, cfg, jnp.float32)
    B, S = 2, 16
    batch = {"tokens": jnp.arange(B * S).reshape(B, S) % cfg.vocab}
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(KEY, (B, S, cfg.d_model),
                                            jnp.float32)
    rt = M.Runtime(q_chunk=8)
    logits, aux = M.forward(params, batch, cfg, rt)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    cache = M.init_cache(cfg, B, 32, jnp.float32, cross_len=S)
    lg, new_cache = M.decode_step(params, cache, batch["tokens"][:, 0],
                                  jnp.zeros(B, jnp.int32), cfg, rt)
    assert lg.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg)).all()


@pytest.mark.parametrize("name", sorted(n for n, c in ARCHS.items()
                                         if not c.enc_dec))
def test_decode_step_matches_forward(name):
    """Tokens fed one at a time through ``decode_step`` give, at each
    position, the logits ``forward`` gives for the whole sequence."""
    cfg = _reduced(name)
    params = M.init_params(KEY, cfg, jnp.float32)
    B, S = 2, 12
    tokens = (jnp.arange(B * S).reshape(B, S) * 7 + 3) % cfg.vocab
    rt = M.Runtime(q_chunk=8)
    want, _ = M.forward(params, {"tokens": tokens}, cfg, rt)
    step = jax.jit(lambda c, t, p: M.decode_step(params, c, t, p, cfg, rt))
    cache = M.init_cache(cfg, B, 16, jnp.float32)
    for t in range(S):
        got, cache = step(cache, tokens[:, t], jnp.full((B,), t, jnp.int32))
        w = np.asarray(want[:, t])
        err = np.abs(np.asarray(got) - w).max()
        assert err <= 1e-4 * np.abs(w).max(), (t, err)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_one_train_step(name):
    cfg = _reduced(name)
    hp = OptHParams(lr=1e-3)
    rt = M.Runtime(q_chunk=8, remat="none")
    state = init_train_state(KEY, cfg, hp, dtype=jnp.float32)
    B, S, accum = 2, 16, 2
    toks = jnp.arange(accum * B * (S + 1)).reshape(accum, B, S + 1) % cfg.vocab
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(KEY, (accum, B, S, cfg.d_model),
                                            jnp.float32)
    new_state, metrics = jax.jit(
        lambda st, b: train_step(st, b, cfg=cfg, hp=hp, rt=rt))(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state["step"]) == 1
    # params actually changed
    d0 = jax.tree.leaves(state["params"])[1]
    d1 = jax.tree.leaves(new_state["params"])[1]
    assert not np.allclose(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_count_matches_init(name):
    cfg = _reduced(name)
    shapes = jax.eval_shape(lambda: M.init_params(KEY, cfg, jnp.float32))
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert actual == cfg.param_count()


def test_full_configs_match_nominal_sizes():
    expect = {"chameleon-34b": 34, "internlm2-1.8b": 1.9, "qwen3-32b": 33,
              "gemma2-9b": 9.2, "jamba-1.5-large-398b": 399,
              "grok-1-314b": 316, "arctic-480b": 477, "falcon-mamba-7b": 7.3}
    for name, nominal in expect.items():
        got = ARCHS[name].param_count() / 1e9
        assert abs(got - nominal) / nominal < 0.08, (name, got)


def test_subquadratic_archs():
    subq = [c.name for c in ARCHS.values() if c.subquadratic]
    assert sorted(subq) == ["falcon-mamba-7b", "jamba-1.5-large-398b"]


def test_tp_padding_preserves_semantics():
    """Padded heads/vocab (for the 16-way model axis) must not change
    logits: padded wo rows are masked, padded vocab rows forced to -inf."""
    cfg = _reduced("starcoder2-7b")
    padded = dataclasses.replace(cfg, pad_heads_to=6, pad_vocab_to=520)
    params = M.init_params(KEY, padded, jnp.float32)
    B, S = 2, 16
    batch = {"tokens": jnp.arange(B * S).reshape(B, S) % cfg.vocab}
    logits, _ = M.forward(params, batch, padded, M.Runtime(q_chunk=8))
    assert logits.shape == (B, S, 520)
    assert np.all(np.asarray(logits[..., cfg.vocab:]) < -1e29)
    assert np.isfinite(np.asarray(logits[..., :cfg.vocab])).all()
