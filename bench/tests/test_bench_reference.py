"""The plain reference against the program at a tiny size on the CPU: the
same seeded weights bit for bit, the same batches as the training feed, and
at float32 the same loss and gradient norm of a training step."""
import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

import jax
import jax.numpy as jnp

from bench import harness, reference as R

SEED = bench_tiny.SEED


def _cfg(name):
    with open(os.path.join(os.path.dirname(__file__), "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tiny-lm", "tiny-mamba"])
def test_seeded_weights_are_the_programs(name):
    from repro.models import model as M
    cfg = _cfg(name)
    a, arch = harness.dims(cfg), harness.arch_config(cfg)
    prog = M.init_params(jax.random.PRNGKey(SEED), arch, jnp.bfloat16)
    ref = R.init_params(SEED, a, jnp.bfloat16)
    for k in ("embed", "final_norm", "unembed"):
        np.testing.assert_array_equal(np.asarray(prog[k], np.float32),
                                      np.asarray(ref[k], np.float32))
    block = prog["blocks"][0]
    flat = {}
    for k, v in block.items():
        flat.update(v if isinstance(v, dict) else {k: v})
    assert set(flat) == set(ref["layers"])
    for k, v in flat.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(ref["layers"][k], np.float32))


def test_corpus_batches_are_the_feeds():
    from repro.data.pipeline import BatchOperator, SyntheticCorpus, pack_fn
    seq, bs, vocab = 16, 4, 100
    shards = SyntheticCorpus(8, (bs // 2) * (seq + 1), vocab, SEED).effect("x")
    packed = [pack_fn(seq)(s) for s in shards]
    agg = BatchOperator("batch", 2, bs).agg
    for step in range(1, 5):
        want = agg(packed[2 * (step - 1): 2 * step])["tokens"]
        np.testing.assert_array_equal(
            R.corpus_batch(SEED, step, vocab, seq, bs), want)


@pytest.mark.parametrize("name", ["tiny-lm", "tiny-mamba"])
def test_float32_step_matches_the_program(name):
    from repro.models import model as M
    from repro.training.loss import loss_fn
    from repro.training.optimizer import global_norm
    cfg = _cfg(name)
    a, arch = harness.dims(cfg), harness.arch_config(cfg)
    t = cfg["train"]
    toks = R.corpus_batch(SEED, 1, a["vocab_size"], t["seq_len"],
                          t["batch_size"])
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    params = M.init_params(jax.random.PRNGKey(SEED), arch, jnp.float32)
    rt = M.Runtime(remat="full", q_chunk=min(t["seq_len"], 512))
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, arch, rt)
        ref = R.Reference(a, dict(t, param_dtype="float32"),
                          cfg["optimizer"], SEED)
        ref_loss, ref_gn = ref.loss_and_grad(toks)
    assert ref_loss == pytest.approx(float(loss), rel=1e-5)
    assert ref_gn == pytest.approx(float(global_norm(g)), rel=1e-5)
