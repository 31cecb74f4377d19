"""The plain reference against the program at a tiny size on the CPU: the
same seeded weights bit for bit, the same batches as the training feed, and
at float32 the same loss and gradient norm of a training step."""
import json
import math
import os
import types

import numpy as np
import pytest

import bench_tiny  # puts the checkout on sys.path

import jax
import jax.numpy as jnp

from bench import harness, reference as R

SEED = bench_tiny.SEED


def _cfg(name):
    with open(os.path.join(os.path.dirname(__file__), "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", bench_tiny.config_names())
def test_seeded_weights_are_the_programs(name):
    from repro.models import model as M
    cfg = _cfg(name)
    a, arch = harness.dims(cfg), harness.arch_config(cfg)
    prog = M.init_params(jax.random.PRNGKey(SEED), arch, jnp.bfloat16)
    ref = R.init_params(SEED, a, jnp.bfloat16)
    assert set(prog) - {"blocks"} == set(ref) - {"layers"}
    for k in ("embed", "final_norm", "unembed"):
        np.testing.assert_array_equal(np.asarray(prog[k], np.float32),
                                      np.asarray(ref[k], np.float32))
    assert len(prog["blocks"]) == len(ref["layers"]) == len(cfg["period"])
    for block, layers in zip(prog["blocks"], ref["layers"]):
        flat = {}
        for k, v in block.items():
            flat.update(v if isinstance(v, dict) else {k: v})
        assert set(flat) == set(layers)
        for k, v in flat.items():
            np.testing.assert_array_equal(np.asarray(v, np.float32),
                                          np.asarray(layers[k], np.float32))


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


@pytest.mark.parametrize("name", bench_tiny.config_names())
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


def test_tied_head_is_the_untied_one_with_unembed_the_embedding():
    # Jamba's tied head: logits h . embed^T, with no embedding scale; the
    # embedding's gradient is the lookup's plus the head's
    cfg = _cfg("tiny-lm")
    a = harness.dims(cfg)
    t = dict(cfg["train"], param_dtype="float32")
    toks = R.corpus_batch(SEED, 1, a["vocab_size"], t["seq_len"],
                          t["batch_size"])
    with jax.default_matmul_precision("highest"):
        tied = R.Reference(dict(a, tie_word_embeddings=True), t,
                           cfg["optimizer"], SEED)
        assert "unembed" not in tied.params
        untied = R.Reference(a, t, cfg["optimizer"], SEED)
        untied.params = dict(tied.params, unembed=tied.params["embed"].T)
        loss_t, gnorm_t = tied.loss_and_grad(toks)
        loss_u, _ = untied.loss_and_grad(toks)
        g_t, g_u = R.flat_leaves(tied.grads), R.flat_leaves(untied.grads)
        tied.update()
        assert set(tied.first_moment_norms()) == set(g_t)
    assert loss_t == pytest.approx(loss_u, rel=1e-6)
    assert set(g_t) == set(g_u) - {"unembed"}
    np.testing.assert_allclose(g_t["embed"], g_u["embed"] + g_u["unembed"].T,
                               rtol=1e-5, atol=1e-9)
    for k in set(g_t) - {"embed"}:
        np.testing.assert_allclose(g_t[k], g_u[k], rtol=1e-5, atol=1e-9)
    assert gnorm_t == pytest.approx(math.sqrt(sum(
        float(np.sum(np.square(v, dtype=np.float64))) for v in g_t.values())),
        rel=1e-5)


def test_a_parts_loss_term_is_in_the_loss_and_its_gradient(monkeypatch):
    # no shipped part has one (an MoE router's balance loss would): an MLP
    # whose output's mean square is added to the loss, against the gradient
    # of the whole model in one piece
    from bench import layers
    mlp = layers.part("mlp")

    def forward(p, h, a, mm):
        y, _ = mlp.forward(p, h, a, mm)
        return y, jnp.mean(jnp.square(y))
    fake = types.SimpleNamespace(**{k: getattr(mlp, k) for k in (
        "ROLE", "SPEC", "KEYS", "SUBKEY", "arch_fields", "init",
        "matmul_params", "flops_fwd")}, forward=forward)
    part = layers.part
    monkeypatch.setattr(layers, "part",
                        lambda n: fake if n == "mlp_aux" else part(n))
    cfg = _cfg("tiny-lm")
    cfg["period"] = [{"mixer": "attention", "ffn": "mlp_aux"}]
    a = harness.dims(cfg)
    t = dict(cfg["train"], param_dtype="float32")
    toks = R.corpus_batch(SEED, 1, a["vocab_size"], t["seq_len"],
                          t["batch_size"])

    def whole(params):
        x = jnp.take(params["embed"], toks[:, :-1], axis=0)
        aux = 0.0
        for i in range(a["num_hidden_layers"]):
            lp = jax.tree.map(lambda v: v[i], params["layers"][0])
            x, term = R.layer_forward(lp, x, a, 0)
            aux = aux + term
        head = {k: params[k] for k in ("final_norm", "unembed")}
        return R.head_loss_sum(head, x, toks[:, 1:], a, t["z_loss"]) / (
            toks[:, 1:].size) + aux

    with jax.default_matmul_precision("highest"):
        ref = R.Reference(a, t, cfg["optimizer"], SEED)
        loss, gnorm = ref.loss_and_grad(toks)
        want, g = jax.value_and_grad(whole)(ref.params)
        plain, _ = R.Reference(harness.dims(_cfg("tiny-lm")), t,
                               cfg["optimizer"], SEED).loss_and_grad(toks)
    assert loss - plain > 1e-3
    assert loss == pytest.approx(float(want), rel=1e-5)
    assert gnorm == pytest.approx(math.sqrt(sum(
        float(jnp.sum(jnp.square(v))) for v in jax.tree.leaves(g))), rel=1e-5)
