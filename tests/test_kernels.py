"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


FLASH_CASES = [
    # (B, S, H, D, causal, window, softcap, dtype, block)
    (2, 128, 4, 64, True, None, None, jnp.float32, 64),
    (1, 256, 2, 128, True, 64, None, jnp.float32, 64),
    (2, 128, 4, 64, True, None, 50.0, jnp.float32, 32),
    (1, 128, 2, 64, False, None, None, jnp.float32, 64),
    (1, 128, 2, 256, True, None, None, jnp.float32, 128),
    (2, 64, 8, 64, True, 32, 30.0, jnp.float32, 32),
    (1, 128, 2, 64, True, None, None, jnp.bfloat16, 64),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    B, S, H, D, causal, window, softcap, dtype, blk = case
    q, k, v = (_rand((B, S, H, D), dtype) for _ in range(3))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=blk, block_k=blk)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


SCAN_CASES = [
    (2, 64, 32, 8, 16, 64),
    (1, 256, 16, 16, 32, 128),
    (3, 128, 8, 4, 128, 32),
    (1, 32, 64, 16, 32, 1024),
]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_matches_ref(case):
    B, S, DI, DS, chunk, bf = case
    a = jnp.asarray(RNG.uniform(0.5, 0.999, (B, S, DI, DS)), jnp.float32)
    b = _rand((B, S, DI, DS), jnp.float32)
    out = ops.selective_scan(a, b, chunk=chunk, block_f=bf)
    want = ref.selective_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


DECODE_CASES = [
    (2, 256, 4, 64, None, None, 64),
    (1, 512, 2, 128, 128, None, 128),
    (2, 128, 8, 64, None, 50.0, 32),
    (4, 64, 2, 256, 32, None, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_matches_ref(case):
    B, S, H, D, window, softcap, blk = case
    q = _rand((B, H, D), jnp.float32)
    k = _rand((B, S, H, D), jnp.float32)
    v = _rand((B, S, H, D), jnp.float32)
    lens = jnp.asarray(RNG.integers(1, S + 1, (B,)), jnp.int32)
    out = ops.decode_attention(q, k, v, lens, window=window, softcap=softcap,
                               block_k=blk)
    want = ref.decode_attention_ref(q, k, v, lens, window=window,
                                    softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_model_attention_path_uses_kernel_consistently():
    """The model's XLA attention path and the Pallas kernel agree."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models import layers as L
    from repro.configs.base import AttnSpec

    cfg = reduced(get_config("qwen3-32b"), d_model=64, n_heads=4,
                  n_kv_heads=2, vocab=128)
    p, _ = L.init_attention(jax.random.PRNGKey(0), cfg, AttnSpec(),
                            jnp.float32)
    x = _rand((2, 64, cfg.d_model), jnp.float32)
    pos = jnp.arange(64)[None, :]
    out_xla = L.apply_attention(p, x, AttnSpec(), cfg, pos, q_chunk=32)
    out_pallas = L.apply_attention(p, x, AttnSpec(), cfg, pos,
                                   attn_impl="pallas")
    np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_pallas),
                               rtol=2e-4, atol=2e-4)


def test_kernels_interpret_on_cpu_backend_only(monkeypatch):
    """The backend, not a switch, decides interpret mode."""
    import jax
    assert ops.interpret_mode() == (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False


# (B, S, H, KV, D, causal, window, softcap, dtype, (block_q, block_k))
FLASH_GRAD_CASES = [
    (2, 128, 4, 4, 64, True, None, None, jnp.float32, (32, 32)),
    (2, 128, 4, 2, 64, True, None, None, jnp.float32, (32, 32)),
    (1, 128, 4, 2, 64, False, None, None, jnp.float32, (64, 32)),
    (1, 256, 2, 1, 64, True, 64, None, jnp.float32, (64, 64)),
    (2, 64, 4, 2, 64, True, None, 30.0, jnp.float32, (32, 32)),
    (2, 64, 4, 2, 64, True, 24, 30.0, jnp.float32, (16, 32)),
    (1, 128, 4, 2, 64, True, None, None, jnp.bfloat16, (64, 64)),
    (1, 128, 4, 2, 64, False, None, None, jnp.bfloat16, (64, 64)),
    # causal skipping drops whole blocks, with block_q != block_k
    (1, 256, 4, 2, 64, True, None, None, jnp.float32, (64, 32)),
    (1, 256, 2, 1, 64, True, 80, None, jnp.float32, (32, 64)),
]


@pytest.mark.parametrize("case", FLASH_GRAD_CASES)
def test_flash_attention_grads_match_ref(case):
    """The kernel's vjp (dq, dk, dv; kv at its own heads, GQA summed over
    each group) against ``jax.vjp`` of the oracle on repeated kv heads."""
    import jax
    from repro.kernels import flash_attention as fa
    B, S, H, KV, D, causal, window, softcap, dtype, (bq, bk) = case
    q, do = (_rand((B, S, H, D), dtype) for _ in range(2))
    k, v = (_rand((B, S, KV, D), dtype) for _ in range(2))

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, block_q=bq, block_k=bk,
                                  interpret=True)

    def oracle(q, k, v):
        g = H // KV
        return ref.flash_attention_ref(q, jnp.repeat(k, g, 2),
                                       jnp.repeat(v, g, 2), causal=causal,
                                       window=window, softcap=softcap)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, vjp_ref = jax.vjp(oracle, q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, got, exp in zip(("o", "dq", "dk", "dv"), (out,) + vjp(do),
                              (want,) + vjp_ref(do)):
        assert got.dtype == exp.dtype, name
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def _tiny_attention_model(d_model=64, n_heads=4):
    from repro.configs import get_config, reduced
    return reduced(get_config("internlm2-1.8b"), d_model=d_model,
                   n_layers=2, vocab=128, d_ff=128, n_heads=n_heads)


def test_train_step_same_with_kernel_and_xla_attention():
    """One float32 train step of a tiny attention model: the same loss,
    gradient norm and per-leaf gradient (the first moment over 1 - b1)
    through the kernel as through the XLA path."""
    import jax
    from repro.models import model as M
    from repro.training.optimizer import OptHParams
    from repro.training.step import init_train_state, make_train_step
    cfg = _tiny_attention_model()
    hp = OptHParams(lr=1e-3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, hp,
                             dtype=jnp.float32)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (1, 2, 65)), jnp.int32)
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    out = {impl: jax.jit(make_train_step(
        cfg, hp, M.Runtime(remat="full", q_chunk=16, attn_impl=impl)))(
            state, batch) for impl in ("pallas", "xla")}
    (s_k, m_k), (s_x, m_x) = out["pallas"], out["xla"]
    for key in ("loss", "grad_norm"):
        np.testing.assert_allclose(float(m_k[key]), float(m_x[key]),
                                   rtol=1e-5, atol=1e-5, err_msg=key)
    for a, b in zip(jax.tree.leaves(s_k["opt"]["m"]),
                    jax.tree.leaves(s_x["opt"]["m"])):
        np.testing.assert_allclose(np.asarray(a) / (1 - hp.b1),
                                   np.asarray(b) / (1 - hp.b1),
                                   rtol=1e-5, atol=1e-5)


def _traced_paths(seq_len, d_head=128, kv_override=False, mesh=None):
    """``apply_attention`` traced (not run) with ``attn_impl="auto"``: the
    ``attn_paths`` tally of its one call site."""
    import jax
    from repro.configs.base import AttnSpec
    from repro.models import layers as L
    cfg = _tiny_attention_model(d_model=2 * d_head, n_heads=2)
    p, _ = L.init_attention(None, cfg, AttnSpec(), jnp.float32)
    x = jax.ShapeDtypeStruct((1, seq_len, cfg.d_model), jnp.float32)
    pos = jnp.arange(seq_len)[None, :]

    def attend(p, x):
        kvo = (x, pos) if kv_override else None
        return L.apply_attention(p, x, AttnSpec(), cfg, pos,
                                 kv_override=kvo, causal=not kv_override)

    with L.tally_attn_paths() as paths:
        if mesh is None:
            jax.eval_shape(attend, p, x)
        else:
            L.set_shard_ctx({"dp": ("data",), "tp": "model"})
            try:
                with mesh:
                    jax.eval_shape(attend, p, x)
            finally:
                L.set_shard_ctx(None)
    return paths


def test_auto_attention_takes_xla_on_cpu():
    assert _traced_paths(256) == {"kernel": 0, "xla": 1}


@pytest.mark.parametrize("where", ["tiles", "sharded", "cross", "untiled",
                                   "narrow_head"])
def test_auto_attention_path_where_kernels_compile(where, monkeypatch):
    """With kernels compiled (not interpreted), ``"auto"`` takes the kernel
    for a self-attention whose length and head width tile, and XLA under a
    sharding context, for ``kv_override`` and where they do not tile."""
    import jax
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    paths = {"tiles": lambda: _traced_paths(1024),
             "sharded": lambda: _traced_paths(1024, mesh=mesh),
             "cross": lambda: _traced_paths(1024, kv_override=True),
             "untiled": lambda: _traced_paths(200),
             "narrow_head": lambda: _traced_paths(1024, d_head=64)}[where]()
    want = {"kernel": 1, "xla": 0} if where == "tiles" else \
        {"kernel": 0, "xla": 1}
    assert paths == want


def test_kernel_ops_carry_the_mixer_scope():
    """In a compiled tiny train step, every instruction traced by the
    kernel's wrapper (``jit(flash_attention)``: the forward kernel, its
    rematerialised copy, and the ``custom_vjp`` backward with its dK/dV and
    dQ kernels) lies under the caller's ``mixer`` scope, so device time by
    scope counts the kernel there. The scalar combiners of reductions
    (``to_apply`` computations) are left out: they are no op of their own."""
    import re

    import jax
    from repro.models import model as M
    from repro.training.optimizer import OptHParams
    from repro.training.step import init_train_state, make_train_step
    cfg = _tiny_attention_model()
    hp = OptHParams()
    state = jax.eval_shape(lambda: init_train_state(
        jax.random.PRNGKey(0), cfg, hp, dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((1, 2, 64), jnp.int32)
    rt = M.Runtime(remat="full", q_chunk=16, attn_impl="pallas")
    hlo = jax.jit(make_train_step(cfg, hp, rt)).lower(
        state, {"tokens": tok, "labels": tok}).compile().as_text()
    combiners = set(re.findall(r"to_apply=%([\w.\-]+)", hlo))
    kernels = {"flash_fwd": 0, "flash_dkv": 0, "flash_dq": 0}
    comp, n = None, 0
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head:
            comp = head.group(1)
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if comp in combiners or name is None:
            continue
        path = name.group(1).split("/")
        if "jit(flash_attention)" not in path:
            continue
        n += 1
        assert "mixer" in path[:path.index("jit(flash_attention)")], \
            name.group(1)
        for kern in kernels:
            kernels[kern] += kern in path
    assert n > 0 and all(kernels.values()), kernels
