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
