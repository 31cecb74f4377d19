"""The Pallas kernels compile through Mosaic for one described TPU v5e chip
at real widths: internlm2-1.8b attention (H=16, D=128, S=4096, bf16), a
B=8 x 4096 decode cache, and falcon-mamba-7b's d_inner x d_state scan at
chunk 256. Nothing runs; the compile raises what the chip's compiler would.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import selective_scan as _ss


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _lm():
    cfg = get_config("internlm2-1.8b")
    return cfg.n_heads, cfg.d_head


def test_flash_attention_compiles_for_v5e(one_chip):
    H, D = _lm()
    qkv = ((1, 4096, H, D), jnp.bfloat16)
    _compile(lambda q, k, v: _fa.flash_attention(q, k, v, interpret=False),
             one_chip, qkv, qkv, qkv)


def test_decode_attention_compiles_for_v5e(one_chip):
    H, D = _lm()
    B, S = 8, 4096
    cache = ((B, S, H, D), jnp.bfloat16)
    _compile(lambda q, k, v, n: _da.decode_attention(q, k, v, n,
                                                     interpret=False),
             one_chip, ((B, H, D), jnp.bfloat16), cache, cache,
             ((B,), jnp.int32))


def test_selective_scan_compiles_for_v5e(one_chip):
    cfg = get_config("falcon-mamba-7b")
    ab = ((1, 1024, cfg.d_inner, cfg.mamba.d_state), jnp.float32)
    _compile(lambda a, b: _ss.selective_scan(a, b, chunk=256,
                                             interpret=False),
             one_chip, ab, ab)
