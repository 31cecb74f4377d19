"""Pallas TPU decode attention: one query token against a long KV cache.

Used by decode_32k / long_500k serving: for each (batch slot, head) the
kernel streams KV blocks HBM->VMEM and maintains the online-softmax
normaliser in VMEM, so the [B,H,S] score tensor never exists in HBM.
Per-slot valid lengths mask the tail; optional sliding window (gemma2 local
layers) and soft-capping.

Layout: as in ``flash_attention``, heads are column blocks of width D of the
``[B, S, H*D]`` view of the cache (and of the ``[B, 1, H*D]`` view of q), so
Mosaic's (8, 128) block tiling holds for D a multiple of 128. The per-slot
lengths arrive by scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: Optional[float], window: Optional[int],
            block_k: int, n_k: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                   # [1, D]
    k = k_ref[...].astype(jnp.float32)                   # [bk, D]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:                              # s: [1, bk]
        s = softcap * jnp.tanh(s / softcap)
    length = len_ref[b]
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = kpos < length
    if window is not None:
        valid &= kpos >= (length - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                  # [1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)        # [1, bk]
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *,
                     softcap: Optional[float] = None,
                     window: Optional[int] = None,
                     block_k: int = 1024, interpret: bool) -> jax.Array:
    """q: [B,H,D]; k,v: [B,S,H,D]; lengths: [B] -> [B,H,D]."""
    B, S, H, D = k.shape
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_k = S // block_k

    kern = functools.partial(_kernel, scale=1.0 / math.sqrt(D),
                             softcap=softcap, window=window,
                             block_k=block_k, n_k=n_k)
    q_spec = pl.BlockSpec((None, 1, D), lambda b, h, ik, lens: (b, 0, h))
    kv_spec = pl.BlockSpec((None, block_k, D),
                           lambda b, h, ik, lens: (b, ik, h))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, 1, H * D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), q.reshape(B, 1, H * D),
      k.reshape(B, S, H * D), v.reshape(B, S, H * D))
    return out.reshape(B, H, D)
