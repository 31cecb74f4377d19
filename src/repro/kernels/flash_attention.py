"""Pallas TPU flash attention (blockwise online softmax).

Target: TPU v5e MXU — q/k/v tiles stream HBM->VMEM in (block_q x block_k)
steps; scores/normalisers never touch HBM (this removes the dominant
memory-roofline term of the XLA attention path: the [B,H,S,S_chunk] f32
score tensors). Supports causal + sliding-window masks and tanh soft-capping
(gemma2). GQA is handled by the caller (kv expanded to q heads — the repeat
is free inside the kernel index_map: kv head index = h // group).

Layout: ``[B, S, H, D]`` is viewed (free reshape) as ``[B, S, H*D]`` and a
head is the column block ``h`` of width D, so every block's last two dims
are ``(block, D)``: Mosaic's (8, 128) tiling rule holds for D a multiple of
128 without transposing the inputs.

Validated on CPU via ``interpret=True`` against ``ref.flash_attention_ref``.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], block_q: int, block_k: int,
            n_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # [bq, D]
    k = k_ref[...].astype(jnp.float32)                 # [bk, D]
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool) -> jax.Array:
    """q,k,v: [B, S, H, D] (H = q heads; kv pre-expanded). -> [B, S, H, D]."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k
    grid = (B, H, n_q, n_k)

    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k=n_k)
    q_spec = pl.BlockSpec((None, block_q, D), lambda b, h, iq, ik: (b, iq, h))
    kv_spec = pl.BlockSpec((None, block_k, D), lambda b, h, iq, ik: (b, ik, h))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max  m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum  l
            pltpu.VMEM((block_q, D), jnp.float32),   # accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q.reshape(B, Sq, H * D), k.reshape(B, Sk, H * D),
      v.reshape(B, Sk, H * D))
    return out.reshape(B, Sq, H, D)
