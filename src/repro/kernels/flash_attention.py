"""Pallas TPU flash attention with its backward (blockwise online softmax).

Target: TPU v5e MXU — q/k/v tiles stream HBM->VMEM in (block_q x block_k)
steps; scores and probabilities never touch HBM, in the forward or in the
backward (this removes the dominant memory-roofline term of the XLA
attention path: the [B,H,S,S_chunk] f32 score tensors). Supports causal +
sliding-window masks and tanh soft-capping (gemma2).

Layout: ``[B, S, H, D]`` is viewed (free reshape) as ``[B, S, H*D]`` and a
head is the column block ``h`` of width D, so every block's last two dims
are ``(block, D)``: Mosaic's (8, 128) tiling rule holds for D a multiple of
128 without transposing the inputs.

* GQA: k and v come at their own ``KV`` heads; q head ``h`` reads kv head
  ``h // (H // KV)`` in the ``index_map``, and dK, dV come out summed over
  each group of q heads.
* Causal (and window) skipping: a (q-block, k-block) pair that no query of
  the q block can see does no compute, and the ``index_map`` clamps its
  block index into the visible range, so the skipped steps re-use the
  resident block and start no DMA. Only blocks that the diagonal (or the
  window's edge) crosses compute a mask.
* Precision: MXU operands enter in the inputs' own dtype with float32
  accumulation; scaling, masking and the softmax are float32; the
  probabilities and dS are cast to the value dtype before their products.
* Backward (``jax.custom_vjp``): the forward also writes the per-row
  log-sum-exp; ``di = rowsum(o * do)`` is computed once; one kernel
  accumulates dK and dV over the q blocks (scores transposed, keys by
  queries, so that log-sum-exp and ``di`` broadcast as rows), one kernel
  accumulates dQ over the k blocks. Both recompute scores blockwise.

Validated on CPU via ``interpret=True`` against ``ref.flash_attention_ref``
and its ``jax.vjp``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# log-sum-exp and di rows are repeated over the 8 sublanes of one tile
SUBLANES = 8
NT = (((1,), (1,)), ((), ()))       # a @ b.T


# (block_q, block_k) of all three kernels, timed on one v5e chip at one
# internlm2-1.8b layer (B 2, S 4096, 16/8 heads of 128, causal; PERF.md)
BLOCK_Q, BLOCK_K = 1024, 1024


class _Attn(NamedTuple):
    """The static part of one call (hashable: ``custom_vjp``'s
    ``nondiff_argnums``)."""
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    block_q: int
    block_k: int
    interpret: bool


def fits(seq_len: int, d_head: int, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K) -> bool:
    """Whether Mosaic can tile a self-attention of this length and head
    width: D a multiple of 128 and each block (cut to S) a multiple of 128
    that divides S."""
    return d_head % 128 == 0 and all(
        min(b, seq_len) % 128 == 0 and seq_len % min(b, seq_len) == 0
        for b in (block_q, block_k))


# --------------------------------------------------------------------------
# which blocks a query block sees (ints or traced int32 scalars)
# --------------------------------------------------------------------------


def _k_range(iq, bq, bk, n_k, a: _Attn):
    """Inclusive range of the k blocks that q block ``iq`` sees."""
    if not a.causal:
        return 0, n_k - 1
    hi = (iq * bq + bq - 1) // bk
    lo = 0 if a.window is None else \
        jnp.maximum(iq * bq - a.window + 1, 0) // bk
    return lo, hi


def _q_range(ik, bq, bk, n_q, a: _Attn):
    """Inclusive range of the q blocks that see some key of k block ``ik``."""
    if not a.causal:
        return 0, n_q - 1
    lo = (ik * bk) // bq
    hi = n_q - 1 if a.window is None else \
        jnp.minimum((ik * bk + bk + a.window - 2) // bq, n_q - 1)
    return lo, hi


def _whole(iq, ik, bq, bk, a: _Attn):
    """True where every query of q block ``iq`` sees every key of k block
    ``ik``: the block needs no mask."""
    if not a.causal:
        return True
    whole = ik * bk + bk - 1 <= iq * bq
    if a.window is not None:
        whole &= ik * bk > iq * bq + bq - 1 - a.window
    return whole


def _visible(q0, k0, shape, q_axis: int, a: _Attn):
    """The causal (+ window) mask of one block; ``q_axis`` is the axis of
    queries (0 for [q, k] scores, 1 for transposed [k, q] ones)."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = k_pos <= q_pos
    if a.window is not None:
        keep &= k_pos > q_pos - a.window
    return keep


def _scores(x, y, scale, softcap):
    """``x @ y.T`` in float32, scaled and soft-capped; also the cap's tanh,
    which its gradient needs (None without a cap)."""
    s = lax.dot_general(x, y, NT, preferred_element_type=jnp.float32) * scale
    if softcap is None:
        return s, None
    t = jnp.tanh(s / softcap)
    return softcap * t, t


def _masked_or_whole(iq, ik, bq, bk, a: _Attn, live, step):
    """Run ``step(masked)`` for a visible block: without the mask where the
    block is whole."""
    whole = _whole(iq, ik, bq, bk, a)
    if whole is True:
        pl.when(live)(lambda: step(False))
        return
    pl.when(live & whole)(lambda: step(False))
    pl.when(live & jnp.logical_not(whole))(lambda: step(True))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, a: _Attn, scale: float, bq: int, bk: int, n_k: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        s, _ = _scores(q_ref[...], k_ref[...], scale, a.softcap)   # [bq, bk]
        if masked:
            keep = _visible(iq * bq, ik * bk, s.shape, 0, a)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]                                        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:   # a row with no visible key yet has m_new == NEG_INF
            p = jnp.where(keep, p, 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    lo, hi = _k_range(iq, bq, bk, n_k, a)
    _masked_or_whole(iq, ik, bq, bk, a, (ik >= lo) & (ik <= hi), step)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(safe)                           # [bq, 1]
        lse_ref[...] = jnp.broadcast_to(lse, (bq, 128)).T[:SUBLANES]


def _forward(q, k, v, a: _Attn):
    """-> o [B, S, H, D], lse [B, H, SUBLANES, S] float32."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    groups = H // KV
    bq, bk = min(a.block_q, Sq), min(a.block_k, Sk)
    n_q, n_k = Sq // bq, Sk // bk

    def kv_map(b, h, iq, ik):
        lo, hi = _k_range(iq, bq, bk, n_k, a)
        return b, jnp.minimum(jnp.maximum(ik, lo), hi), h // groups

    q_spec = pl.BlockSpec((None, bq, D), lambda b, h, iq, ik: (b, iq, h))
    kv_spec = pl.BlockSpec((None, bk, D), kv_map)
    lse_spec = pl.BlockSpec((None, None, SUBLANES, bq),
                            lambda b, h, iq, ik: (b, h, 0, iq))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, a=a, scale=1.0 / math.sqrt(D),
                          bq=bq, bk=bk, n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, SUBLANES, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max  m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum  l
            pltpu.VMEM((bq, D), jnp.float32),   # accumulator
        ],
        interpret=a.interpret,
        name="flash_fwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q.reshape(B, Sq, H * D), k.reshape(B, Sk, KV * D),
      v.reshape(B, Sk, KV * D))
    return o.reshape(B, Sq, H, D), lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, a: _Attn, scale: float, bq: int, bk: int,
                n_q: int, groups: int):
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        q, v, do = q_ref[...], v_ref[...], do_ref[...]
        s, t = _scores(k_ref[...], q, scale, a.softcap)            # [bk, bq]
        if masked:
            s = jnp.where(_visible(iq * bq, ik * bk, s.shape, 1, a), s,
                          NEG_INF)
        p = jnp.exp(s - lse_ref[:1, :])
        dv_scr[...] += lax.dot_general(
            p.astype(v.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[:1, :])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_scr[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    lo, hi = _q_range(ik, bq, bk, n_q, a)
    _masked_or_whole(iq, ik, bq, bk, a, (iq >= lo) & (iq <= hi), step)

    @pl.when((g == groups - 1) & (iq == n_q - 1))
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               *, a: _Attn, scale: float, bq: int, bk: int, n_k: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        k = k_ref[...]
        s, t = _scores(q_ref[...], k, scale, a.softcap)            # [bq, bk]
        if masked:
            s = jnp.where(_visible(iq * bq, ik * bk, s.shape, 0, a), s,
                          NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_scr[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    lo, hi = _k_range(iq, bq, bk, n_k, a)
    _masked_or_whole(iq, ik, bq, bk, a, (ik >= lo) & (ik <= hi), step)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _backward(a: _Attn, res, do):
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = 1.0 / math.sqrt(D)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(jnp.swapaxes(di, 1, 2)[:, :, None, :],
                          (B, H, SUBLANES, Sq))
    qf, dof = q.reshape(B, Sq, H * D), do.reshape(B, Sq, H * D)
    kf, vf = k.reshape(B, Sk, KV * D), v.reshape(B, Sk, KV * D)

    bq, bk = min(a.block_q, Sq), min(a.block_k, Sk)
    n_q, n_k = Sq // bq, Sk // bk

    # dK, dV: per kv head and k block, over the group's heads and q blocks
    def q_blk(ik, iq):
        lo, hi = _q_range(ik, bq, bk, n_q, a)
        return jnp.minimum(jnp.maximum(iq, lo), hi)

    q_spec = pl.BlockSpec((None, bq, D), lambda b, j, ik, g, iq: (
        b, q_blk(ik, iq), j * groups + g))
    kv_spec = pl.BlockSpec((None, bk, D), lambda b, j, ik, g, iq: (b, ik, j))
    row_spec = pl.BlockSpec((None, None, SUBLANES, bq),
                            lambda b, j, ik, g, iq: (
                                b, j * groups + g, 0, q_blk(ik, iq)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, a=a, scale=scale, bq=bq, bk=bk,
                          n_q=n_q, groups=groups),
        grid=(B, KV, n_k, groups, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=a.interpret,
        name="flash_dkv",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
    )(qf, kf, vf, dof, lse, di)

    # dQ: per q head and q block, over the k blocks
    def kv_map(b, h, iq, ik):
        lo, hi = _k_range(iq, bq, bk, n_k, a)
        return b, jnp.minimum(jnp.maximum(ik, lo), hi), h // groups

    q_spec = pl.BlockSpec((None, bq, D), lambda b, h, iq, ik: (b, iq, h))
    kv_spec = pl.BlockSpec((None, bk, D), kv_map)
    row_spec = pl.BlockSpec((None, None, SUBLANES, bq),
                            lambda b, h, iq, ik: (b, h, 0, iq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, a=a, scale=scale, bq=bq, bk=bk,
                          n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=a.interpret,
        name="flash_dq",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qf, kf, vf, dof, lse, di)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, a: _Attn):
    return _forward(q, k, v, a)[0]


def _attention_fwd(q, k, v, a: _Attn):
    o, lse = _forward(q, k, v, a)
    return o, (q, k, v, o, lse)


_attention.defvjp(_attention_fwd, _backward)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool) -> jax.Array:
    """q: [B, S, H, D]; k, v: [B, Sk, KV, D] with H a multiple of KV (GQA:
    q head h attends with kv head h // (H // KV)). -> [B, S, H, D].
    Differentiable in q, k and v. The mask is on sequence index, so the
    rows are positions 0..S-1; a causal call needs Sk == S."""
    H, KV = q.shape[2], k.shape[2]
    assert H % KV == 0, (H, KV)
    assert not causal or q.shape[1] == k.shape[1], (q.shape, k.shape)
    assert q.shape[1] % min(block_q, q.shape[1]) == 0, (q.shape, block_q)
    assert k.shape[1] % min(block_k, k.shape[1]) == 0, (k.shape, block_k)
    return _attention(q, k, v, _Attn(causal, window, softcap, block_q,
                                     block_k, interpret))
