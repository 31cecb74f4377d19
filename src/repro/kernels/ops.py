"""Jit'd public wrappers around the Pallas kernels.

The backend decides how a kernel runs: on ``cpu`` it is interpreted (the
kernel body executes per grid step in Python — correctness only); on
``tpu`` it is compiled through Mosaic. There is no switch for it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import selective_scan as _ss


def interpret_mode() -> bool:
    """True where kernels run in the Pallas interpreter (the CPU backend)."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = _fa.BLOCK_Q, block_k: int = _fa.BLOCK_K):
    """q [B,S,H,D]; k,v [B,Sk,KV,D], H a multiple of KV (GQA). Has a
    backward."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk", "block_f"))
def selective_scan(a, b, *, chunk: int = 256, block_f: int = 1024):
    """Linear recurrence h_t = a_t h_{t-1} + b_t; a,b [B,S,DI,DS] f32."""
    return _ss.selective_scan(a, b, chunk=chunk, block_f=block_f,
                              interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("softcap", "window", "block_k"))
def decode_attention(q, k, v, lengths, *, softcap: Optional[float] = None,
                     window: Optional[int] = None, block_k: int = 1024):
    """q [B,H,D]; k,v [B,S,H,D]; lengths [B] -> [B,H,D]."""
    return _da.decode_attention(q, k, v, lengths, softcap=softcap,
                                window=window, block_k=block_k,
                                interpret=interpret_mode())
