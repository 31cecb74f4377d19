"""Pallas TPU chunked selective scan (Mamba-1 recurrence).

h_t = a_t * h_{t-1} + b_t, elementwise over the flattened (d_inner x d_state)
feature dim. The kernel keeps the per-chunk [chunk, block_f] tiles plus the
carried state in VMEM; a_t/b_t never round-trip to HBM between timesteps
(the XLA associative-scan path materialises [B,S,di,ds] f32 intermediates).
It has no backward, so no train step takes it: ``chip_smoke.py`` and the
tests check it against ``kernels/ref.py``.

Grid: (B, F/block_f, S/chunk); the chunk axis is sequential ("arbitrary"),
carrying h in a VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        h = a_ref[0, t, :] * h + b_ref[0, t, :]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[0, :])
    h_scr[0, :] = h


def selective_scan(a: jax.Array, b: jax.Array, *, chunk: int = 256,
                   block_f: int = 1024, interpret: bool) -> jax.Array:
    """a, b: [B, S, DI, DS] f32 -> h [B, S, DI, DS] (see ref.py oracle)."""
    B, S, DI, DS = a.shape
    F = DI * DS
    af = a.reshape(B, S, F)
    bf = b.reshape(B, S, F)
    chunk = min(chunk, S)
    block_f = min(block_f, F)
    assert S % chunk == 0 and F % block_f == 0, (S, F, chunk, block_f)
    grid = (B, F // block_f, S // chunk)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_f), lambda b_, jf, ic: (b_, ic, jf)),
            pl.BlockSpec((1, chunk, block_f), lambda b_, jf, ic: (b_, ic, jf)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_f),
                               lambda b_, jf, ic: (b_, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((B, S, F), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_f), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(af, bf)
    return out.reshape(B, S, DI, DS)
