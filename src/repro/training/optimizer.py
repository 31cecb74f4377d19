"""Custom AdamW with configurable moment dtypes.

``moment_dtype=bf16`` halves optimizer-state HBM; ``moment_dtype=int8``
enables the blockwise-quantized (bnb-style) moments implemented in
``quant.py``, which ``presets.ONE_CHIP_TRAIN`` needs to fit internlm2-1.8b
on one chip.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.training import quant


@dataclasses.dataclass(frozen=True)
class OptHParams:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup: int = 100
    moment_dtype: str = "float32"       # "float32" | "bfloat16" | "int8"
    grad_accum_dtype: str = "float32"   # "float32" | "bfloat16"


def _moment_init(p, dtype_name):
    if dtype_name == "int8":
        return quant.qzeros_like(p)
    return jnp.zeros(p.shape, jnp.dtype(
        {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]))


def init_opt_state(params, hp: OptHParams) -> Dict[str, Any]:
    return {
        "m": jax.tree.map(lambda p: _moment_init(p, hp.moment_dtype), params),
        "v": jax.tree.map(lambda p: _moment_init(p, hp.moment_dtype), params),
        "count": jnp.zeros((), jnp.int32),
    }


def _read_moment(x, hp):
    if hp.moment_dtype == "int8":
        return quant.dequant(x)
    return x.astype(jnp.float32)


def _write_moment(x32, hp, like):
    if hp.moment_dtype == "int8":
        return quant.quant(x32, like)
    return x32.astype(like.dtype)


def schedule(count, hp: OptHParams):
    warm = jnp.minimum(count.astype(jnp.float32) / max(hp.warmup, 1), 1.0)
    return hp.lr * warm


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def adamw_update(params, grads, opt_state, hp: OptHParams):
    count = opt_state["count"] + 1
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, hp.clip_norm / (gn + 1e-9))
    lr = schedule(count, hp)
    b1c = 1.0 - hp.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - hp.b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32 = hp.b1 * _read_moment(m, hp) + (1 - hp.b1) * g
        v32 = hp.b2 * _read_moment(v, hp) + (1 - hp.b2) * jnp.square(g)
        mh = m32 / b1c
        vh = v32 / b2c
        step = mh / (jnp.sqrt(vh) + hp.eps) + hp.weight_decay * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
        return newp, _write_moment(m32, hp, m), _write_moment(v32, hp, v)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt_state["m"], is_leaf=quant.is_qtensor)
    flat_v = jax.tree.leaves(opt_state["v"], is_leaf=quant.is_qtensor)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}, gn
