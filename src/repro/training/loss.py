"""Next-token cross-entropy loss (+ z-loss + MoE aux)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import model as M


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 1e-4):
    """logits [.., S, V] f32, labels [.., S] int32 (-1 = masked)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels.clip(0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (lse - gold) * mask
    zl = z_loss * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return (nll.sum() + zl.sum()) / denom


def loss_fn(params, batch, cfg, rt: M.Runtime):
    """batch: tokens [B,S], labels [B,S] (+frames for enc-dec)."""
    logits, aux = M.forward(params, batch, cfg, rt)
    with jax.named_scope("head"):
        ce = cross_entropy(logits, batch["labels"])
    total = ce + rt.aux_loss_weight * aux
    return total, {"ce": ce, "moe_aux": aux}
