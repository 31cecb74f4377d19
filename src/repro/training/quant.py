"""Shape-preserving int8 quantization for optimizer moments and gradient
compression.

``QTensor`` keeps the int8 payload in the ORIGINAL parameter shape with one
f32 scale per last-dim row (shape[:-1] + (1,)). Shape preservation is the
point: the quantized buffers inherit the parameter's sharding unchanged, so
no reshape-induced resharding/all-gather appears in the update (a flat
[rows, 256] layout measured +3.8TB/chip of temp on arctic-480B from GSPMD
re-sharding the flat<->param reshapes).

Used for (a) int8 AdamW moments (memory-term lever: 1.25B/el vs 2B bf16 /
4B f32) and (b) int8 gradient all-reduce with bounded error.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    q: jax.Array          # int8, original param shape
    scale: jax.Array      # f32, shape[:-1] + (1,)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1])

    @property
    def shape(self):
        return self.q.shape


def is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def quant(x32: jax.Array, like: "QTensor | None" = None) -> QTensor:
    x32 = x32.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0 + 1e-20
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale)


def dequant(t: QTensor) -> jax.Array:
    return t.q.astype(jnp.float32) * t.scale


def qzeros_like(p) -> QTensor:
    shape = p.shape
    return QTensor(jnp.zeros(shape, jnp.int8),
                   jnp.zeros(shape[:-1] + (1,), jnp.float32))
