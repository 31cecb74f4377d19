"""Causal grouped-query attention with rotate-half RoPE: a mixer."""
import math

import jax
import jax.numpy as jnp

from bench.layers import normal

ROLE = "mixer"
SPEC = "attn"
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rope_theta")
SUBKEY = 0
F32 = jnp.float32


def arch_fields(a: dict) -> dict:
    return {"family": "dense", "n_heads": a["num_attention_heads"],
            "n_kv_heads": a["num_key_value_heads"], "d_head": a["head_dim"],
            "rope_theta": a["rope_theta"]}


def init(key, a: dict, dtype) -> dict:
    d, h, kv, dh = (a["hidden_size"], a["num_attention_heads"],
                    a["num_key_value_heads"], a["head_dim"])
    ka = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {"wq": normal(ka[0], (d, h, dh), s, dtype),
            "wk": normal(ka[1], (d, kv, dh), s, dtype),
            "wv": normal(ka[2], (d, kv, dh), s, dtype),
            "wo": normal(ka[3], (h, dh, d), 1.0 / math.sqrt(h * dh), dtype)}


def rope(x, theta):
    """Rotate-half RoPE over the last dim; x [B, S, H, D], positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(p, h, a, mm, q_block=512):
    B, S, _ = h.shape
    H, KV, dh = (a["num_attention_heads"], a["num_key_value_heads"],
                 a["head_dim"])
    q = rope(mm("bsd,dhk->bshk", h, p["wq"]), a["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, p["wk"]), a["rope_theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"])
    k = jnp.repeat(k, H // KV, axis=2)       # query head j reads kv head j//g
    v = jnp.repeat(v, H // KV, axis=2)
    qb = min(q_block, S)
    nb = S // qb

    @jax.checkpoint
    def block(args):
        qc, start = args                      # [B, qb, H, dh]
        sc = mm("bqhd,bkhd->bhqk", qc, k) / math.sqrt(dh)
        qpos = start + jnp.arange(qb)
        keep = jnp.arange(S)[None, :] <= qpos[:, None]
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    qs = jnp.moveaxis(q.reshape(B, nb, qb, H, dh), 1, 0)
    out = jax.lax.map(block, (qs, jnp.arange(nb) * qb))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)
    return mm("bshk,hkd->bsd", out, p["wo"]), None


def matmul_params(a: dict) -> int:
    d, h, kv, dh = (a["hidden_size"], a["num_attention_heads"],
                    a["num_key_value_heads"], a["head_dim"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d


def flops_fwd(a: dict, batch: int, seq: int) -> int:
    """QK^T and PV of causal attention: 2 products of 2*S*S*H*dh each, half
    of it under the mask."""
    return batch * 2 * seq * seq * a["num_attention_heads"] * a["head_dim"]
