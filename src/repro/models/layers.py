"""Pure-JAX model layers shared by all 10 assigned architectures.

Every ``init_*`` returns ``(params, logical_specs)`` where ``logical_specs``
is a pytree of tuples of logical axis names (mapped to mesh axes by
``repro.parallel.sharding``). Every ``apply_*`` is shape-polymorphic and used
for train/prefill (full-sequence) and decode (single-token + cache) paths.

Attention runs the Pallas ``flash_attention`` kernel from ``repro.kernels``
(forward and backward) where it compiles and fits, and otherwise a
*query-chunked* XLA path (memory-safe for 32k prefill without materialising
the full S x S score matrix); ``attn_impl`` pins either (``_attn_path``).
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, AttnSpec

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs       # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                             # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _init(key, shape, scale, dtype):
    if key is None:   # specs-only mode: no allocation (used by logical_specs)
        return jax.ShapeDtypeStruct(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# activation-sharding context: GSPMD propagation from the param shardings
# alone replicates attention heads through scan/map bodies (measured 5.5x
# compute blow-up), so the model inserts explicit constraints when a context
# is set (by forward()/decode_step() from Runtime; off for 1-device tests).
# Tokens: "dp" -> data axes, "tp" -> tensor axis, None -> unsharded.
# ---------------------------------------------------------------------------

_SHARD_CTX: dict | None = None


def set_shard_ctx(ctx: dict | None):
    global _SHARD_CTX
    _SHARD_CTX = ctx


def _cs(x: jax.Array, *axes):
    """Apply with_sharding_constraint if a sharding context is active."""
    if _SHARD_CTX is None:
        return x
    from jax.sharding import PartitionSpec as P
    resolved = tuple(
        _SHARD_CTX["dp"] if a == "dp" else
        (_SHARD_CTX["tp"] if a == "tp" else None)
        for a in axes)
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def _cs_ep(x: jax.Array, *axes):
    """Like _cs but the 'ep' token maps to tp only when EP is active."""
    if _SHARD_CTX is None:
        return x
    from jax.sharding import PartitionSpec as P
    resolved = tuple(
        _SHARD_CTX["tp"] if (a == "ep" and _SHARD_CTX.get("ep")) else
        (_SHARD_CTX["dp"] if a == "dp" else
         (_SHARD_CTX["tp"] if a == "tp" else None))
        for a in axes)
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def _split(key, n):
    return jax.random.split(key, n) if key is not None else [None] * n


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, spec: AttnSpec, dtype) -> Tuple[Params, Params]:
    d, h, kv, dh = cfg.d_model, cfg.eff_heads, cfg.n_kv_heads, cfg.d_head
    ks = _split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": _init(ks[0], (d, h, dh), s, dtype),
        "wk": _init(ks[1], (d, kv, dh), s, dtype),
        "wv": _init(ks[2], (d, kv, dh), s, dtype),
        "wo": _init(ks[3], (h, dh, d), 1.0 / math.sqrt(h * dh), dtype),
    }
    l = {
        "wq": ("embed", "heads", "head"),
        "wk": ("embed", "kv_heads", "head"),
        "wv": ("embed", "kv_heads", "head"),
        "wo": ("heads", "head", "embed"),
    }
    if spec.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), dtype)
        p["k_norm"] = jnp.zeros((dh,), dtype)
        l["q_norm"] = (None,)
        l["k_norm"] = (None,)
    return p, l


def _attn_mask(q_pos, k_pos, window: Optional[int]):
    """causal (+ optional sliding window) mask: [*, Sq, Sk] bool (True=keep)."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


# attention call sites traced by the path they took (``tally_attn_paths``)
_ATTN_PATHS: dict | None = None


@contextlib.contextmanager
def tally_attn_paths():
    """Yields ``{"kernel": n, "xla": m}``: the attention call sites traced
    inside the block, by the path ``apply_attention`` chose for each."""
    global _ATTN_PATHS
    prev, _ATTN_PATHS = _ATTN_PATHS, {"kernel": 0, "xla": 0}
    try:
        yield _ATTN_PATHS
    finally:
        _ATTN_PATHS = prev


def _attn_path(attn_impl: str, S: int, dh: int, self_attn: bool) -> str:
    """"kernel" or "xla". ``"auto"`` takes the kernel where it compiles (not
    the Pallas interpreter), where no sharding context is set (GSPMD would
    replicate an unsharded ``pallas_call``), for self-attention whose length
    and head width its blocks tile; both paths compute one function."""
    if attn_impl in ("pallas", "xla"):
        return "kernel" if attn_impl == "pallas" else "xla"
    assert attn_impl == "auto", attn_impl
    from repro.kernels import flash_attention as fa, ops as kops
    ok = (not kops.interpret_mode() and _SHARD_CTX is None and self_attn
          and fa.fits(S, dh))
    return "kernel" if ok else "xla"


def apply_attention(p: Params, x: jax.Array, spec: AttnSpec, cfg: ArchConfig,
                    positions: jax.Array, *, kv_override: Optional[Tuple] = None,
                    kv_positions: Optional[jax.Array] = None,
                    causal: bool = True, q_chunk: int = 1024,
                    attn_impl: str = "auto") -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross).

    x: [B, S, d].  kv_override: (k_src, v_src) already projected (cross-attn
    passes encoder memory through wk/wv itself via this fn with x_kv).
    The kernel masks by sequence index: it assumes positions 0..S-1, which
    is what ``forward`` and the encoder pass.
    """
    B, S, _ = x.shape
    h, dh = cfg.eff_heads, cfg.d_head
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        k_pos = positions
    else:
        xkv, k_pos = kv_override
        k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    if spec.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if causal or kv_override is None:   # self-attn gets RoPE; cross does not
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, k_pos, cfg.rope_theta)
    path = _attn_path(attn_impl, S, dh, kv_override is None)
    if _ATTN_PATHS is not None:
        _ATTN_PATHS[path] += 1
    if path == "kernel":   # GQA inside the kernel: k, v stay at kv heads
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal,
                                   window=spec.window, softcap=spec.softcap)
    else:
        out = _xla_attn(q, k, v, spec, positions, k_pos, causal, q_chunk)
    if h != cfg.n_heads:   # zero TP-padded heads (blocks grads into wo pad)
        out = out * (jnp.arange(h) < cfg.n_heads)[None, None, :, None
                                                  ].astype(out.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _xla_attn(q, k, v, spec: AttnSpec, positions, k_pos, causal: bool,
              q_chunk: int) -> jax.Array:
    """The query-chunked XLA path: [B, S, h, dh] out."""
    B, S, h, dh = q.shape
    Sk = k.shape[1]
    # GQA via repeat: kv heads -> q heads BEFORE the score einsum. The repeat
    # keeps the head dim shardable over "model" (a reshape h->(kv,groups)
    # would break GSPMD head sharding and replicate attention compute).
    groups = h // k.shape[2]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    q = _cs(q, "dp", None, "tp", None)
    k = _cs(k, "dp", None, "tp", None)
    v = _cs(v, "dp", None, "tp", None)

    scale = 1.0 / math.sqrt(dh)
    n_chunks = max(1, S // q_chunk) if S % q_chunk == 0 else 1
    qs = q.reshape(B, n_chunks, S // n_chunks, h, dh)
    pos_b = jnp.broadcast_to(positions, (B, S)) if positions.ndim == 2 \
        else jnp.broadcast_to(positions[None, :], (B, S))
    kpos_b = jnp.broadcast_to(k_pos, (B, Sk)) if k_pos.ndim == 2 \
        else jnp.broadcast_to(k_pos[None, :], (B, Sk))
    qpos = pos_b.reshape(B, n_chunks, S // n_chunks)

    def one_chunk(args):
        qc, qp = args   # [B, Sq, h, dh], [B, Sq]
        sc = jnp.einsum("bqhd,bshd->bhqs", qc.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
        sc = _cs(sc, "dp", "tp", None, None)
        if spec.softcap is not None:
            sc = spec.softcap * jnp.tanh(sc / spec.softcap)
        if causal:
            m = _attn_mask(qp, kpos_b, spec.window)
        else:
            m = jnp.ones((B, qc.shape[1], Sk), bool)
        sc = jnp.where(m[:, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", pr.astype(v.dtype), v)

    out = lax.map(one_chunk, (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qpos, 1, 0)))
    return _cs(jnp.moveaxis(out, 0, 1).reshape(B, S, h, dh),
               "dp", None, "tp", None)


def apply_attention_decode(p: Params, x: jax.Array, spec: AttnSpec,
                           cfg: ArchConfig, cache_k: jax.Array,
                           cache_v: jax.Array, pos: jax.Array,
                           *, cross: bool = False,
                           cross_len: Optional[jax.Array] = None):
    """One-token decode. x: [B, 1, d]; cache_k/v: [B, S, kv, dh]; pos: [B].

    Returns (out [B,1,d], new_cache_k, new_cache_v). For cross-attn the cache
    holds the (pre-projected) encoder memory K/V and is not updated.
    """
    B, _, _ = x.shape
    h, kv, dh = cfg.eff_heads, cfg.n_kv_heads, cfg.d_head
    S = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if spec.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if not cross:
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if spec.qk_norm:
            k_new = rms_norm(k_new, p["k_norm"], cfg.norm_eps)
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)
        # insert at position pos (ring-buffer for windowed layers handled by mask)
        oh = jax.nn.one_hot(pos % S, S, dtype=cache_k.dtype)       # [B,S]
        cache_k = cache_k * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k_new
        cache_v = cache_v * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v_new
    # Decode keeps the cache at kv heads with the SEQUENCE dim sharded (SP);
    # q is tiny, so its heads are gathered and grouped instead of repeating
    # K/V to h heads (the repeat materialised a 2x cache copy — measured
    # +11GB/chip on gemma2 decode_32k).
    groups = h // kv
    kk = _cs(cache_k, "dp", "tp", None, None)   # SP: cache seq stays sharded
    vv = _cs(cache_v, "dp", "tp", None, None)
    qh = _cs(q, "dp", None, None, None)[:, 0].reshape(B, kv, groups, dh)
    sc = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                    kk.astype(jnp.float32)) / math.sqrt(dh)
    sc = _cs(sc, "dp", None, None, "tp")
    if spec.softcap is not None:
        sc = spec.softcap * jnp.tanh(sc / spec.softcap)
    kpos = jnp.arange(S)[None, :]
    if cross:
        valid = kpos < (cross_len[:, None] if cross_len is not None
                        else jnp.full((B, 1), S))
    else:
        valid = kpos <= pos[:, None]
        if spec.window is not None:
            valid &= kpos > (pos[:, None] - spec.window)
    sc = jnp.where(valid[:, None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", pr.astype(vv.dtype), vv)
    out = out.reshape(B, h, dh)
    if h != cfg.n_heads:
        out = out * (jnp.arange(h) < cfg.n_heads)[None, :, None
                                                  ].astype(out.dtype)
    out = out[:, None]                                      # [B, 1, h, dh]
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


# ---------------------------------------------------------------------------
# dense gated MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, dtype) -> Tuple[Params, Params]:
    d, f = cfg.d_model, cfg.d_ff
    ks = _split(key, 3)
    p = {
        "w1": _init(ks[0], (d, f), 1 / math.sqrt(d), dtype),
        "w3": _init(ks[1], (d, f), 1 / math.sqrt(d), dtype),
        "w2": _init(ks[2], (f, d), 1 / math.sqrt(f), dtype),
    }
    l = {"w1": ("embed", "mlp"), "w3": ("embed", "mlp"), "w2": ("mlp", "embed")}
    return p, l


def apply_mlp(p: Params, x: jax.Array, act: str) -> jax.Array:
    g = _act(act)(_cs(jnp.einsum("bsd,df->bsf", x, p["w1"]), "dp", None, "tp"))
    u = _cs(jnp.einsum("bsd,df->bsf", x, p["w3"]), "dp", None, "tp")
    return jnp.einsum("bsf,fd->bsd", g * u, p["w2"])


# ---------------------------------------------------------------------------
# MoE — top-k routing with capacity, gather/scatter dispatch (no O(T·E·C)
# one-hot einsums; see DESIGN.md).  TPU-idiomatic: sort-based slotting.
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig, dtype) -> Tuple[Params, Params]:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    sp = cfg.moe.expert_split
    E2, f2 = E * sp, f // sp
    ks = _split(key, 4)
    p = {
        "router": _init(ks[0], (d, E), 1 / math.sqrt(d), jnp.float32),
        "w1": _init(ks[1], (E2, d, f2), 1 / math.sqrt(d), dtype),
        "w3": _init(ks[2], (E2, d, f2), 1 / math.sqrt(d), dtype),
        "w2": _init(ks[3], (E2, f2, d), 1 / math.sqrt(f), dtype),
    }
    l = {
        "router": ("embed", None),
        "w1": ("expert", "embed", "expert_mlp"),
        "w3": ("expert", "embed", "expert_mlp"),
        "w2": ("expert", "expert_mlp", "embed"),
    }
    return p, l


def moe_capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(m.capacity_factor * n_tokens * m.top_k / m.n_experts))
    # multiple of 32 so the capacity dim shards evenly over a 16-way axis
    return max(32, -(-c // 32) * 32)


MOE_TOKEN_CHUNK = 65_536


def apply_moe(p: Params, x: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B,S,d], aux_loss scalar).

    Token-chunked: the gather-based dispatch all-gathers the token block, so
    blocks are capped at MOE_TOKEN_CHUNK tokens (capacity is per-block —
    equivalent to microbatching the router)."""
    B, S, d = x.shape
    T = B * S
    if T > MOE_TOKEN_CHUNK and T % MOE_TOKEN_CHUNK == 0:
        n = T // MOE_TOKEN_CHUNK
        xc = x.reshape(n, -1, d)                # [n_chunks, chunk_tokens, d]
        outs, auxes = lax.map(lambda xi: _moe_block(p, xi[None], cfg), xc)
        return outs.reshape(B, S, d), jnp.mean(auxes)
    return _moe_block(p, x, cfg)


def _moe_block(p: Params, x: jax.Array, cfg: ArchConfig
               ) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    m = cfg.moe
    sp = m.expert_split
    T, E, K = B * S, m.n_experts * sp, m.top_k * sp
    C = moe_capacity(B * S, cfg)
    xf = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, m.top_k)                # [T,k]
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-9)
    if sp > 1:
        # expert e -> split shards e*sp..e*sp+sp-1 (outputs sum in combine)
        top_e = (top_e[..., None] * sp
                 + jnp.arange(sp)[None, None, :]).reshape(T, K)
        top_w = jnp.repeat(top_w, sp, axis=-1)

    # ---- slotting: rank of each assignment within its expert ----
    flat_e = top_e.reshape(-1)                              # [T*K]
    order = jnp.argsort(flat_e, stable=True)                # token-order within expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                 # tokens per expert
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * K) - starts[sorted_e]             # pos within expert
    keep = rank < C                                         # dropped beyond capacity
    slot = sorted_e * C + jnp.where(keep, rank, 0)          # [T*K]
    tok = order // K                                        # source token id

    slot_tok = jnp.zeros((E * C,), jnp.int32).at[slot].set(
        jnp.where(keep, tok, 0), mode="drop")
    slot_valid = jnp.zeros((E * C,), jnp.bool_).at[slot].set(keep, mode="drop")
    slot_w = jnp.zeros((E * C,), jnp.float32).at[slot].set(
        jnp.where(keep, top_w.reshape(-1)[order], 0.0), mode="drop")

    xe = jnp.take(xf, slot_tok, axis=0)                     # [E*C, d]  (gather)
    xe = jnp.where(slot_valid[:, None], xe, 0).reshape(E, C, d)
    ep = _SHARD_CTX is not None and _SHARD_CTX.get("ep")
    # EP: experts -> model AND capacity -> data (leaving C unsharded
    # replicates each expert's compute across the whole data axis — measured
    # 16x useful-flops waste on grok/arctic); non-EP: capacity -> data with
    # the expert ffn dim -> model.
    xe = _cs(xe, "tp" if ep else None, "dp", None)
    hidden_spec = ("tp", "dp", None) if ep else (None, "dp", "tp")
    g = _act(cfg.act)(_cs(jnp.einsum("ecd,edf->ecf", xe, p["w1"]), *hidden_spec))
    u = _cs(jnp.einsum("ecd,edf->ecf", xe, p["w3"]), *hidden_spec)
    ye = _cs(jnp.einsum("ecf,efd->ecd", g * u, p["w2"]),
             "tp" if ep else None, "dp", None).reshape(E * C, d)

    out = jnp.zeros((T, d), ye.dtype).at[slot_tok].add(
        ye * (slot_w * slot_valid)[:, None].astype(ye.dtype), mode="drop")

    # load-balance aux loss (Switch-style, on the un-split router)
    frac_tokens = jnp.bincount(flat_e // sp if sp > 1 else flat_e,
                               length=m.n_experts).astype(jnp.float32) / (T * K)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(frac_tokens * mean_prob)
    return out.reshape(B, S, d), aux


def apply_moe_decode(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Decode-path MoE for tiny T (B tokens): dense top-k gather of experts."""
    B, S, d = x.shape
    m = cfg.moe
    xf = x.reshape(B * S, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, m.top_k)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-9)
    w1 = jnp.take(p["w1"], top_e, axis=0)    # [T,K,d,f]
    w3 = jnp.take(p["w3"], top_e, axis=0)
    w2 = jnp.take(p["w2"], top_e, axis=0)    # [T,K,f,d]
    g = _act(cfg.act)(jnp.einsum("td,tkdf->tkf", xf, w1))
    u = jnp.einsum("td,tkdf->tkf", xf, w3)
    y = jnp.einsum("tkf,tkfd->tkd", g * u, w2)
    out = jnp.einsum("tkd,tk->td", y, top_w.astype(y.dtype))
    return out.reshape(B, S, d)


# ---------------------------------------------------------------------------
# Mamba-1 mixer (conv + selective scan)
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ArchConfig, dtype) -> Tuple[Params, Params]:
    d = cfg.d_model
    ms = cfg.mamba
    di, ds, dc, dr = cfg.d_inner, ms.d_state, ms.d_conv, cfg.dt_rank
    ks = _split(key, 6)
    p = {
        "in_proj": _init(ks[0], (d, 2 * di), 1 / math.sqrt(d), dtype),
        "conv_w": _init(ks[1], (dc, di), 1 / math.sqrt(dc), dtype),
        "x_proj": _init(ks[2], (di, dr + 2 * ds), 1 / math.sqrt(di), dtype),
        "dt_proj": _init(ks[3], (dr, di), 1 / math.sqrt(dr), dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32) + jnp.log(jnp.expm1(0.01)),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))).astype(jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _init(ks[5], (di, d), 1 / math.sqrt(di), dtype),
    }
    l = {
        "in_proj": ("embed", "inner"),
        "conv_w": (None, "inner"),
        "x_proj": ("inner", None),
        "dt_proj": (None, "inner"),
        "dt_bias": ("inner",),
        "A_log": ("inner", None),
        "D": ("inner",),
        "out_proj": ("inner", "embed"),
    }
    return p, l


def _mamba_pre(p: Params, x: jax.Array, cfg: ArchConfig,
               conv_state: Optional[jax.Array] = None):
    """Shared projection + causal depthwise conv. x: [B,S,d].

    Returns (u [B,S,di] post-conv+silu, z gate [B,S,di], dt, Bc, Cc, new_conv_tail).
    """
    ms = cfg.mamba
    di, ds, dc, dr = cfg.d_inner, ms.d_state, ms.d_conv, cfg.dt_rank
    xz = _cs(jnp.einsum("bsd,de->bse", x, p["in_proj"]), "dp", None, "tp")
    u, z = jnp.split(xz, 2, axis=-1)                       # [B,S,di] each
    # causal depthwise conv via shifted adds (k = d_conv, tiny)
    if conv_state is None:
        pad = jnp.zeros((u.shape[0], dc - 1, di), u.dtype)
    else:
        pad = conv_state.astype(u.dtype)                   # [B, dc-1, di]
    up = jnp.concatenate([pad, u], axis=1)                 # [B, S+dc-1, di]
    conv = sum(up[:, i:i + u.shape[1], :] * p["conv_w"][i][None, None]
               for i in range(dc))
    new_tail = up[:, up.shape[1] - (dc - 1):, :]
    u = jax.nn.silu(conv)
    dbc = jnp.einsum("bsi,ie->bse", u, p["x_proj"])
    dt, Bc, Cc = jnp.split(dbc, [dr, dr + ds], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,ri->bsi", dt, p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"])
    return u, z, dt, Bc, Cc, new_tail


MAMBA_CHUNK = 256


def apply_mamba(p: Params, x: jax.Array, cfg: ArchConfig,
                scan_impl: str = "chunked") -> jax.Array:
    """Full-sequence selective scan. x: [B,S,d] -> [B,S,d].

    Default path is CHUNKED+FUSED: a sequential lax.scan over
    S/MAMBA_CHUNK chunks carrying the SSM state; the [*, chunk, di, ds]
    expansion a_t=exp(dt A), b_t=dt*B*u AND the contraction y=h.C happen
    INSIDE the chunk body, so no [B,S,di,ds] tensor ever exists — only
    [B,chunk,di,ds] working sets (the same blocking the Pallas
    selective_scan kernel keeps in VMEM). §Perf P5/P8.
    """
    ms = cfg.mamba
    S = x.shape[1]
    u, z, dt, Bc, Cc, _ = _mamba_pre(p, x, cfg)
    A = -jnp.exp(p["A_log"])                               # [di, ds]

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    def expand(u_c, dt_c, B_c):
        a = jnp.exp(dt_c[..., None] * A)                   # [.., di, ds] f32
        a = _cs(a, "dp", None, "tp", None)
        b = (dt_c * u_c.astype(jnp.float32))[..., None] * \
            B_c.astype(jnp.float32)[:, :, None, :]
        return a, _cs(b, "dp", None, "tp", None)

    if scan_impl == "pallas":
        from repro.kernels import ops as kops
        a, b = expand(u, dt, Bc)
        h = kops.selective_scan(a, b)
        y = jnp.einsum("bsin,bsn->bsi", h, Cc.astype(jnp.float32))
    elif scan_impl == "chunked" and S > MAMBA_CHUNK and S % MAMBA_CHUNK == 0:
        n = S // MAMBA_CHUNK
        Bsz, di, ds = x.shape[0], cfg.d_inner, ms.d_state

        def to_chunks(t):
            return jnp.moveaxis(
                t.reshape(Bsz, n, MAMBA_CHUNK, t.shape[-1]), 1, 0)

        def chunk_step(h0, args):
            u_c, dt_c, B_c, C_c = args        # [B, chunk, ...]
            a, b = expand(u_c, dt_c, B_c)
            cum_a, hin = lax.associative_scan(combine, (a, b), axis=1)
            hi = hin + cum_a * h0[:, None]    # fold in carried state
            y_c = jnp.einsum("bsin,bsn->bsi", hi, C_c.astype(jnp.float32))
            return hi[:, -1], y_c

        _, yc = lax.scan(chunk_step, jnp.zeros((Bsz, di, ds), jnp.float32),
                         (to_chunks(u), to_chunks(dt), to_chunks(Bc),
                          to_chunks(Cc)))
        y = jnp.moveaxis(yc, 0, 1).reshape(Bsz, S, di)
    else:
        a, b = expand(u, dt, Bc)
        _, h = lax.associative_scan(combine, (a, b), axis=1)
        y = jnp.einsum("bsin,bsn->bsi", h, Cc.astype(jnp.float32))
    y = y + u.astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bsi,id->bsd", y, p["out_proj"])


def apply_mamba_decode(p: Params, x: jax.Array, cfg: ArchConfig,
                       conv_state: jax.Array, ssm_state: jax.Array):
    """One-token step. x: [B,1,d]; conv_state [B,dc-1,di]; ssm_state [B,di,ds]."""
    u, z, dt, Bc, Cc, new_tail = _mamba_pre(p, x, cfg, conv_state=conv_state)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[:, 0, :, None] * A)                     # [B,di,ds]
    b = (dt[:, 0] * u[:, 0].astype(jnp.float32))[..., None] * \
        Bc[:, 0].astype(jnp.float32)[:, None, :]
    h = a * ssm_state + b
    y = jnp.einsum("bin,bn->bi", h, Cc[:, 0].astype(jnp.float32))
    y = y + u[:, 0].astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bi,id->bd", y, p["out_proj"])[:, None, :]
    return out, new_tail, h
