"""Model assembly: decoder LM (all archs) + encoder-decoder (seamless).

Layers repeat as *blocks* (cfg.block pattern) stacked with ``lax.scan`` so the
HLO contains one block body regardless of depth — critical for fast GSPMD
compiles at 256/512 devices. Params for in-block position ``i`` live in
``params["blocks"][i]`` with every leaf stacked over ``n_blocks`` on axis 0.

Public API:
    init_params(key, cfg, dtype)        -> params
    logical_specs(cfg)                  -> pytree of logical-axis tuples
    forward(params, batch, cfg, rt)     -> logits (train/prefill; enc-dec aware)
    init_cache(cfg, B, S, dtype, ...)   -> decode cache pytree (+ specs)
    decode_step(params, cache, tokens, pos, cfg, rt) -> (logits, new_cache)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import layers as L

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Static runtime knobs (hashable; passed as static arg to jit)."""
    attn_impl: str = "xla"        # "xla" | "pallas"
    scan_impl: str = "chunked"    # mamba scan: "chunked" | "assoc" | "pallas"
    remat: str = "block"          # "none" | "block" | "full"
    q_chunk: int = 1024
    aux_loss_weight: float = 0.01
    cross_len: int = 4096         # encoder memory length for enc-dec decode
    # activation sharding (GSPMD propagation alone replicates heads through
    # scan bodies — see layers._cs). Empty dp_axes => batch unsharded.
    shard_activations: bool = False
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    ep: bool = True

    def shard_ctx(self):
        if not self.shard_activations:
            return None
        return {"dp": self.dp_axes if self.dp_axes else None,
                "tp": self.tp_axis or None, "ep": self.ep}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, spec: LayerSpec, dtype,
                with_cross: bool) -> Tuple[Params, Params]:
    ks = L._split(key, 8)
    p: Params = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    l: Params = {"norm1": (None,)}
    if spec.mixer == "attn":
        p["attn"], l["attn"] = L.init_attention(ks[0], cfg, spec.attn, dtype)
    else:
        p["mamba"], l["mamba"] = L.init_mamba(ks[0], cfg, dtype)
    if with_cross:
        p["norm_cross"] = jnp.zeros((cfg.d_model,), dtype)
        l["norm_cross"] = (None,)
        p["cross"], l["cross"] = L.init_attention(ks[1], cfg, spec.attn, dtype)
    if spec.ffn != "none":
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        l["norm2"] = (None,)
    if spec.ffn == "dense":
        p["mlp"], l["mlp"] = L.init_mlp(ks[2], cfg, dtype)
    elif spec.ffn == "moe":
        p["moe"], l["moe"] = L.init_moe(ks[3], cfg, dtype)
    elif spec.ffn == "moe_dense":
        p["moe"], l["moe"] = L.init_moe(ks[3], cfg, dtype)
        p["mlp"], l["mlp"] = L.init_mlp(ks[4], cfg, dtype)
    return p, l


def _stacked_layer_init(key, cfg, spec, dtype, n, with_cross=False):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _init_layer(k, cfg, spec, dtype, with_cross)[0])(keys)


def init_params(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 8 + len(cfg.block))
    d, V = cfg.d_model, cfg.eff_vocab
    p: Params = {
        "embed": (jax.random.normal(ks[0], (V, d), jnp.float32)).astype(dtype),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(ks[1], (d, V), jnp.float32)
                        / math.sqrt(d)).astype(dtype)
    p["blocks"] = [
        _stacked_layer_init(ks[8 + i], cfg, spec, dtype, cfg.n_blocks,
                            with_cross=cfg.enc_dec)
        for i, spec in enumerate(cfg.block)
    ]
    if cfg.enc_dec:
        enc_spec = LayerSpec(mixer="attn", ffn="dense")
        p["encoder"] = {
            "layers": _stacked_layer_init(ks[2], cfg, enc_spec, dtype,
                                          cfg.n_enc_layers),
            "final_norm": jnp.zeros((d,), dtype),
        }
    return p


def logical_specs(cfg: ArchConfig) -> Params:
    """Pytree matching init_params with logical-axis tuples at leaves."""
    def _init_layer_specs(spec, with_cross):
        # key=None puts the init fns in specs-only mode: large tensors come
        # back as ShapeDtypeStructs, so nothing real is allocated even for
        # the 480B config.
        _, l = _init_layer(None, cfg, spec, jnp.bfloat16, with_cross)
        return l

    out: Params = {"embed": ("vocab", "embed"), "final_norm": (None,)}
    if not cfg.tie_embeddings:
        out["unembed"] = ("embed", "vocab")

    def stack(l):   # scanned leaves gain a leading "layers" axis
        return jax.tree.map(lambda ax: ("layers",) + tuple(ax), l,
                            is_leaf=lambda x: isinstance(x, tuple))

    out["blocks"] = [stack(_init_layer_specs(spec, cfg.enc_dec))
                     for spec in cfg.block]
    if cfg.enc_dec:
        enc_spec = LayerSpec(mixer="attn", ffn="dense")
        out["encoder"] = {
            "layers": stack(_init_layer_specs(enc_spec, False)),
            "final_norm": (None,),
        }
    return out


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_layer(p: Params, spec: LayerSpec, x, positions, cfg, rt: Runtime,
                 memory=None, mem_positions=None):
    aux = jnp.zeros((), jnp.float32)
    x = L._cs(x, "dp", None, None)
    with jax.named_scope("norm"):
        h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    with jax.named_scope("mixer"):
        if spec.mixer == "attn":
            mix = L.apply_attention(p["attn"], h, spec.attn, cfg, positions,
                                    q_chunk=rt.q_chunk,
                                    attn_impl=rt.attn_impl)
        else:
            mix = L.apply_mamba(p["mamba"], h, cfg, scan_impl=rt.scan_impl)
    x = x + mix
    if memory is not None:
        with jax.named_scope("norm"):
            h = L.rms_norm(x, p["norm_cross"], cfg.norm_eps)
        with jax.named_scope("mixer"):
            cross = L.apply_attention(
                p["cross"], h, spec.attn, cfg, positions,
                kv_override=(memory, mem_positions), causal=False,
                q_chunk=rt.q_chunk, attn_impl="xla")
        x = x + cross
    if spec.ffn != "none":
        with jax.named_scope("norm"):
            h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            f = jnp.zeros_like(x)
            if spec.ffn in ("moe", "moe_dense"):
                mo, a = L.apply_moe(p["moe"], h, cfg)
                f = f + mo
                aux = aux + a
            if spec.ffn in ("dense", "moe_dense"):
                f = f + L.apply_mlp(p["mlp"], h, cfg.act)
        x = x + f
    return x, aux


def _block_fn(block_params, x, positions, cfg, rt, memory, mem_positions):
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(cfg.block):
        x, a = _apply_layer(block_params[i], spec, x, positions, cfg, rt,
                            memory=memory if cfg.enc_dec else None,
                            mem_positions=mem_positions)
        aux = aux + a
    return x, aux


def _run_blocks(params, x, positions, cfg, rt, memory=None, mem_positions=None):
    def body(carry, xs):
        x, aux = carry
        x, a = _block_fn(xs, x, positions, cfg, rt, memory, mem_positions)
        return (x, aux + a), None

    body_fn = body
    if rt.remat in ("block", "full"):
        policy = (jax.checkpoint_policies.nothing_saveable if rt.remat == "full"
                  else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        body_fn = jax.checkpoint(body, policy=policy, prevent_cse=False)
    with jax.named_scope("blocks"):
        (x, aux), _ = lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               tuple(params["blocks"]))
    return x, aux


def _embed(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _logits(params, x, cfg):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.eff_vocab != cfg.vocab:   # mask TP-padded vocab rows
        logits = jnp.where(jnp.arange(cfg.eff_vocab) < cfg.vocab,
                           logits, -1e30)
    return logits


def _encode(params, frames, cfg, rt):
    """frames: [B, Ss, d] precomputed frontend embeddings (stub frontend).

    Bidirectional self-attention encoder, scanned over layers.
    """
    Ss = frames.shape[1]
    positions = jnp.arange(Ss)[None, :]
    enc_spec = LayerSpec(mixer="attn", ffn="dense")

    def enc_layer(x, lp):
        h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
        mix = L.apply_attention(lp["attn"], h, enc_spec.attn, cfg, positions,
                                causal=False, q_chunk=rt.q_chunk)
        x = x + mix
        h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + L.apply_mlp(lp["mlp"], h, cfg.act)
        return x, None

    enc_fn = (jax.checkpoint(enc_layer, prevent_cse=False)
              if rt.remat != "none" else enc_layer)
    x, _ = lax.scan(enc_fn, frames, params["encoder"]["layers"])
    return L.rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps), positions


def forward(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            rt: Runtime = Runtime()) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B,S,V], moe_aux scalar).

    batch: {"tokens": [B,S] int32}  (+ "frames": [B,Ss,d] for enc-dec).
    """
    L.set_shard_ctx(rt.shard_ctx())
    try:
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.arange(S)[None, :]
        with jax.named_scope("embed"):
            x = L._cs(_embed(params, tokens, cfg), "dp", None, None)
        memory = mem_pos = None
        if cfg.enc_dec:
            memory, mem_pos = _encode(params, batch["frames"].astype(x.dtype),
                                      cfg, rt)
        x, aux = _run_blocks(params, x, positions, cfg, rt, memory, mem_pos)
        with jax.named_scope("head"):
            return L._cs(_logits(params, x, cfg), "dp", None, "tp"), aux
    finally:
        L.set_shard_ctx(None)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, B: int, S: int, dtype=jnp.bfloat16,
               cross_len: int = 4096):
    """Decode cache: per in-block position, stacked over n_blocks (axis 0)."""
    n = cfg.n_blocks
    cache = []
    for spec in cfg.block:
        if spec.mixer == "attn":
            c = {"k": jnp.zeros((n, B, S, cfg.n_kv_heads, cfg.d_head), dtype),
                 "v": jnp.zeros((n, B, S, cfg.n_kv_heads, cfg.d_head), dtype)}
        else:
            ms = cfg.mamba
            c = {"conv": jnp.zeros((n, B, ms.d_conv - 1, cfg.d_inner), dtype),
                 "ssm": jnp.zeros((n, B, cfg.d_inner, ms.d_state), jnp.float32)}
        if cfg.enc_dec:
            c["xk"] = jnp.zeros((n, B, cross_len, cfg.n_kv_heads, cfg.d_head), dtype)
            c["xv"] = jnp.zeros((n, B, cross_len, cfg.n_kv_heads, cfg.d_head), dtype)
        cache.append(c)
    return cache


def cache_logical_specs(cfg: ArchConfig):
    """Sharding: batch->data, kv seq->model (SP), mamba inner->model."""
    specs = []
    for spec in cfg.block:
        if spec.mixer == "attn":
            c = {"k": ("layers", "batch", "kv_seq", None, None),
                 "v": ("layers", "batch", "kv_seq", None, None)}
        else:
            c = {"conv": ("layers", "batch", None, "inner"),
                 "ssm": ("layers", "batch", "inner", None)}
        if cfg.enc_dec:
            c["xk"] = ("layers", "batch", "kv_seq", None, None)
            c["xv"] = ("layers", "batch", "kv_seq", None, None)
        specs.append(c)
    return specs


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                cfg: ArchConfig, rt: Runtime = Runtime()):
    """One decode step. tokens: [B] int32; pos: [B] current positions.

    Returns (logits [B,V], new_cache).
    """
    L.set_shard_ctx(rt.shard_ctx())
    try:
        return _decode_step_inner(params, cache, tokens, pos, cfg, rt)
    finally:
        L.set_shard_ctx(None)


def _decode_step_inner(params, cache, tokens, pos, cfg, rt):
    x = _embed(params, tokens[:, None], cfg)      # [B,1,d]

    def body(x, xs):
        new_cache = []
        x = L._cs(x, "dp", None, None)
        for i, spec in enumerate(cfg.block):
            lp, c = xs[0][i], xs[1][i]
            h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
            if spec.mixer == "attn":
                mix, nk, nv = L.apply_attention_decode(
                    lp["attn"], h, spec.attn, cfg, c["k"], c["v"], pos)
                nc = {"k": nk, "v": nv}
            else:
                mix, nconv, nssm = L.apply_mamba_decode(
                    lp["mamba"], h, cfg, c["conv"], c["ssm"])
                nc = {"conv": nconv, "ssm": nssm}
            x = x + mix
            if cfg.enc_dec:
                h = L.rms_norm(x, lp["norm_cross"], cfg.norm_eps)
                cross, _, _ = L.apply_attention_decode(
                    lp["cross"], h, spec.attn, cfg, c["xk"], c["xv"], pos,
                    cross=True)
                x = x + cross
                nc["xk"], nc["xv"] = c["xk"], c["xv"]
            if spec.ffn != "none":
                h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
                f = jnp.zeros_like(x)
                if spec.ffn in ("moe", "moe_dense"):
                    mo, _ = L.apply_moe(lp["moe"], h, cfg)
                    f = f + mo
                if spec.ffn in ("dense", "moe_dense"):
                    f = f + L.apply_mlp(lp["mlp"], h, cfg.act)
                x = x + f
            new_cache.append(nc)
        return x, tuple(new_cache)

    x, new_cache = lax.scan(body, x, (tuple(params["blocks"]), tuple(cache)))
    logits = _logits(params, x, cfg)[:, 0, :]
    return logits, list(new_cache)
