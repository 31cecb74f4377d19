"""Serving substrate: one-token serve_step + slot-based batched server."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models import model as M


def serve_step(params, cache, tokens: jax.Array, pos: jax.Array, *,
               cfg, rt: M.Runtime, temperature: float = 0.0,
               rng: jax.Array | None = None):
    """One decode step for a batch of request slots.

    tokens: [B] int32 current token per slot; pos: [B] int32 positions.
    Returns (next_tokens [B], logits [B,V], new_cache).
    """
    logits, new_cache = M.decode_step(params, cache, tokens, pos, cfg, rt)
    if temperature > 0.0 and rng is not None:
        nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32), logits, new_cache


def make_serve_step(cfg, rt: M.Runtime, temperature: float = 0.0):
    return functools.partial(serve_step, cfg=cfg, rt=rt,
                             temperature=temperature)


class SlotServer:
    """Minimal continuous-batching server: fixed B slots, per-slot position,
    requests queue in when slots free up. Driven by ``repro.launch.serve``
    (reduced configs, one device)."""

    def __init__(self, params, cfg, rt: M.Runtime, n_slots: int,
                 max_len: int, bos: int = 1):
        self.params, self.cfg, self.rt = params, cfg, rt
        self.n_slots, self.max_len, self.bos = n_slots, max_len, bos
        self.cache = M.init_cache(cfg, n_slots, max_len, jnp.float32,
                                  cross_len=rt.cross_len)
        self.tokens = jnp.full((n_slots,), bos, jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.active = [False] * n_slots
        self.outputs: Dict[int, list] = {}
        self._step = jax.jit(make_serve_step(cfg, rt))
        self._next_req = 0
        self._slot_req: Dict[int, int] = {}

    def submit(self, prompt_token: int) -> int:
        rid = self._next_req
        self._next_req += 1
        for s in range(self.n_slots):
            if not self.active[s]:
                self.active[s] = True
                self.tokens = self.tokens.at[s].set(prompt_token)
                self.pos = self.pos.at[s].set(0)
                # attention masks stale rows by position, but a Mamba
                # layer's conv/SSM state would carry the last request over
                self.cache = jax.tree.map(lambda c: c.at[:, s].set(0),
                                          self.cache)
                self.outputs[rid] = []
                self._slot_req[s] = rid
                return rid
        raise RuntimeError("no free slot")

    def step(self):
        nxt, _, self.cache = self._step(self.params, self.cache,
                                        self.tokens, self.pos)
        self.pos = self.pos + jnp.asarray([1 if a else 0 for a in self.active],
                                          jnp.int32)
        self.tokens = jnp.where(jnp.asarray(self.active), nxt, self.tokens)
        for s in range(self.n_slots):
            if self.active[s]:
                rid = self._slot_req[s]
                self.outputs[rid].append(int(nxt[s]))

    def finish(self, rid: int):
        for s, r in self._slot_req.items():
            if r == rid:
                self.active[s] = False
        return self.outputs.pop(rid)
