"""Serving driver: batched decode over any assigned architecture.

Reduced configs only, on one device (CPU or one chip):
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b \
        --requests 8 --tokens 16
No full-width serving path exists yet. ``serve_step`` is also what
``launch/dryrun.py`` lowers, on forced CPU devices, for the production
mesh's decode cells; nothing has run it sharded on a chip.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.serving import SlotServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    full = get_config(args.arch)
    cfg = reduced(full, d_model=args.d_model,
                  n_layers=2 * len(full.block) if len(full.block) == 1
                  else len(full.block))
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rt = M.Runtime(q_chunk=16, cross_len=16)
    server = SlotServer(params, cfg, rt, n_slots=args.slots,
                        max_len=args.max_len)

    t0 = time.time()
    pending = list(range(args.requests))
    active, done = {}, {}
    while pending or active:
        while pending and len(active) < server.n_slots:
            req = pending.pop(0)
            active[server.submit(prompt_token=req + 2)] = req
        server.step()
        for rid in list(active):
            if len(server.outputs.get(rid, [])) >= args.tokens:
                done[active.pop(rid)] = server.finish(rid)
    dt = time.time() - t0
    total = args.requests * args.tokens
    print(f"served {args.requests} requests x {args.tokens} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s, {args.slots} slots, "
          f"arch={args.arch} reduced)")


if __name__ == "__main__":
    main()
