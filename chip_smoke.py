#!/usr/bin/env python3
"""Smoke test of the main path on one TPU chip.

    python chip_smoke.py

Phases, all in this one process (which holds the chip):
  (a) device check: exits non-zero unless JAX's first device is a TPU;
  (b) kernels: each Pallas kernel, compiled through Mosaic, against its jnp
      oracle in ``kernels/ref.py`` at real widths (internlm2-1.8b attention,
      a B=8 x 4096 decode cache, falcon-mamba-7b's d_inner x d_state scan);
  (c) main path: the LOG.io training feed (``Engine``, thread mode) into the
      full-width internlm2-1.8b train step (``launch/train.run_training``
      with ``presets.ONE_CHIP_TRAIN``), run twice in fresh temporary
      checkpoint directories: A failure-free, B with a pipeline-worker crash
      and a trainer crash in mid-run. B must replay A's losses from the last
      checkpoint and end in a bit-identical state.
Weights and data come from seeds; nothing is downloaded. Every time printed
is a bring-up observation, not a benchmark result. Any failed phase exits
non-zero; only a run in which every phase passed ends with the line
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import hashlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

SEED = 0


def check_device() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] {dev}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev['platform']!r}); nothing was run")
    return dev


def device_memory() -> dict:
    import jax
    return jax.devices()[0].memory_stats()


def _close(name, out, want, rtol, atol) -> bool:
    import numpy as np
    o = np.asarray(out, np.float32)
    w = np.asarray(want, np.float32)
    ok = bool(np.all(np.isfinite(o))) and bool(
        np.allclose(o, w, rtol=rtol, atol=atol))
    print(f"[kernels] {name}: shape {o.shape} max_abs_err "
          f"{float(np.max(np.abs(o - w)))!r} (tolerance rtol={rtol} "
          f"atol={atol}) {'ok' if ok else 'FAIL'}", flush=True)
    return ok


def kernel_phase() -> bool:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref

    if ops.interpret_mode():
        raise RuntimeError("kernels would run in the Pallas interpreter")
    lm = get_config("internlm2-1.8b")
    mamba = get_config("falcon-mamba-7b")
    H, D, S = lm.n_heads, lm.d_head, 4096
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    bf16 = jnp.bfloat16

    def compiled(fn, *args, **kw):
        if "tpu_custom_call" not in fn.lower(*args, **kw).as_text():
            raise RuntimeError(f"{fn.__name__}: no Mosaic kernel")
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        print(f"[kernels] {fn.__name__}: first call (compile + run) "
              f"{time.perf_counter() - t!r} s", flush=True)
        return out

    # one compile per oracle: run eagerly, each jnp op would compile alone
    flash_ref = jax.jit(ref.flash_attention_ref, static_argnames="causal")
    decode_ref = jax.jit(ref.decode_attention_ref)
    scan_ref = jax.jit(ref.selective_scan_ref)

    ok = True
    # flash attention: one internlm2-1.8b layer's heads at a 4096 context
    q, k, v = (jax.random.normal(ks[i], (1, S, H, D), bf16) for i in range(3))
    out = compiled(ops.flash_attention, q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        want = flash_ref(q, k, v, causal=True)
    ok &= _close("flash_attention", out, want, 2e-2, 2e-2)
    del q, k, v, out, want

    # decode attention: 8 slots against a 4096-token cache, ragged lengths
    B = 8
    q = jax.random.normal(ks[3], (B, H, D), bf16)
    k = jax.random.normal(ks[4], (B, S, H, D), bf16)
    v = jax.random.normal(ks[5], (B, S, H, D), bf16)
    lens = jax.random.randint(ks[6], (B,), 1, S + 1, jnp.int32)
    out = compiled(ops.decode_attention, q, k, v, lens)
    with jax.default_matmul_precision("highest"):
        want = decode_ref(q, k, v, lens)
    ok &= _close("decode_attention", out, want, 2e-2, 2e-2)
    del q, k, v, lens, out, want

    # selective scan: falcon-mamba-7b d_inner x d_state, two chunks of 256.
    # The oracle scans the flat [B, S, DI*DS] view (same elementwise
    # recurrence) so that no padded [.., DS=16] copy is needed for it.
    DI, DS, Ss = mamba.d_inner, mamba.mamba.d_state, 512
    a = jax.random.uniform(ks[7], (1, Ss, DI * DS), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[0], (1, Ss, DI * DS), jnp.float32)
    out = compiled(ops.selective_scan, a.reshape(1, Ss, DI, DS),
                   b.reshape(1, Ss, DI, DS), chunk=256)
    out = out.reshape(1, Ss, DI * DS)
    want = scan_ref(a, b)
    ok &= _close("selective_scan", out, want, 1e-5, 1e-5)
    print(f"[kernels] {device_memory()}", flush=True)
    return ok


def state_digest(state) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(state):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _report(tag, out):
    t = out["timings"]
    steady = sorted(t["step_s"][1:]) or t["step_s"]
    print(f"[train {tag}] steps {out['steps']} losses {out['losses']}",
          flush=True)
    print(f"[train {tag}] compile_s {t['compile_s']!r} first_step_s "
          f"{t['step_s'][0]!r} steady_step_s_median "
          f"{steady[len(steady) // 2]!r} save_s {t['save_s']} restore_s "
          f"{t['restore_s']} pipeline failures {out['engine'].failures} "
          f"restarts {out['engine'].restarts}", flush=True)


def train_phase() -> bool:
    from repro.launch.presets import ONE_CHIP_TRAIN
    from repro.launch.train import run_training

    steps, every, kill_worker, kill_trainer = 8, 4, 2, 6
    restored_from = kill_trainer - kill_trainer % every
    print(f"[train] internlm2-1.8b full width, {ONE_CHIP_TRAIN}", flush=True)
    root = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    print(f"[train] checkpoints under {root} "
          f"({shutil.disk_usage(root).free / 2**30:.1f} GiB free)", flush=True)
    try:
        a = run_training(use_reduced=False, steps=steps, ckpt_every=every,
                         ckpt_dir=os.path.join(root, "a"), seed=SEED,
                         log_every=1)
        _report("A", a)
        a_losses, a_failures = a["losses"], a["engine"].failures
        a_digest = state_digest(a["final_state"])
        del a                       # frees A's state on the device
        shutil.rmtree(os.path.join(root, "a"))
        print(f"[train] after A: {device_memory()}", flush=True)

        b = run_training(use_reduced=False, steps=steps, ckpt_every=every,
                         ckpt_dir=os.path.join(root, "b"), seed=SEED,
                         log_every=1, kill_worker_at=kill_worker,
                         kill_trainer_at=kill_trainer)
        _report("B", b)
        b_losses, b_failures = b["losses"], b["engine"].failures
        b_digest = state_digest(b["final_state"])
        del b
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(f"[train] after B: {device_memory()}", flush=True)

    checks = {
        "A ran every step": len(a_losses) == steps,
        "A failure-free": a_failures == 0,
        "losses finite": all(math.isfinite(x) for x in a_losses + b_losses),
        "B pre-crash losses == A": b_losses[:kill_trainer]
        == a_losses[:kill_trainer],
        f"B post-crash losses == A from step {restored_from}":
            b_losses[kill_trainer:] == a_losses[restored_from:],
        "B counted worker + trainer failures": b_failures >= 2,
        "final states bit-identical": a_digest == b_digest,
    }
    for name, ok in checks.items():
        print(f"[train] {name}: {'ok' if ok else 'FAIL'}", flush=True)
    return all(checks.values())


def main() -> int:
    use_compile_cache()
    dev = check_device()
    failed = []
    for name, phase in (("kernels", kernel_phase), ("train", train_phase)):
        t = time.perf_counter()
        try:
            ok = phase()
        except Exception:
            traceback.print_exc()
            ok = False
        print(f"[{name}] {'passed' if ok else 'FAILED'} in "
              f"{time.perf_counter() - t!r} s", flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
