"""End-to-end training driver: LOG.io-protected data pipeline + train step
+ checkable checkpoint write actions, on one device.

CPU (reduced width, float32 — ``presets.REDUCED_TRAIN``):
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 60 --kill-worker-at 15 --kill-trainer-at 30
One TPU v5e chip (full published width — ``presets.ONE_CHIP_TRAIN``: bf16
params, int8 AdamW moments, bf16 accumulation, full remat, 2 x 4096 tokens):
    PYTHONPATH=src python -m repro.launch.train --full --steps 8 --ckpt-every 4
No mesh is built: the step runs unsharded on the default device.

Exactly-once training semantics: consumed batches are acknowledged (their
Input Sets marked done, with the checkpoint as the covering *write action*)
only at checkpoint boundaries, so after ANY crash the pipeline re-delivers
exactly the batches after the last checkpoint, in order — the restarted
trainer replays the identical trajectory (asserted by tests).
  * --kill-worker-at N  : crash a pipeline worker group after ~N batches;
    LOG.io recovers it non-blocking while training keeps running.
  * --kill-trainer-at N : drop the train state at step N, restore from the
    latest checkpoint, and crash the feed group (simulating the trainer pod
    dying with its buffered batches).
"""
from __future__ import annotations

import argparse
import queue as _queue
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore
from repro.configs import get_config, reduced
from repro.core.engine import Engine, FailureInjector
from repro.core.metrics import span
from repro.data import build_data_pipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.presets import ONE_CHIP_TRAIN, REDUCED_TRAIN
from repro.models import layers as L
from repro.models import model as M
from repro.training.optimizer import OptHParams
from repro.training.step import init_train_state, make_train_step


def check_restorable(restored, want) -> None:
    """Raise if a checkpoint's tree, shapes or dtypes differ from ``want``
    (``jax.eval_shape`` of the fresh state): a stale ``ckpt_dir`` from a
    run of another width or dtype must not be resumed."""
    got_def, want_def = jax.tree.structure(restored), jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"checkpoint tree {got_def} does not match this "
                         f"run's train state {want_def}")
    for path, g, w in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                          jax.tree.leaves(restored), jax.tree.leaves(want)):
        if (tuple(g.shape), jnp.dtype(g.dtype)) != (w.shape, w.dtype):
            raise ValueError(
                f"checkpoint leaf {jax.tree_util.keystr(path[0])} is "
                f"{g.dtype}{tuple(g.shape)} but this run's train state has "
                f"{w.dtype}{w.shape}; resume from a checkpoint of the same "
                f"configuration or use a fresh ckpt_dir")


def run_training(*, arch: str = "internlm2-1.8b", use_reduced: bool = True,
                 steps: int = 60, seq_len: Optional[int] = None,
                 batch_size: Optional[int] = None,
                 ckpt_every: int = 10, ckpt_dir: Optional[str] = None,
                 kill_worker_at: Optional[int] = None,
                 kill_trainer_at: Optional[int] = None,
                 lr: float = 1e-3, seed: int = 0, log_every: int = 10,
                 d_model: int = 256, n_layers: int = 4, verbose: bool = True):
    """``ckpt_dir`` defaults to a fresh temporary directory; pass an existing
    one to resume from its latest checkpoint.

    Each iteration of the loop is a ``jax.profiler.StepTraceAnnotation``
    ("train", ``step_num`` the step it trains) tiled by the spans
    ``feed.get``, ``feed.put``, ``step.run``, ``step.sync`` and, at a
    checkpoint, ``ckpt.save`` and ``feed.ack`` (``core/metrics.span``;
    docs/metrics.md). ``timings`` holds the lengths of ``step.compile``
    (``compile_s``), ``step.run`` (``step_s``), ``ckpt.save`` (``save_s``)
    and of each ``ckpt.restore`` that found a checkpoint (``restore_s``),
    and ``attn_paths``: the step's attention call sites by the path each
    took, ``{"kernel": n, "xla": m}`` (also the ``step.compile`` span's
    ``attn_kernel`` and ``attn_xla``)."""
    ts = REDUCED_TRAIN if use_reduced else ONE_CHIP_TRAIN
    seq_len = seq_len or ts.seq_len
    batch_size = batch_size or ts.batch_size
    cfg = get_config(arch)
    if use_reduced:
        nl = n_layers - n_layers % len(cfg.block) or len(cfg.block)
        cfg = reduced(cfg, d_model=d_model, n_layers=nl, vocab=2048,
                      d_ff=4 * d_model, n_heads=4)
    hp = OptHParams(lr=lr, warmup=20, moment_dtype=ts.moment_dtype,
                    grad_accum_dtype=ts.grad_accum_dtype)
    rt = M.Runtime(remat=ts.remat, q_chunk=min(seq_len, ts.q_chunk),
                   shard_activations=False)
    store = CheckpointStore(ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_"))
    timings = {"compile_s": None, "step_s": [], "save_s": [], "restore_s": []}

    # ---- train state (restore-or-init) -----------------------------------
    def fresh_state():
        return init_train_state(jax.random.PRNGKey(seed), cfg, hp,
                                dtype=jnp.dtype(ts.param_dtype))

    def load_state():
        with span("ckpt.restore") as restore:
            _, restored = store.latest()
            if restored is not None:
                check_restorable(restored, jax.eval_shape(fresh_state))
                state = jax.tree.map(jnp.asarray, restored)
                jax.block_until_ready(state)
        if restored is None:
            return fresh_state()
        timings["restore_s"].append(restore.seconds)
        return state

    state = load_state()
    tok_spec = jax.ShapeDtypeStruct((1, batch_size, seq_len), jnp.int32)
    with span("step.compile") as compile_:
        with L.tally_attn_paths() as attn_paths:
            lowered = jax.jit(make_train_step(cfg, hp, rt),
                              donate_argnums=0).lower(
                state, {"tokens": tok_spec, "labels": tok_spec})
        compile_.annotate(**{f"attn_{k}": n for k, n in attn_paths.items()})
        train_step = lowered.compile()
    timings["compile_s"] = compile_.seconds
    timings["attn_paths"] = attn_paths

    # ---- data pipeline (LOG.io-protected) --------------------------------
    pipeline, feed_id = build_data_pipeline(
        seq_len=seq_len, batch_size=batch_size, vocab=cfg.vocab,
        n_shards=2 * steps + 32,
        shard_tokens=(batch_size // 2) * (seq_len + 1),
        per_batch=2, seed=seed)
    plan = []
    if kill_worker_at is not None:
        plan.append(("pack", "post_log", 2 * kill_worker_at))
    engine = Engine(pipeline, injector=FailureInjector(plan),
                    mode="thread", restart_delay=0.01)

    def next_batch(deadline=30.0):
        t_end = time.monotonic() + deadline
        while time.monotonic() < t_end:
            feed = engine.ops[feed_id]
            feed.requeue()
            try:
                return feed, feed.buffer.get(timeout=0.2)
            except _queue.Empty:
                continue
        raise TimeoutError("no batch from the data pipeline")

    engine.start()
    losses, crash_steps = [], []
    pending_insets = []
    killed_trainer = False
    t0 = time.monotonic()
    step = int(state["step"])
    while step < steps:
        with jax.profiler.StepTraceAnnotation("train", step_num=step + 1):
            with span("feed.get"):
                feed, (inset, body) = next_batch()
            with span("feed.put"):
                toks = jnp.asarray(body["tokens"][:batch_size])
                batch = {"tokens": toks[None, :, :-1],
                         "labels": toks[None, :, 1:].astype(jnp.int32)}
            with span("step.run", into=timings["step_s"]):
                state, metrics = train_step(state, batch)
                jax.block_until_ready(state)
            with span("step.sync"):
                step = int(state["step"])
                loss = float(metrics["loss"])
                losses.append(loss)
                pending_insets.append(inset)
                if verbose and (step % log_every == 0 or step >= steps):
                    print(f"step {step:4d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"({time.monotonic() - t0:.1f}s)", flush=True)

            if step % ckpt_every == 0 or step >= steps:
                with span("ckpt.save", into=timings["save_s"]):
                    ref = store.save(state, step)
                with span("feed.ack"):
                    feed_now = engine.ops[feed_id]
                    for ins in pending_insets:
                        feed_now.complete(ins, step, ref)
                    pending_insets = []

            if (kill_trainer_at is not None and step >= kill_trainer_at
                    and not killed_trainer):
                with span("trainer.restart"):
                    killed_trainer = True
                    crash_steps.append(step)
                    if verbose:
                        print(f"!! trainer crash at step {step}: dropping "
                              f"state, restoring from checkpoint", flush=True)
                    old_feed = engine.ops[feed_id]
                    engine.kill_group(engine.pipeline.groups[feed_id])
                    state = metrics = None  # free the device copy first
                    state = load_state()
                    step = int(state["step"])
                    pending_insets = []
                    # wait for the feed group to be rebuilt (fresh buffer)
                    t_end = time.monotonic() + 10
                    while (engine.ops[feed_id] is old_feed
                           and time.monotonic() < t_end):
                        time.sleep(0.01)

    with span("feed.stop"):
        engine.stop()
    return {"losses": losses, "crash_steps": crash_steps, "engine": engine,
            "final_state": state, "store": store,
            "steps": int(state["step"]), "timings": timings}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    default=True)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None,
                    help="resume from / save into this directory "
                         "(default: a fresh temporary directory)")
    ap.add_argument("--kill-worker-at", type=int, default=None)
    ap.add_argument("--kill-trainer-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    out = run_training(arch=args.arch, use_reduced=args.reduced,
                       steps=args.steps, seq_len=args.seq_len,
                       batch_size=args.batch_size, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir,
                       kill_worker_at=args.kill_worker_at,
                       kill_trainer_at=args.kill_trainer_at,
                       d_model=args.d_model, n_layers=args.n_layers,
                       seed=args.seed)
    print(f"finished at step {out['steps']}; "
          f"pipeline failures={out['engine'].failures} "
          f"restarts={out['engine'].restarts}; "
          f"checkpoints in {out['store'].dir}")


if __name__ == "__main__":
    main()
