"""Spans (``core/metrics.span``) where the training loop, the checkpoint save
and the log commit do their work, and the named scopes of the train step."""
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import metrics
from repro.core.metrics import recent_spans, span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_LEAVES = ("feed.get", "feed.put", "step.run", "step.sync")
SAVE_LEAVES = ("ckpt.save", "feed.ack")


def test_span_records_its_length_and_keeps_it():
    into = []
    with span("test.span", into=into, k=1) as s:
        pass
    assert into == [s.seconds] and s.seconds >= 0
    start, end = recent_spans("test.span")[-1]
    assert end - start == s.seconds
    assert recent_spans("test.unknown") == []


def test_span_records_when_the_body_raises():
    with pytest.raises(ValueError):
        with span("test.raises"):
            raise ValueError("x")
    assert recent_spans("test.raises")


def test_span_keeps_the_latest_per_name(monkeypatch):
    monkeypatch.setattr(metrics, "SPANS_KEPT", 3)
    monkeypatch.setattr(metrics, "_spans", {})
    for _ in range(5):
        with span("test.kept"):
            pass
    assert len(recent_spans("test.kept")) == 3


_NO_JAX = """
import sys
from repro.core import Engine
from repro.core.metrics import recent_spans
from tests.helpers import linear_pipeline
build, expected = linear_pipeline()
eng = Engine(build(), mode="step")
eng.run_to_completion()
assert eng.external.committed() == expected
commits = recent_spans("log.commit")
assert commits
# one place: the counter sums the spans' lengths, commit by commit
total = sum(m.commit_us for m in eng.metrics().ops.values())
assert total == sum(int((e - s) * 1e6) for s, e in commits), total
assert "jax" not in sys.modules
"""


def test_engine_commits_are_spans_and_the_counter_without_jax():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run([sys.executable, "-c", _NO_JAX], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _loop_line(xplane):
    """The events of the train loop's thread line, and of every host line."""
    pd = jax.profiler.ProfileData.from_file(xplane)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    loop = next(ev for ev in lines if any(n == "train" for n, _, _ in ev))
    return loop, [ev for line in lines for ev in line]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny traced ``run_training`` and its ``.xplane.pb``."""
    from repro.launch.train import run_training
    d = tmp_path_factory.mktemp("spans")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d / "trace"), profiler_options=opts)
    try:
        out = run_training(steps=4, ckpt_every=4, seq_len=16, batch_size=2,
                           ckpt_dir=str(d / "ckpt"), d_model=32, n_layers=1,
                           verbose=True, seed=5)
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(str(d / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)[-1]
    return out, xplane


@pytest.fixture(scope="module")
def traced_run(traced):
    out, xplane = traced
    return out, _loop_line(xplane)


def test_leaf_spans_tile_each_iteration(traced_run):
    out, (loop, _) = traced_run
    steps = sorted((s, e) for n, s, e in loop if n == "train")
    assert len(steps) == 4
    leaves = LOOP_LEAVES + SAVE_LEAVES
    spans = sorted((s, e, n) for n, s, e in loop if n in leaves)
    covered = total = 0
    for i, (s0, e0) in enumerate(steps):
        inside = [sp for sp in spans if s0 <= sp[0] and sp[1] <= e0]
        names = tuple(n for _, _, n in inside)
        assert names == LOOP_LEAVES + (SAVE_LEAVES if i == 3 else ())
        for a, b in zip(inside, inside[1:]):
            assert a[1] <= b[0]        # in order, none overlaps the next
        covered += sum(e - s for s, e, _ in inside)
        total += e0 - s0
    assert covered > 0.5 * total


def test_save_has_its_three_children(traced_run):
    _, (loop, _) = traced_run
    (save,) = [(s, e) for n, s, e in loop if n == "ckpt.save"]
    kids = sorted((s, n) for n, s, e in loop
                  if n.startswith("ckpt.") and n != "ckpt.save"
                  and save[0] <= s and e <= save[1])
    assert [n for _, n in kids] == ["ckpt.pull", "ckpt.write", "ckpt.fsync"]


def test_write_span_carries_the_leaf_bytes(traced):
    out, xplane = traced
    pd = jax.profiler.ProfileData.from_file(xplane)
    (write,) = [dict(e.stats) for plane in pd.planes
                if plane.name.startswith("/host:") for line in plane.lines
                for e in line.events if e.name == "ckpt.write"]
    _, state = out["store"].latest()
    assert write["bytes"] == sum(a.nbytes for a in jax.tree.leaves(state))
    assert write["bytes"] > 0


def test_log_commits_are_spans_on_the_feed_and_the_ack(traced_run):
    _, (loop, every) = traced_run
    assert any(n == "log.commit" for n, _, _ in every)
    (ack,) = [(s, e) for n, s, e in loop if n == "feed.ack"]
    assert any(n == "log.commit" and ack[0] <= s and e <= ack[1]
               for n, s, e in loop)


def test_timings_hold_one_entry_per_step_and_save(traced_run):
    out, _ = traced_run
    t = out["timings"]
    assert len(t["step_s"]) == 4 and len(t["save_s"]) == 1
    assert t["compile_s"] > 0 and t["restore_s"] == []
    # the same enter and exit as the spans
    assert [e - s for s, e in recent_spans("step.run")[-4:]] == t["step_s"]
    assert [e - s for s, e in recent_spans("ckpt.save")[-1:]] == t["save_s"]


def test_every_span_name_is_documented(traced_run):
    _, (_, every) = traced_run
    with open(os.path.join(ROOT, "docs", "metrics.md")) as f:
        doc = f.read()
    names = {n for n, _, _ in every
             if re.fullmatch(r"[a-z]+\.[a-z]+", n) or n == "train"}
    assert {"feed.stop", "step.compile", "log.commit"} <= names
    for n in names:
        assert f"`{n}`" in doc, n


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "falcon-mamba-7b"])
def test_step_carries_the_named_scopes(arch):
    from repro.configs import get_config, reduced
    from repro.launch.presets import ONE_CHIP_TRAIN as ts
    from repro.models import model as M
    from repro.training.optimizer import OptHParams
    from repro.training.step import init_train_state, make_train_step
    cfg = reduced(get_config(arch), d_model=32, n_layers=1, vocab=64,
                  d_ff=64, n_heads=2)
    hp = OptHParams(moment_dtype=ts.moment_dtype,
                    grad_accum_dtype=ts.grad_accum_dtype)
    rt = M.Runtime(remat=ts.remat, q_chunk=8)
    state = jax.eval_shape(lambda: init_train_state(
        jax.random.PRNGKey(0), cfg, hp, dtype=jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((1, 2, 8), jnp.int32)
    text = jax.jit(make_train_step(cfg, hp, rt)).lower(
        state, {"tokens": tok, "labels": tok}).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    want = {"embed", "blocks", "norm", "mixer", "head", "grad_accum",
            "optimizer"} | ({"ffn"} if arch.startswith("internlm") else set())
    for scope in want:
        assert any(re.search(rf"(^|/|\(){scope}(\)|/|$)", p) for p in paths), \
            scope
    # the backward pass keeps the forward's scope
    assert any("transpose(jvp(blocks))" in p for p in paths)
