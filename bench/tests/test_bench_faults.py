"""The harness without its look for a chip, driving a whole run of a tiny
cell on the CPU: a sound run comes out correct, and a run with the timed
path broken underneath comes out not correct, for each fault a training
cell can have on one chip."""

import bench_tiny

import repro.launch.train as train_mod


def _broken_step(monkeypatch, change):
    orig = train_mod.make_train_step

    def make(cfg, hp, rt, **kw):
        step = orig(cfg, hp, rt, **kw)
        return lambda state, batch: change(step, state, batch)
    monkeypatch.setattr(train_mod, "make_train_step", make)


def test_sound_run_is_correct():
    r = bench_tiny.run("tiny-lm.steady")
    assert r["correct"], r["checks"]
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert {"gnorm_gap", "grad_leaf_gap", "change1_leaf_gap"} <= set(r["checks"])


def test_sound_hybrid_run_is_correct():
    # a period of two kinds of layer, made only of the shipped parts
    r = bench_tiny.run("tiny-hybrid.steady")
    assert r["correct"], r["checks"]
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert {"gnorm_gap", "grad_leaf_gap", "change1_leaf_gap"} <= set(r["checks"])


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    def half(step, state, batch):
        b = batch["tokens"].shape[1] // 2
        return step(state, {k: v[:, :b] for k, v in batch.items()})
    _broken_step(monkeypatch, half)
    r = bench_tiny.run("tiny-lm.steady")
    assert not r["correct"]
    assert r["checks"]["gnorm_gap"]["value"] > r["checks"]["gnorm_gap"]["limit"]


def test_parameters_left_unchanged_is_not_correct(monkeypatch):
    # the step advances its counters but returns the parameters it got;
    # a step that returns its whole state unchanged never ends the loop
    def frozen(step, state, batch):
        new, metrics = step(state, batch)
        return dict(new, params=state["params"]), metrics
    _broken_step(monkeypatch, frozen)
    r = bench_tiny.run("tiny-lm.steady")
    assert not r["correct"]
    assert r["checks"]["state_mismatches"]["value"] > 0
    c = r["checks"]["change1_leaf_gap"]
    assert c["value"] == 1.0 > c["limit"]



def test_missing_readings_are_not_correct():
    # a run whose log stops after step 1 leaves the later readings unread
    from bench import harness, reference

    class Unread:
        grad_leaf = None
        change_last_leaf = None
    cfg = {"limits": {"state_mismatches": 0, "feed_mismatches": 0},
           "train": {"seq_len": 8, "batch_size": 2}}
    batch = harness.batch_digest(reference.corpus_batch(5, 1, 16, 8, 2))
    checks = harness.compare(cfg, {"vocab_size": 16}, 5, [(1, 1.0, 1.0)],
                             [1.0], [batch], 0, [], Unread())
    c = checks["readings_missing"]
    assert c["value"] > c["limit"] == 0
