"""The span and scope reduction (``bench/spans.py``) on a small recorded
trace, and the per-layer readers of the program's own spans."""
from collections import deque

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

from bench import harness, spans
from bench.harness import load_reader
from repro.core import metrics

MS = 1e6   # ns

BLOCKS_BWD = "jit(train_step)/while/body/closed_call/transpose(jvp(blocks))"
OPS = [
    ("fusion.1", 16 * MS, 30 * MS,
     BLOCKS_BWD + "/while/body/closed_call/checkpoint/mixer/dot_general"),
    ("fusion.2", 46 * MS, 32 * MS,
     BLOCKS_BWD + "/while/body/dynamic_update_slice"),
    ("while.3", 16 * MS, 62 * MS, "jit(train_step)/while"),
    ("add.4", 116 * MS, 20 * MS, "jit(train_step)/while/body/grad_accum/add"),
    ("fusion.5", 136 * MS, 42 * MS, "jit(train_step)/optimizer/mul"),
    ("copy.6", 200 * MS, 5 * MS, ""),
]


def _s(name, a, b):
    return (name, a * MS, b * MS)


LOOP = [
    _s("train", 0, 100), _s("feed.get", 0, 10), _s("feed.put", 10, 15),
    _s("step.run", 15, 80), _s("step.sync", 80, 95),       # 95..100: none
    _s("train", 100, 300), _s("feed.get", 100, 110), _s("feed.put", 110, 115),
    _s("step.run", 115, 180), _s("step.sync", 180, 190),
    _s("ckpt.save", 190, 290), _s("ckpt.pull", 190, 220),
    _s("ckpt.write", 220, 260), _s("ckpt.fsync", 260, 290),
    _s("feed.ack", 290, 300), _s("log.commit", 292, 296),
    _s("feed.stop", 300, 400),
]
FEED_THREAD = [_s("log.commit", 20, 21), _s("log.commit", 120, 122)]


def _reduce(window_s=0.4):
    return spans.reduce_spans({"/device:TPU:0": OPS}, [FEED_THREAD, LOOP],
                              window_s)


def test_idle_time_goes_to_the_innermost_open_span():
    out = _reduce()
    want = {"feed.get": 20, "feed.put": 10, "step.run": 6, "step.sync": 25,
            spans.NO_SPAN: 5, "ckpt.pull": 25, "ckpt.write": 40,
            "ckpt.fsync": 30, "feed.ack": 6, "log.commit": 4,
            "feed.stop": 100}
    assert out["idle_by_span"] == pytest.approx(
        {k: v / 1000 for k, v in want.items()})
    # idle and busy (62 + 62 + 5 ms) fill the window
    assert sum(out["idle_by_span"].values()) == pytest.approx(0.4 - 0.129)
    assert out["idle_unattributed_share"] == pytest.approx(0.005 / 0.4)


def test_gaps_are_cut_at_span_boundaries_and_named():
    gaps = _reduce()["idle_gaps"]
    assert gaps[0] == ["feed.stop", pytest.approx(0.1)]
    assert gaps[1] == ["ckpt.write", pytest.approx(0.04)]
    assert all(label != "host" for label, _ in gaps) and len(gaps) == 10


def test_the_edge_gap_outside_the_loop_is_no_span():
    # a window that opens 50 ms before the loop's first span
    out = _reduce(window_s=0.45)
    assert out["idle_by_span"][spans.NO_SPAN] == pytest.approx(0.055)
    assert out["idle_by_span"]["feed.get"] == pytest.approx(0.02)


def test_device_time_by_scope_through_the_backward_pass():
    out = _reduce()
    assert out["device_by_scope"] == pytest.approx({
        "mixer": 0.030, "blocks": 0.032, "grad_accum": 0.020,
        "optimizer": 0.042, spans.NO_SCOPE: 0.005})


def test_device_time_by_span_shows_the_clocks_agree():
    out = _reduce()
    assert out["device_by_span"] == pytest.approx(
        {"step.run": 0.124, "ckpt.pull": 0.005})
    # only the copy in ckpt.pull lies outside a step.run
    assert out["device_outside_step_run_s"] == pytest.approx(0.005)


def test_an_op_that_outlasts_its_step_counts_outside():
    late = OPS + [("fusion.7", 170 * MS, 20 * MS, "")]   # step.run ends at 180
    out = spans.outside(late, LOOP, "step.run")
    assert out == pytest.approx(0.005 + 0.020)


def test_top_operations_carry_their_scope():
    ops = _reduce()["device_ops"]
    assert ops[0] == ["fusion.5", pytest.approx(0.042), "optimizer"]
    assert ["while.3"] not in [o[:1] for o in ops]       # a container
    assert {o[0]: o[2] for o in ops}["fusion.2"] == "blocks"


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/while/body/closed_call/transpose(jvp(head))/jit(take_along_axis)"
     "/scatter-add", "head"),
    (BLOCKS_BWD + "/while/body/closed_call/checkpoint/rematted_computation/"
     "norm/div", "norm"),
    ("jit(f)/jvp(blocks)/while/body/closed_call/ffn/jit(silu)/mul", "ffn"),
    ("jit(f)/mixer/closed_call/bsd,dhk->bshk/add_any", "mixer"),
    ("state['opt']['m']['embed']", spans.NO_SCOPE),
    ("", spans.NO_SCOPE),
])
def test_scope_of_an_op_name(path, scope):
    assert spans.scope_of(path) == scope


def test_op_names_from_the_compiled_text():
    text = """HloModule jit_train_step, is_scheduled=true
  %fusion.1 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/mixer/dot" source_file="x.py"}
  ROOT %bitcast_add_fusion.6 = f32[2]{0} fusion(%a), metadata={op_name="jit(f)/grad_accum/add"}
  %param.2 = f32[2]{0} parameter(0)
"""
    assert spans.hlo_op_names(text) == {
        "fusion.1": "jit(f)/mixer/dot",
        "bitcast_add_fusion.6": "jit(f)/grad_accum/add"}


def test_no_loop_or_no_device_reads_nothing():
    assert spans.reduce_spans({"/device:TPU:0": OPS}, [FEED_THREAD], 0.4) \
        is None
    assert spans.reduce_spans({}, [LOOP], 0.4) is None


def _run(steps=3, saves=1):
    return harness.RunRecord(setup_s=1.0, window_s=5.0, tokens_per_step=8,
                             step_s=[1.0] * steps, save_s=[0.5] * saves,
                             flops_per_step=1.0, peak_flops_per_s=1.0)


@pytest.fixture
def recorded(monkeypatch):
    """The program's span record, as a run of 4 steps and one save leaves
    it: the window holds the last 3 steps."""
    rec = {"feed.get": deque([(0.0, 0.010), (1.0, 1.001), (2.0, 2.002),
                              (3.0, 3.003)]),
           "ckpt.pull": deque([(3.5, 3.7)]),
           "ckpt.write": deque([(3.7, 4.0)]),
           "ckpt.fsync": deque([(4.0, 4.1)]),
           # one commit before the window's first step, three in it
           "log.commit": deque([(0.5, 0.6), (1.5, 1.501), (2.5, 2.502),
                                (4.2, 4.203)])}
    monkeypatch.setattr(metrics, "_spans", rec)
    return rec


@pytest.mark.parametrize("name, want", [
    ("feed_get_ms", 2.0),              # (1 + 2 + 3) ms / 3 window steps
    ("ckpt_pull_s", 0.2),
    ("ckpt_write_s", 0.3),
    ("ckpt_fsync_s", 0.1),
    ("log_commit_ms", 2.0),            # (1 + 2 + 3) ms / 3 window steps
])
def test_readers_of_the_program_spans(recorded, name, want):
    assert load_reader(name)(_run()) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", ["feed_get_ms", "ckpt_pull_s",
                                  "ckpt_write_s", "ckpt_fsync_s",
                                  "log_commit_ms"])
def test_readers_read_nothing_where_the_program_keeps_no_spans(
        recorded, monkeypatch, name):
    assert load_reader(name)(_run(steps=9, saves=2)) is None   # too few
    monkeypatch.delattr(metrics, "recent_spans")    # a program without them
    assert load_reader(name)(_run()) is None


def test_report_run_reads_the_program_spans_and_restores_the_harness():
    import jax
    from bench import span_report, trace
    find, compile_ = trace.find_xplane, jax.stages.Lowered.compile
    result, report = span_report.traced_run(
        "lm.steady", bench_tiny.SEED, 1, require_accelerator=False,
        spec=bench_tiny.tiny_spec())
    assert result["correct"], result["checks"]
    got = result["metrics"]
    for name in ("feed_get_ms", "ckpt_pull_s", "ckpt_write_s",
                 "ckpt_fsync_s", "log_commit_ms"):
        assert got[name]["value"] >= 0, name
    # the children of the one save add up to it, bar the file's creation
    parts = sum(got[k]["value"] for k in ("ckpt_pull_s", "ckpt_write_s",
                                          "ckpt_fsync_s"))
    assert parts <= got["ckpt_save_s"]["value"]
    assert got["feed_get_ms"]["value"] <= got["feed_wait_ms"]["value"]
    assert report is None           # the CPU has no device plane
    assert trace.find_xplane is find
    assert jax.stages.Lowered.compile is compile_
