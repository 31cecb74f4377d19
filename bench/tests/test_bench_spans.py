"""The span and scope reduction (``bench/spans.py``) on a small recorded
trace, and the per-layer readers of the program's own spans."""
import json
from collections import deque

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

from bench import harness, spans, trace
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
    # inclusive: fusion.1 counts under blocks and under mixer
    out = _reduce()
    assert out["device_by_scope"] == pytest.approx({
        "mixer": 0.030, "blocks": 0.062, "grad_accum": 0.020,
        "optimizer": 0.042, spans.NO_SCOPE: 0.005})


def test_a_nested_scope_leaves_the_outer_scopes_time_as_it_is():
    nested = [(n, s, d, p.replace("/mixer/", "/mixer/mamba/"))
              for n, s, d, p in OPS]
    known = spans.program_scopes() | {"mamba"}
    out = spans.reduce_spans({"/device:TPU:0": nested}, [FEED_THREAD, LOOP],
                             0.4, known=known)
    assert out["device_by_scope"] == pytest.approx(dict(
        _reduce()["device_by_scope"], mamba=0.030))
    assert ["mamba/fusion.1", pytest.approx(0.030)] in out["device_ops"]


@pytest.mark.parametrize("path, on", [
    # nested scopes, outermost first
    ("jit(f)/blocks/while/body/mixer/mamba/mul", ["blocks", "mixer", "mamba"]),
    # wrappers taken off, and a scope met twice on one path counted once
    ("jit(f)/transpose(jvp(blocks))/closed_call/transpose(jvp(mixer))/"
     "checkpoint/mixer/dot_general", ["blocks", "mixer"]),
    # a name that is not a known scope, and a primitive named like none
    ("jit(f)/attention/ffn/jit(silu)/logistic", ["ffn"]),
    ("jit(f)/while/body/add", []),
])
def test_known_scopes_on_a_path(path, on):
    known = {"blocks", "mixer", "mamba", "ffn"}
    assert spans.scopes_on(path, known) == on


def test_scopes_are_the_names_the_program_gives_named_scope(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "a.py").write_text(
        'with jax.named_scope("mixer"):\n    pass\n'
        "with jax.named_scope( 'mamba' ):\n    pass\n")
    (tmp_path / "b.py").write_text('named_scope("ffn")\nscope("x")\n')
    assert spans.program_scopes(tmp_path) == {"mixer", "mamba", "ffn"}
    # the three that the per-layer readers read are the program's
    assert {"mixer", "ffn", "optimizer"} <= spans.program_scopes()


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
    assert ops[0] == ["optimizer/fusion.5", pytest.approx(0.042)]
    names = [name for name, _ in ops]
    assert not any("while.3" in n for n in names)       # a container
    assert "blocks/fusion.2" in names and "(no scope)/copy.6" in names


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/while/body/closed_call/transpose(jvp(head))/jit(take_along_axis)"
     "/scatter-add", "head"),
    (BLOCKS_BWD + "/while/body/closed_call/checkpoint/rematted_computation/"
     "norm/div", "norm"),
    ("jit(f)/jvp(blocks)/while/body/closed_call/ffn/jit(silu)/mul", "ffn"),
    ("jit(f)/mixer/closed_call/bsd,dhk->bshk/add_any", "mixer"),
    ("state['opt']['m']['embed']", None),
    ("", None),
])
def test_scope_of_an_op_name(path, scope):
    on = spans.scopes_on(path, spans.program_scopes())
    assert (on[-1] if on else None) == scope


def test_op_names_from_the_compiled_text():
    text = """HloModule jit_train_step, is_scheduled=true
  %fusion.1 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/mixer/dot" source_file="x.py"}
  ROOT %bitcast_add_fusion.6 = f32[2]{0} fusion(%a), metadata={op_name="jit(f)/grad_accum/add"}
  %param.2 = f32[2]{0} parameter(0)
"""
    assert spans.hlo_module_name(text) == "jit_train_step"
    assert spans.hlo_op_names(text) == {
        "fusion.1": "jit(f)/mixer/dot",
        "bitcast_add_fusion.6": "jit(f)/grad_accum/add"}


def _xspace_event(name, start_ns, dur_ns, ids):
    ids.setdefault(name, len(ids) + 1)
    return (f"events {{ metadata_id: {ids[name]} "
            f"offset_ps: {int(start_ns * 1000)} "
            f"duration_ps: {int(dur_ns * 1000)} }}")


def _write_xplane(path, planes):
    """A profiler trace file of planes ``{plane: {line: [(name, start_ns,
    dur_ns)]}}``."""
    import jax
    text = []
    for p, (plane, lines) in enumerate(planes.items()):
        ids = {}
        body = [f"lines {{ id: {i} name: {json.dumps(line)} timestamp_ns: 0 "
                + " ".join(_xspace_event(*e, ids) for e in events) + " }"
                for i, (line, events) in enumerate(lines.items())]
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} "
                f"name: {json.dumps(n)} }} }}" for n, i in ids.items()]
        text.append(f"planes {{ id: {p} name: {json.dumps(plane)} "
                    + " ".join(body + meta) + " }")
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(text)))
    return str(path)


def test_an_op_of_another_program_takes_no_scope_from_the_step(tmp_path):
    # feed.put's own program runs a fusion.1 and a copy.6 too: the step's
    # names must not lend them its scopes
    step = [(f"%{n} = f32[2]{{0}} {n.split('.')[0]}(%p)", s, d)
            for n, s, d, _ in OPS]
    put = [("%fusion.1 = bf16[2]{0} convert(%x)", 111 * MS, 2 * MS),
           ("%copy.6 = bf16[2]{0} copy(%y)", 113 * MS, 1 * MS)]
    path = _write_xplane(tmp_path / "t.xplane.pb", {
        "/device:TPU:0": {
            "XLA Modules": [("jit_train_step(42)", 16 * MS, 62 * MS),
                            ("jit_convert_element_type(7)", 111 * MS,
                             3 * MS),
                            ("jit_train_step(42)", 116 * MS, 62 * MS)],
            "XLA Ops": sorted(step + put, key=lambda e: e[1])},
        "/host:CPU": {"python": [(n, a, b - a) for n, a, b in LOOP]}})
    ops = trace.device_ops(path)["/device:TPU:0"]
    assert [m for _, _, _, m in ops] == ["jit_train_step"] * 3 + [
        "jit_convert_element_type"] * 2 + ["jit_train_step"] * 2 + [""]
    names = {"jit_train_step": {n: p for n, _, _, p in OPS}}
    out = spans.reduce_trace(path, names, 0.4)
    want = dict(_reduce()["device_by_scope"])
    want[spans.NO_SCOPE] += 0.003
    assert out["device_by_scope"] == pytest.approx(want)
    assert out["busy_s"] == pytest.approx(0.129 + 0.003)
    assert out["idle_gaps"][0] == ["feed.stop", pytest.approx(0.1)]


def test_no_loop_or_no_device_reads_nothing():
    assert spans.reduce_spans({"/device:TPU:0": OPS}, [FEED_THREAD], 0.4) \
        is None
    assert spans.reduce_spans({}, [LOOP], 0.4) is None


def _run(steps=3, saves=1, device_by_scope=None):
    summary = None if device_by_scope is None else {
        "device_by_scope": device_by_scope}
    return harness.RunRecord(setup_s=1.0, window_s=5.0, tokens_per_step=8,
                             step_s=[1.0] * steps, save_s=[0.5] * saves,
                             flops_per_step=1.0, peak_flops_per_s=1.0,
                             trace=summary)


SCOPE_S = {"blocks": 23.0, "mixer": 17.0, "ffn": 5.8, "optimizer": 0.6,
           spans.NO_SCOPE: 0.7}


@pytest.mark.parametrize("name, want", [
    ("mixer_device_ms", 17.0 / 13 * 1000),
    ("ffn_device_ms", 5.8 / 13 * 1000),
    ("optimizer_device_ms", 0.6 / 13 * 1000),
])
def test_readers_of_device_time_by_scope(name, want):
    read = load_reader(name)
    assert read(_run(steps=13, device_by_scope=SCOPE_S)) == pytest.approx(
        want, rel=1e-12)
    # no trace, or no operation under the scope (a model with no FFN)
    assert read(_run(steps=13)) is None
    scope = name[:-len("_device_ms")]
    assert read(_run(steps=13, device_by_scope={
        k: v for k, v in SCOPE_S.items() if k != scope})) is None


def test_a_scope_the_program_does_not_open_is_an_error():
    # a reader of it would read nothing, and its metric leave the line
    with pytest.raises(KeyError, match="no-such-scope"):
        spans.scope_ms(_run(device_by_scope=SCOPE_S), "no-such-scope")


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (harness.BENCH / "metrics").glob("*.py")))
def test_every_reader_reads_a_traced_record(name):
    # each reader of device time by scope reads a scope the program opens
    run = _run(device_by_scope={})
    run.trace.update(busy_s=1.0, window_s=5.0, idle_share=0.8)
    value = load_reader(name)(run)
    assert value is None or value >= 0


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
        "tiny-lm.steady", bench_tiny.SEED, 1, require_accelerator=False,
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
    assert not {"mixer_device_ms", "ffn_device_ms",
                "optimizer_device_ms"} & set(got)
    assert trace.find_xplane is find
    assert jax.stages.Lowered.compile is compile_
