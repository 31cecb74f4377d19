"""Host spans and device scopes in a profiler trace of the measured window.

The program names its host work with spans (``repro.core.metrics.span``,
listed in docs/metrics.md): each is a ``TraceMe`` event on the host plane,
one line per thread, on the clock the device planes are aligned to. The
train loop's line is the one that holds its ``train`` step events. The
device step carries ``jax.named_scope`` names in each operation's
``op_name`` (``.../transpose(jvp(blocks))/.../mixer/dot_general``). A v5e
trace does not carry ``op_name``: it is read from the compiled step's text
(``compiled_op_names``) by the operation's program and name. The scopes
known are the names the program's own source gives ``jax.named_scope``
(``program_scopes``), so that a scope the program adds is known here with
no change to this file; a reader of a scope the program does not open
fails (``scope_ms``).

``reduce_trace`` adds to ``bench/trace.py``'s busy time and idle share
(``reduce_spans``):

* each interval of the window in which the device ran nothing, cut at the
  loop's span boundaries, each piece labelled by the innermost span open
  over it, or ``NO_SPAN``;
* device time by scope, inclusive: an operation counts for every known
  scope on its ``op_name`` path, or for ``NO_SCOPE`` where it has none;
* device time by the loop span open as each operation starts, and outside
  ``step.run``: the two clocks agree when a step's operations lie inside
  its ``step.run``;
* the top operations named ``<scope>/<op>`` by their innermost scope.

The harness reduces the trace of every traced run so; the readers of
``bench/metrics/`` take device time by scope from ``RunRecord`` and the
host spans from the program's own record of them (``recorded``).
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple

from bench import trace as trace_mod
from bench.trace import busy_intervals

SRC = Path(__file__).resolve().parent.parent / "src"

STEP = "train"
# the program's spans, as docs/metrics.md lists them
SPANS = (STEP, "step.compile", "ckpt.restore", "feed.get", "feed.put",
         "step.run", "step.sync", "ckpt.save", "ckpt.pull", "ckpt.write",
         "ckpt.fsync", "feed.ack", "trainer.restart", "feed.stop",
         "log.commit")
NO_SPAN = "host (no span)"
NO_SCOPE = "(no scope)"
# operations that contain others on the same line: they count towards busy
# time, but their own totals would hide the operations inside them
CONTAINERS = ("while", "conditional", "call")
_SCOPE_PART = re.compile(r"(?:[\w-]+\()*([\w-]+)\)*")
_NAMED_SCOPE = re.compile(r"""named_scope\(\s*["']([\w.-]+)["']""")
_HLO_MODULE = re.compile(r"^\s*HloModule ([\w.-]+)", re.M)
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)

Span = Tuple[str, float, float]              # name, start_ns, end_ns
Op = Tuple[str, float, float, str]           # name, start_ns, dur_ns, op_name
Interval = Tuple[float, float]


def host_spans(xplane_path: str) -> List[List[Span]]:
    """The program's spans on each thread line of the host planes, in the
    trace's order of lines."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, float(e.start_ns),
                      float(e.start_ns + e.duration_ns))
                     for e in line.events if e.name in SPANS]
            if spans:
                out.append(spans)
    return out


def with_op_names(ops_by_device: Dict[str, List[Tuple[str, float, float,
                                                      str]]],
                  op_names: Dict[str, Dict[str, str]]) -> Dict[str, List[Op]]:
    """``bench.trace.device_ops`` with each operation's ``op_name`` from
    ``op_names`` (``compiled_op_names``), matched by its program and its
    name: "" for an operation of a program not compiled there, whose names
    (``copy.3``, ``fusion.1``) a compiled one may share."""
    return {dev: [(name, s, d, op_names.get(module, {}).get(name, ""))
                  for name, s, d, module in ops]
            for dev, ops in ops_by_device.items()}


def hlo_module_name(hlo_text: str) -> str:
    """The program's name (``jit_train_step``) from its ``as_text()``."""
    m = _HLO_MODULE.search(hlo_text)
    return m.group(1) if m else ""


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: op_name} from a compiled program's ``as_text()``."""
    return dict(_HLO_OP_NAME.findall(hlo_text))


@contextlib.contextmanager
def compiled_op_names():
    """Yields a dict that collects, by program name, ``hlo_op_names`` of
    every program compiled ahead of time (``jax.stages.Lowered.compile``)
    inside the block: ``run_training`` compiles its train step so, and is
    the only caller in a run that does."""
    import jax
    names: Dict[str, Dict[str, str]] = {}
    compile_ = jax.stages.Lowered.compile

    def compile_and_read_names(self, *args, **kwargs):
        compiled = compile_(self, *args, **kwargs)
        text = compiled.as_text()
        names.setdefault(hlo_module_name(text), {}).update(hlo_op_names(text))
        return compiled

    jax.stages.Lowered.compile = compile_and_read_names
    try:
        yield names
    finally:
        jax.stages.Lowered.compile = compile_


@functools.lru_cache(maxsize=None)
def program_scopes(src: Path = SRC) -> FrozenSet[str]:
    """The scopes the reduction knows: every name that the program's source
    under ``src`` gives ``jax.named_scope``."""
    return frozenset(name for path in sorted(src.rglob("*.py"))
                     for name in _NAMED_SCOPE.findall(path.read_text()))


def loop_spans(lines: List[List[Span]]) -> List[Span]:
    """The train loop's line: the one that holds its step events."""
    for spans in lines:
        if any(name == STEP for name, _, _ in spans):
            return spans
    return []


def window_bounds(loop: List[Span], window_s: float) -> Optional[Interval]:
    """The window on the trace's clock: it ends as the loop's last span ends
    (the loop returns right after) and lasts ``window_s``."""
    if not loop:
        return None
    end = max(e for _, _, e in loop)
    return end - window_s * 1e9, end


def idle_intervals(busy: List[Interval], lo: float, hi: float
                   ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Segments:
    """The loop's timeline cut at every span boundary, each piece labelled
    by the innermost span open over it: of those that hold it, the one that
    started last, or of two that started together the one that ends first.
    The step events count as no span, so that time inside a step that no
    span covers reads ``NO_SPAN``."""

    def __init__(self, loop: List[Span]):
        spans = [sp for sp in loop if sp[0] != STEP]
        self.cuts = sorted({t for _, s, e in spans for t in (s, e)})
        self.labels = []
        for p, q in zip(self.cuts, self.cuts[1:]):
            mid = (p + q) / 2
            held = [(s, -e, name) for name, s, e in spans if s <= mid < e]
            self.labels.append(max(held)[2] if held else NO_SPAN)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.labels[i] if 0 <= i < len(self.labels) else NO_SPAN

    def label(self, a: float, b: float) -> List[Tuple[str, float]]:
        """``[a, b]`` cut at the boundaries: ``(label, seconds)`` per run of
        pieces with one label."""
        lo = bisect.bisect_right(self.cuts, a)
        hi = bisect.bisect_left(self.cuts, b)
        points = [a] + self.cuts[lo:hi] + [b]
        out: List[List] = []
        for p, q in zip(points, points[1:]):
            label = self.at((p + q) / 2)
            if out and out[-1][0] == label:
                out[-1][1] += q - p
            else:
                out.append([label, q - p])
        return [(label, ns / 1e9) for label, ns in out]


def scopes_on(name: str, known: Iterable[str]) -> List[str]:
    """The known scopes on an ``op_name`` path, outermost first and each
    once, wrappers such as ``transpose(jvp(...))`` taken off."""
    out: List[str] = []
    for part in name.split("/"):
        m = _SCOPE_PART.fullmatch(part)
        if m and m.group(1) in known and m.group(1) not in out:
            out.append(m.group(1))
    return out


def outside(ops: List[Op], loop: List[Span], name: str) -> float:
    """Seconds of device time in operations that do not lie wholly inside
    one span named ``name``. For ``step.run``, what the two clocks put
    outside the steps: where they agree, the batch's own small operations
    in ``feed.put`` and nothing else."""
    held = sorted((s, e) for n, s, e in loop if n == name)
    starts = [s for s, _ in held]
    total = 0.0
    for op, s, d, _ in ops:
        if op.split(".")[0] in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s + d > held[i][1]:
            total += d / 1e9
    return total


def reduce_spans(ops_by_device: Dict[str, List[Op]], lines: List[List[Span]],
                 window_s: float, top: int = 10,
                 known: Optional[Iterable[str]] = None) -> Optional[dict]:
    """The window's idle time by span, device time by scope (inclusive) and
    by span, the device time outside ``step.run``, the operations with most
    time named by their innermost scope, and the longest idle pieces, each a
    mean over the devices. None when the trace holds no device operation or
    no step of the loop."""
    known = program_scopes() if known is None else frozenset(known)
    devices = {k: v for k, v in ops_by_device.items() if v}
    loop = loop_spans(lines)
    bounds = window_bounds(loop, window_s)
    if not devices or bounds is None or window_s <= 0:
        return None
    seg = Segments(loop)
    idle_by: Dict[str, float] = defaultdict(float)
    scope_by: Dict[str, float] = defaultdict(float)
    span_by: Dict[str, float] = defaultdict(float)
    per_op: Dict[str, float] = defaultdict(float)
    op_scope: Dict[str, str] = {}
    pieces, off_step = [], 0.0
    n = len(devices)
    for ops in devices.values():
        busy = busy_intervals(ops)
        for a, b in idle_intervals(busy, *bounds):
            for label, g in seg.label(a, b):
                pieces.append((label, g))
                idle_by[label] += g / n
        off_step += outside(ops, loop, "step.run") / n
        for name, s, d, path in ops:
            if name.split(".")[0] in CONTAINERS:
                continue      # their operations count on their own
            on = scopes_on(path, known) or [NO_SCOPE]
            op_scope[name] = on[-1]
            for scope in on:
                scope_by[scope] += d / 1e9 / n
            span_by[seg.at(s)] += d / 1e9 / n
            per_op[name] += d / 1e9 / n
    return {"window_s": window_s,
            "idle_by_span": dict(idle_by),
            "idle_unattributed_share": idle_by.get(NO_SPAN, 0.0) / window_s,
            "device_by_scope": dict(scope_by),
            "device_by_span": dict(span_by),
            "device_outside_step_run_s": off_step,
            "device_ops": [[f"{op_scope[name]}/{name}", t] for name, t in
                           sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label, g] for label, g in
                          sorted(pieces, key=lambda p: -p[1])[:top]]}


def reduce_trace(xplane_path: str, op_names: Dict[str, Dict[str, str]],
                 window_s: float) -> Optional[dict]:
    """``bench.trace.reduce``'s busy time and idle share of a traced window,
    and ``reduce_spans`` over it where the trace holds the loop's spans.
    None when the trace holds no device operation."""
    ops = trace_mod.device_ops(xplane_path)
    out = trace_mod.reduce(ops, window_s)
    if out is not None:
        out.update(reduce_spans(with_op_names(ops, op_names),
                                host_spans(xplane_path), window_s) or {})
    return out


def scope_ms(run, scope: str) -> Optional[float]:
    """Milliseconds of device time a window step under ``scope``, from the
    run's traced window; None where no operation ran under it. Raises where
    ``scope`` is no scope the program opens, so that a reader whose scope
    the program does not name fails rather than reads nothing."""
    if scope not in program_scopes():
        raise KeyError(f"{scope!r} is not among the program's named scopes "
                       f"{sorted(program_scopes())}")
    t = (run.trace or {}).get("device_by_scope", {}).get(scope)
    return None if t is None or not run.step_s else 1000.0 * t / len(run.step_s)


# ---------------------------------------------------------------------------
# the program's own record of its spans, for the per-layer readers
# ---------------------------------------------------------------------------

def recorded(name: str) -> List[Interval]:
    """``(start, end)`` in seconds of the latest spans named ``name`` that
    the program ran in this process (``repro.core.metrics.recent_spans``);
    empty where the program keeps no such record."""
    try:
        from repro.core import metrics
    except ImportError:
        return []
    recent = getattr(metrics, "recent_spans", None)
    return [] if recent is None else recent(name)


def last(name: str, n: int) -> List[float]:
    """The lengths in seconds of the last ``n`` spans named ``name``; empty
    unless there are ``n``."""
    spans = recorded(name)
    if n <= 0 or len(spans) < n:
        return []
    return [e - s for s, e in spans[-n:]]


def window_start(run) -> Optional[float]:
    """Where the window's first step starts: its ``feed.get``."""
    spans = recorded("feed.get")
    n = len(run.step_s)
    return spans[-n][0] if 0 < n <= len(spans) else None
