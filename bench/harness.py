"""One run of one benchmark cell: set-up, the measured window, the metrics,
and the comparison with the plain reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as run, its published
  values, its ``period`` of layer parts, its training settings, the sizing
  numbers that turn ``--seconds`` into a number of steps, and the limits of
  the correctness comparison;
* ``bench/layers/<part>.py``: one mixer or FFN that a ``period`` names, with
  the keys it reads, the program's ``ArchConfig`` fields it stands for, its
  seeded leaves, its float32 forward pass and its FLOPs (see
  ``bench/layers/__init__.py``);
* ``bench/traffic/<traffic>.json``: the training job's arrivals;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None`` of
  one metric from the run's record (see ``RunRecord``); a reader with
  nothing to read returns None, and the run leaves that metric out.

A run is one call of the program's own entry, ``repro.launch.train.
run_training``, in this process. Set-up builds the train state and the
compiled step and drives them through the first ``REFERENCE_STEPS`` steps
from the LOG.io feed; the window takes over the same call at the next step
and ends when the call returns, after its final checkpoint save. Observers
are installed around the program without changing it: a subclass of the
feed sink whose hand-off queue notes a digest of every batch the train loop
takes (and, before the first step, copies the seeded weights to the host),
and a stream for the loop's printed step log that copies the train state to
the host at the step lines the comparison needs and marks the window's
start. The comparison's readings are worked out from those copies after the
window. A traced run also keeps the train step's ``op_name``s as it is
compiled, and reduces the window's trace by loop span and by scope
(``bench/spans.py``) before it deletes it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import io
import json
import math
import os
import queue
import re
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional, Tuple, get_args, get_type_hints

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
REFERENCE_STEPS = 2          # set-up's steps, which the reference follows
STEP_LINE = re.compile(r"step\s+(\d+) loss (\S+) gnorm (\S+)")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str) -> dict:
    return load_json(ROOT / find(spec["configs"], name, "config")["file"])


def load_traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def dims(cfg: dict) -> dict:
    """The configuration's shapes under one set of keys, for the reference
    and the FLOP count: the common keys, ``period`` as (mixer, ffn) names,
    and the keys that the period's parts read."""
    from bench import layers
    period = [(p["mixer"], p["ffn"]) for p in cfg["period"]]
    if not period or cfg["num_hidden_layers"] % len(period):
        raise ValueError(f"bench: {cfg['name']}: num_hidden_layers "
                         f"{cfg['num_hidden_layers']} is not a multiple of "
                         f"the period's {len(period)} layers")
    a = {k: cfg[k] for k in ("hidden_size", "num_hidden_layers", "vocab_size",
                             "tie_word_embeddings")}
    a["rms_norm_eps"] = cfg.get("rms_norm_eps", cfg.get("layer_norm_epsilon"))
    a["period"] = period
    for pair in layers.period(a):
        for part in pair:
            a.update({k: cfg[k] for k in part.KEYS})
    return a


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for the configuration file: ``block`` is
    the period, and each part sets the fields it stands for. Parts that set
    ``family`` differently make the model a ``hybrid``."""
    from repro.configs.base import ArchConfig, LayerSpec
    from bench import layers
    a = dims(cfg)
    fields, block = {}, []
    for mixer, ffn in layers.period(a):
        block.append(LayerSpec(mixer=mixer.SPEC, ffn=ffn.SPEC))
        for part in (mixer, ffn):
            for k, v in part.arch_fields(a).items():
                if fields.get(k, v) != v:
                    if k != "family":
                        raise ValueError(f"bench: {cfg['name']}: layer parts"
                                         f" set {k} to {fields[k]!r} and "
                                         f"{v!r}")
                    v = "hybrid"
                fields[k] = v
    hints = get_type_hints(ArchConfig)
    for k, v in fields.items():
        if isinstance(v, dict):        # a nested spec, such as ``mamba``
            fields[k] = next(t for t in get_args(hints[k])
                             if dataclasses.is_dataclass(t))(**v)
    return ArchConfig(name=cfg["name"], n_layers=a["num_hidden_layers"],
                      d_model=a["hidden_size"], vocab=a["vocab_size"],
                      norm_eps=a["rms_norm_eps"],
                      tie_embeddings=cfg["tie_word_embeddings"],
                      source=cfg["source"], block=tuple(block),
                      **{"n_heads": 0, "n_kv_heads": 0, "d_head": 0, "d_ff": 0,
                         **fields})


def config_departures(cfg: dict, settings) -> List[str]:
    """Where the program would run otherwise than the file states: its
    training preset and its optimizer's defaults."""
    from repro.training.optimizer import OptHParams
    out = []
    t = cfg["train"]
    for k in ("param_dtype", "moment_dtype", "grad_accum_dtype", "remat"):
        if getattr(settings, k) != t[k]:
            out.append(f"train.{k}: program {getattr(settings, k)!r}, "
                       f"file {t[k]!r}")
    hp = OptHParams()
    for k in ("b1", "b2", "eps", "weight_decay", "clip_norm"):
        if getattr(hp, k) != cfg["optimizer"][k]:
            out.append(f"optimizer.{k}: program {getattr(hp, k)!r}, "
                       f"file {cfg['optimizer'][k]!r}")
    return out


def plan_steps(seconds: float, sizing: dict, traffic: dict) -> int:
    """The step the run trains up to, from ``--seconds`` and the
    configuration's sizing numbers (seconds per step and per save, measured
    on the chip). Set-up runs the first ``REFERENCE_STEPS`` steps; the window
    holds the rest and the final save."""
    n = math.floor((seconds - sizing["save_s"]) / sizing["step_s"])
    return REFERENCE_STEPS + max(n, traffic["min_window_steps"])


def batch_digest(tokens) -> str:
    import numpy as np
    t = np.ascontiguousarray(tokens, dtype=np.int32)
    return hashlib.sha256(repr(t.shape).encode() + t.tobytes()).hexdigest()


def process_age_s() -> float:
    """Seconds since this process started, from /proc; 0 where unknown."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read: the window's numbers."""
    setup_s: float
    window_s: float
    tokens_per_step: int
    step_s: List[float]        # the train steps run in the window
    save_s: List[float]
    flops_per_step: float
    peak_flops_per_s: float
    trace: Optional[dict] = None      # bench.spans.reduce_trace's reduction


def training_state():
    """The train state of ``run_training``'s loop, read from its frame while
    the loop waits on an observer: the program has no hook between steps."""
    from repro.launch.train import run_training
    f = sys._getframe(1)
    while f is not None and f.f_code is not run_training.__code__:
        f = f.f_back
    return f.f_locals["state"]


class StepLog(io.StringIO):
    """``run_training``'s printed log. Calls ``on_step(step)`` as a step's
    line is written, before the loop goes on to its next batch."""

    def __init__(self, on_step):
        super().__init__()
        self.on_step = on_step

    def write(self, s):
        n = super().write(s)
        m = STEP_LINE.match(s)
        if m:
            self.on_step(int(m.group(1)))
        return n


def state_readers(b1: float):
    """Readings of the program's train state, per leaf: the norm of the
    stored first moment over ``1 - b1`` (after one step: the gradient as the
    optimizer got it) and the norm of the parameters' change since the
    seeded weights, the loop's own before its first step.

    In set-up the states are only copied to the host (``copy``), leaf by
    leaf as the program's checkpoint save copies its state, so that the
    readings hold no buffer on the device beside the program's. ``read``
    works the norms out on the device once the window has closed and the
    peak memory has been read.

    Returns ``(copy, read)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.training import quant
    from bench.reference import diff_norms, flat_leaves

    @jax.jit
    def moment_norms(m):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            quant.dequant(x) if quant.is_qtensor(x) else x.astype(jnp.float32)
        ))) / (1.0 - b1) for k, x in flat_leaves(m).items()}

    change_norms = jax.jit(diff_norms)

    def floats(d):
        return {k: float(v) for k, v in jax.device_get(d).items()}

    def read(init, m1, p1, p2) -> Dict[str, Dict[str, float]]:
        init = jax.device_put(init)
        return {"grad_leaf": floats(moment_norms(jax.device_put(m1))),
                "change1_leaf": floats(change_norms(jax.device_put(p1), init)),
                "change_last_leaf": floats(change_norms(jax.device_put(p2),
                                                        init))}

    return (lambda tree: jax.tree.map(np.asarray, tree)), read


class Probes:
    """Observers installed around the program for one run."""

    def __init__(self, batch_size: int, readers, on_window=None):
        self.batch_size = batch_size
        self.copy, self._read = readers
        self.on_window = on_window
        self.window_start: Optional[float] = None
        self.batches: List[str] = []
        self.compiles: List[float] = []
        # host copies: the seeded weights, the first moment and the weights
        # after step 1, the weights after the last of set-up's steps
        self.copies: Dict[str, object] = {}
        self.grad_leaf: Optional[Dict[str, float]] = None
        self.change1_leaf: Optional[Dict[str, float]] = None
        self.change_last_leaf: Optional[Dict[str, float]] = None
        self._undo = []
        self._listener = None

    def on_step(self, step: int):
        if step == 1:
            state = training_state()
            self.copies["m1"] = self.copy(state["opt"]["m"])
            self.copies["p1"] = self.copy(state["params"])
        if step == REFERENCE_STEPS:
            self.copies["p2"] = self.copy(training_state()["params"])
            if self.on_window is not None:
                self.on_window()
            self.window_start = time.perf_counter()

    def install(self):
        import jax
        import repro.data.pipeline as pipeline_mod
        probes = self

        class RecordingQueue(queue.Queue):
            def get(self, *args, **kwargs):
                if "init" not in probes.copies:
                    # the loop asks for its first batch: the seeded weights
                    probes.copies["init"] = probes.copy(
                        training_state()["params"])
                item = super().get(*args, **kwargs)
                probes.batches.append(
                    batch_digest(item[1]["tokens"][:probes.batch_size]))
                return item

        class RecordingSink(pipeline_mod.TrainFeedSink):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                self.buffer = RecordingQueue(maxsize=self.buffer.maxsize)

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                probes.compiles.append(time.perf_counter())

        self._listener = on_event
        self._undo = [(pipeline_mod, "TrainFeedSink",
                       pipeline_mod.TrainFeedSink)]
        pipeline_mod.TrainFeedSink = RecordingSink
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def read(self):
        """The readings from the host copies, once the window has closed;
        the copies go."""
        c, self.copies = self.copies, {}
        if {"init", "m1", "p1", "p2"} <= set(c):
            r = self._read(c["init"], c["m1"], c["p1"], c["p2"])
            self.grad_leaf = r["grad_leaf"]
            self.change1_leaf = r["change1_leaf"]
            self.change_last_leaf = r["change_last_leaf"]

    def uninstall(self):
        from jax._src import monitoring
        for mod, attr, val in self._undo:
            setattr(mod, attr, val)
        self._undo = []
        if self._listener is not None:
            monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None


def parse_step_log(text: str) -> List[tuple]:
    """(step, loss, grad norm) of every step that ``run_training`` logged."""
    return [(int(s), float(l), float(g)) for s, l, g in STEP_LINE.findall(text)]


def device_info(require_accelerator: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_accelerator and (dev["platform"] == "cpu"
                                or dev["count"] < chips):
        raise SystemExit(f"bench: the cell needs {chips} accelerator chip(s); "
                         f"JAX found {dev['count']} {dev['platform']} "
                         f"device(s). Nothing was run.")
    return dev


def peak_flops(kind: str, require_accelerator: bool) -> float:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind in peaks:
        return peaks[kind]["bf16_flops_per_s"]
    if require_accelerator:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json")
    return float("nan")


def unmoved_leaves(params, a: dict, seed: int, dtype) -> int:
    """Leaves of the trained parameters still equal, element for element, to
    the seeded weights (regenerated by the reference): after the run's steps
    every leaf has a gradient and should have moved."""
    import jax.numpy as jnp
    from bench import reference as ref_mod
    init = ref_mod.flat_leaves(ref_mod.init_params(seed, a, dtype))
    got = ref_mod.flat_leaves(params)
    if set(got) != set(init):
        return len(set(got) ^ set(init))
    return sum(bool(jnp.array_equal(got[k], init[k])) for k in init)


def leaf_gap(got: Dict[str, float], ref: Dict[str, float],
             keep=None) -> float:
    """The worst leaf's gap between two norms, over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    keys = [k for k in ref if keep is None or k in keep]
    med = sorted(ref[k] for k in keys)[len(keys) // 2]
    if set(got) != set(ref):
        return math.inf
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in keys)


def moving_leaves(ref: dict) -> List[str]:
    """Leaves that count in the change: those whose first gradient in the
    reference is at least a thousandth of the median leaf's. A leaf with no
    gradient beyond rounding moves under Adam by round-off alone."""
    g = ref["grad_leaf"]
    med = sorted(g.values())[len(g) // 2]
    return [k for k, v in g.items() if v >= 1e-3 * med]


def gaps(got: dict, ref: dict) -> Dict[str, float]:
    """The numbers that compare a training run with the reference. Both
    hold each step's ``loss`` and ``gnorm`` and the per-leaf ``grad_leaf``,
    ``change1_leaf`` and ``change_last_leaf`` (see
    ``reference.reference_steps``)."""
    n = len(ref["loss"])
    moving = moving_leaves(ref)
    out = {"loss_gap": max(abs(got["loss"][i] - ref["loss"][i])
                           for i in range(n)),
           "gnorm_gap": abs(got["gnorm"][0] - ref["gnorm"][0]) / ref["gnorm"][0],
           "grad_leaf_gap": leaf_gap(got["grad_leaf"], ref["grad_leaf"])}
    for k in ("change1", "change_last"):
        out[k + "_leaf_gap"] = leaf_gap(got[k + "_leaf"], ref[k + "_leaf"],
                                        moving)
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def compare(cfg: dict, a: dict, seed: int, log: List[tuple],
            losses: List[float], batches: List[str], state_bad: int,
            departures: List[str], probes: Probes) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit. A number
    that the configuration gives no limit is reported and not compared."""
    from bench import reference as ref_mod
    lim = cfg["limits"]
    t = cfg["train"]
    steps = [s for s, _, _ in log]
    checks = {"config_departures": (len(departures), 0),
              "state_mismatches": (state_bad, lim["state_mismatches"])}
    expect = [batch_digest(ref_mod.corpus_batch(
        seed, s, a["vocab_size"], t["seq_len"], t["batch_size"]))
        for s in steps]
    bad = sum(x != y for x, y in zip(expect, batches))
    bad += abs(len(expect) - len(batches)) + abs(len(steps) - len(losses))
    checks["feed_mismatches"] = (bad, lim["feed_mismatches"])
    first = dict(zip(steps, losses))
    gnorm = {s: g for s, _, g in log}
    n = REFERENCE_STEPS
    missing = sum(k not in first for k in range(1, n + 1)) + sum(
        x is None for x in (probes.grad_leaf, probes.change_last_leaf))
    if missing:
        checks["readings_missing"] = (missing, 0)
        return {k: {"value": v, "limit": lv} for k, (v, lv) in checks.items()}
    ref = ref_mod.reference_steps(a, t, cfg["optimizer"], seed, n)
    got = {"loss": [first[k] for k in range(1, n + 1)],
           "gnorm": [gnorm[k] for k in range(1, n + 1)],
           "grad_leaf": probes.grad_leaf, "change1_leaf": probes.change1_leaf,
           "change_last_leaf": probes.change_last_leaf}
    print("[compare] " + json.dumps({"program": got, "reference": ref}),
          file=sys.stderr, flush=True)
    for k, v in gaps(got, ref).items():
        checks[k] = (v, lim.get(k))
    return {k: {"value": v, "limit": lv} for k, (v, lv) in checks.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, spec: Optional[dict] = None,
             t_start: Optional[float] = None
             ) -> Tuple[dict, Optional[RunRecord]]:
    """One run of one cell. Returns the result line's object and the record
    that the metrics were read from (None where the run did not finish)."""
    t_start = time.perf_counter() if t_start is None else t_start
    age0 = process_age_s()
    spec = spec or load_spec()
    cell = find(spec["workloads"], workload, "workload")
    cfg = load_config(spec, cell["config"])
    traffic = load_traffic(cell["traffic"])
    for p in (str(SRC), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    dev = device_info(require_accelerator, cell["chips"])

    import repro.configs as configs
    from repro.launch import presets
    from repro.launch.train import run_training
    from bench import spans
    from bench.flops import train_step_flops
    a = dims(cfg)
    arch = arch_config(cfg)
    configs.ARCHS[cfg["name"]] = arch
    t = cfg["train"]
    departures = config_departures(cfg, presets.ONE_CHIP_TRAIN)
    n_steps = plan_steps(seconds, cfg["sizing"], traffic)
    tokens = t["batch_size"] * t["seq_len"]
    pdtype = jax.numpy.dtype(t["param_dtype"])

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    trace_t0 = [None]

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        trace_t0[0] = time.perf_counter()

    probes = Probes(t["batch_size"], state_readers(cfg["optimizer"]["b1"]),
                    on_window=start_trace if trace else None)
    probes.install()
    stdout = StepLog(probes.on_step)
    collect = spans.compiled_op_names() if trace else contextlib.nullcontext({})
    try:
        with contextlib.redirect_stdout(stdout), collect as op_names:
            out = run_training(
                arch=cfg["name"], use_reduced=False, steps=n_steps,
                seq_len=t["seq_len"], batch_size=t["batch_size"],
                ckpt_every=10 ** 9, ckpt_dir=ckpt_dir,
                lr=cfg["optimizer"]["lr"], seed=seed, log_every=1,
                verbose=True)
        t_end = time.perf_counter()
    except Exception:
        # a run that does not finish is a run that is not correct
        traceback.print_exc()
        return {"correct": False, "attempted": len(probes.batches),
                "failed": len(probes.batches), "metrics": {}, "device": dev,
                "checks": {"run_error": {"value": 1, "limit": 0}}}, None
    finally:
        if trace_t0[0] is not None:
            jax.profiler.stop_trace()
        probes.uninstall()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    window_s = t_end - probes.window_start
    setup_s = age0 + (probes.window_start - t_start)
    timings = out["timings"]
    window_steps = list(timings["step_s"][REFERENCE_STEPS:])
    losses = list(out["losses"])
    window_compiles = sum(probes.window_start <= c <= t_end
                          for c in probes.compiles)
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    state = out["final_state"]
    del out
    state_bad = int(int(state["step"]) != n_steps) + int(
        int(state["opt"]["count"]) != n_steps)
    state_bad += unmoved_leaves(state["params"], a, seed, pdtype)
    del state
    gc.collect()
    probes.read()

    trace_summary = None
    if trace:
        from bench import trace as trace_mod
        path = trace_mod.find_xplane(trace_dir)
        if path is not None:
            trace_summary = spans.reduce_trace(path, op_names,
                                               t_end - trace_t0[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_summary is not None:
            dev["busy_s"] = trace_summary["busy_s"]
            dev["window_s"] = trace_summary["window_s"]

    log = parse_step_log(stdout.getvalue())
    record = RunRecord(
        setup_s=setup_s, window_s=window_s, tokens_per_step=tokens,
        step_s=window_steps, save_s=list(timings["save_s"]),
        flops_per_step=train_step_flops(a, t["batch_size"], t["seq_len"]),
        peak_flops_per_s=peak_flops(dev["kind"], require_accelerator),
        trace=trace_summary)

    t_ref = time.perf_counter()
    compared = compare(cfg, a, seed, log, losses, probes.batches, state_bad,
                       departures, probes)
    ref_s = time.perf_counter() - t_ref
    checks = {k: c for k, c in compared.items() if c["limit"] is not None}

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": len(losses), "failed": 0, "metrics": metrics,
              "device": dev}
    if trace_summary is not None and "device_ops" in trace_summary:
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["checks"] = checks
    info = {"steps": n_steps, "executed": len(losses), "window_s": window_s,
            "setup_s": setup_s, "reference_s": ref_s,
            "window_compiles": window_compiles,
            "not_compared": {k: c["value"] for k, c in compared.items()
                             if c["limit"] is None},
            "step_s": timings["step_s"], "save_s": timings["save_s"],
            "compile_s": timings["compile_s"], "departures": departures,
            "losses": losses}
    print("[bench] " + json.dumps(info), file=sys.stderr, flush=True)
    return result, record


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=t_start)
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
