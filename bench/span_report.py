#!/usr/bin/env python3
"""One traced run of a benchmark cell, reduced by span and by scope.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> \
        [--out <file.json>]

Runs the cell as ``bench/run.py --trace 1`` does and prints its result line;
it also keeps the run's profiler trace until it has been reduced with
``bench/spans.py``: the window's device-idle time by the train loop's span,
device time by ``jax.named_scope`` and by loop span, and the longest idle
pieces. A v5e trace does not carry the operations' ``op_name``, so the
report reads it from the train step's compiled text as the program compiles
it. The reduction goes to stderr as a ``[spans]`` line, and to ``--out``
when given.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, spans  # noqa: E402
from bench import trace as trace_mod  # noqa: E402


def traced_run(workload: str, seed: int, seconds: float, **run_kw):
    """``harness.run_cell`` with the trace on. Returns the result line's
    object and the reduction of its trace (None where the trace holds no
    device operation or no step of the loop)."""
    import jax
    kept = tempfile.mkdtemp(prefix="span_report_")
    found, op_names = [], {}
    find, compile_ = trace_mod.find_xplane, jax.stages.Lowered.compile

    def find_and_keep(log_dir):
        path = find(log_dir)
        if path is not None:
            found.append(shutil.copy(path, kept))
        return path

    def compile_and_read_names(self, *args, **kwargs):
        # run_training compiles its train step ahead of time, and is the
        # only caller here that does
        compiled = compile_(self, *args, **kwargs)
        op_names.update(spans.hlo_op_names(compiled.as_text()))
        return compiled

    trace_mod.find_xplane = find_and_keep
    jax.stages.Lowered.compile = compile_and_read_names
    try:
        result = harness.run_cell(workload, seed, seconds, True, **run_kw)
        window_s = result["device"].get("window_s")
        if not found or window_s is None:
            return result, None
        report = spans.reduce_spans(
            spans.device_ops_named(found[0], op_names),
            spans.host_spans(found[0]), window_s)
        if report is not None:
            report["busy_s"] = result["device"]["busy_s"]
        return result, report
    finally:
        trace_mod.find_xplane = find
        jax.stages.Lowered.compile = compile_
        shutil.rmtree(kept, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    result, report = traced_run(args.workload, args.seed, args.seconds)
    print(json.dumps(result), flush=True)
    if report is None:
        print("[spans] no device trace", file=sys.stderr)
        return 1
    report["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    line = json.dumps(report)
    print("[spans] " + line, file=sys.stderr, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
