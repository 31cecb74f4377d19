#!/usr/bin/env python3
"""One traced run of a benchmark cell, reduced by span and by scope.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> \
        [--out <file.json>]

Runs the cell as ``bench/run.py --trace 1`` does and prints its result line,
and the whole reduction of its trace that the run made (``bench/spans.py``
``reduce_trace``): the window's device-idle time by the train loop's span,
device time by ``jax.named_scope`` and by loop span, the top operations and
the longest idle pieces. The reduction goes to stderr as a ``[spans]``
line, and to ``--out`` when given.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def traced_run(workload: str, seed: int, seconds: float, **run_kw):
    """``harness.run_cell`` with the trace on. Returns the result line's
    object and the reduction of its trace (None where the trace holds no
    device operation)."""
    result, record = harness.run_cell(workload, seed, seconds, True, **run_kw)
    return result, None if record is None else record.trace


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
