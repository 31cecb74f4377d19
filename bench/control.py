#!/usr/bin/env python3
"""Readings of the correctness comparison's control and planted faults.

    python bench/control.py --config <name> --seeds 1 2 3 [--modes fp8 half_batch]

For each seed, the reference's first training steps are computed once in
float32 and once in each mode, put in the program's place:

* ``fp8``: the control, every matrix product computed from float8 operands,
  the precision below the configuration's bfloat16;
* ``half_batch``: the loss and gradient over half of each batch's sequences;
* ``frozen``: steps that leave the parameters as they were.

Each mode's reading is the harness's numbers (``harness.gaps``) against the
float32 reference. The benchmark's own runs do not run this; it gives the
upper readings from which the limits were chosen (PERF.md).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, reference as R  # noqa: E402


def readings(cfg: dict, seed: int, modes=("fp8", "half_batch")) -> dict:
    """{mode: {number: gap}} against the float32 reference."""
    a, t, o = harness.dims(cfg), cfg["train"], cfg["optimizer"]
    n = harness.REFERENCE_STEPS
    ref = R.reference_steps(a, t, o, seed, n)
    out = {}
    for mode in modes:
        kw = {"precision": "fp8"} if mode == "fp8" else {"fault": mode}
        out[mode] = harness.gaps(R.reference_steps(a, t, o, seed, n, **kw),
                                 ref)
    return out


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["fp8", "half_batch"])
    args = ap.parse_args()
    spec = harness.load_spec()
    cfg = harness.load_config(spec, args.config)
    sys.path.insert(0, str(harness.SRC))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    dev = harness.device_info(True, 1)
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(cfg, seed, args.modes)
        print(json.dumps({"config": args.config, "seed": seed, "device": dev,
                          "seconds": time.perf_counter() - t,
                          "readings": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
