"""Reduction of a profiler trace of the measured window to device metrics.

The trace is JAX's ``.xplane.pb``. A device plane is named ``/device:<kind>:<n>``
(TPU and GPU alike); its line ``XLA Ops`` holds one event per operation that
ran on that device, with a start and a duration in nanoseconds, and its line
``XLA Modules`` one event per run of a program. Busy time is the union of
the operations' intervals; the idle share is one minus busy over the
window's length on the host clock. The top operations and the idle gaps,
named by scope and by loop span, are ``bench/spans.py``'s.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]          # start_ns, end_ns


def device_ops(xplane_path: str
               ) -> Dict[str, List[Tuple[str, float, float, str]]]:
    """{device plane name: [(op name, start_ns, duration_ns, module), ...]}:
    ``module`` is the program the operation ran in, the ``XLA Modules``
    event that holds the operation's start (``module_name``), or "" where
    none does."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    out: Dict[str, List[Tuple[str, float, float, str]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = sorted((float(e.start_ns), float(e.end_ns),
                          module_name(e.name))
                         for e in (lines[MODULES_LINE].events
                                   if MODULES_LINE in lines else ()))
        starts = [s for s, _, _ in modules]
        ops = []
        for e in lines[OPS_LINE].events:
            s = float(e.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            held = i >= 0 and s < modules[i][1]
            ops.append((op_name(e.name), s, float(e.duration_ns),
                        modules[i][2] if held else ""))
        out[plane.name] = ops
    return out


def module_name(event: str) -> str:
    """``jit_train_step(42)`` -> ``jit_train_step``: the program's name as
    its compiled text gives it, without the run's program id."""
    return re.sub(r"\(\d+\)$", "", event.strip())


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def busy_intervals(ops: List[tuple]) -> List[Interval]:
    """The union of the intervals of operations ``(name, start_ns,
    duration_ns, ...)``, sorted and disjoint."""
    spans = sorted((op[1], op[1] + op[2]) for op in ops if op[2] > 0)
    merged: List[Interval] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def reduce(ops_by_device: Dict[str, List[tuple]],
           window_s: float) -> Optional[dict]:
    """busy_s (mean over devices), the window's length and the idle share.
    None when the trace holds no device operation."""
    devices = {k: v for k, v in ops_by_device.items() if v}
    if not devices or window_s <= 0:
        return None
    busy_s = sum(sum(e - s for s, e in busy_intervals(ops)) / 1e9
                 for ops in devices.values()) / len(devices)
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s}
