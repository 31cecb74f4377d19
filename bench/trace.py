"""Reduction of a profiler trace of the measured window to device metrics.

The trace is JAX's ``.xplane.pb``. A device plane is named ``/device:<kind>:<n>``
(TPU and GPU alike); its line ``XLA Ops`` holds one event per operation that
ran on that device, with a start and a duration in nanoseconds. Busy time is
the union of those intervals; the idle share is one minus busy over the
window's length on the host clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
# operations that contain others on the same line: they count towards busy
# time, but their own totals would hide the operations inside them
CONTAINERS = ("while", "conditional", "call")
Interval = Tuple[float, float]          # start_ns, end_ns


def device_ops(xplane_path: str) -> Dict[str, List[Tuple[str, float, float]]]:
    """{device plane name: [(op name, start_ns, duration_ns), ...]}."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(op_name(e.name), float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events]
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def busy_intervals(ops: List[Tuple[str, float, float]]) -> List[Interval]:
    """The union of the operations' intervals, sorted and disjoint."""
    spans = sorted((s, s + d) for _, s, d in ops if d > 0)
    merged: List[Interval] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def reduce(ops_by_device: Dict[str, List[Tuple[str, float, float]]],
           window_s: float, top: int = 10) -> Optional[dict]:
    """busy_s (mean over devices), idle share, the operations that took most
    device time and the longest gaps between busy intervals. None when the
    trace holds no device operation."""
    devices = {k: v for k, v in ops_by_device.items() if v}
    if not devices or window_s <= 0:
        return None
    busy, per_op, gaps = [], defaultdict(float), []
    for ops in devices.values():
        iv = busy_intervals(ops)
        busy.append(sum(e - s for s, e in iv) / 1e9)
        for name, _, d in ops:
            if name.split(".")[0] not in CONTAINERS:
                per_op[name] += d / 1e9
        gaps += [("host", (b[0] - a[1]) / 1e9) for a, b in zip(iv, iv[1:])]
        span = (iv[-1][1] - iv[0][0]) / 1e9
        # the window outside its first..last operation: the wait for the
        # first batch and the final checkpoint save lie there
        gaps.append(("host, before the first or after the last device op",
                     max(window_s - span, 0.0)))
    busy_s = sum(busy) / len(busy)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, s / len(devices)] for n, s in ops_top],
            "idle_gaps": [[label, g] for label, g in gaps_top]}
