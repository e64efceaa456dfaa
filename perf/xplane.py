"""From a profiler trace (``.xplane.pb``) to device busy time and the
operations that took it. Read with ``jax.profiler.ProfileData`` alone.

A device plane is one named ``/device:TPU:<n>``. Its line "XLA Ops" holds one
event per executed operation (the other lines, "XLA Modules" and "Steps",
cover the same time again at a coarser grain, so they are not added in).
Busy time is the union of the op intervals; the window is the extent of all
events of all planes, host threads included, so a device that sat idle at
either end of the trace is counted idle there."""

from __future__ import annotations

from pathlib import Path
from typing import Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals, in their unit."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def find_trace(trace_dir: Path) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def reduce(profile, top: int = 10) -> Optional[dict]:
    """``profile`` is a ``ProfileData``. Returns None where no device plane
    holds an operation; else ``busy_s`` and ``idle_share`` as means over the
    device planes, ``window_s``, and ``device_ops``: the ``top`` operations by
    total device time, summed over the planes."""
    lo, hi = None, None
    per_device: List[float] = []
    by_op: dict = {}
    for plane in profile.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = list(plane.lines)
        op_lines = [ln for ln in lines if ln.name == OPS_LINE] if is_device else []
        if is_device and not op_lines:
            op_lines = lines  # a runtime that names its lines otherwise: take them all
        intervals = []
        op_line_ids = {id(ln) for ln in op_lines}
        for line in lines:
            keep = id(line) in op_line_ids
            for event in line.events:
                start, end = event.start_ns, event.start_ns + event.duration_ns
                lo = start if lo is None else min(lo, start)
                hi = end if hi is None else max(hi, end)
                if keep:
                    intervals.append((start, end))
                    by_op[event.name] = by_op.get(event.name, 0.0) + event.duration_ns
        if is_device and intervals:
            per_device.append(union_seconds(intervals) * 1e-9)
    if not per_device or hi is None or hi <= lo:
        return None
    window_s = (hi - lo) * 1e-9
    busy_s = sum(per_device) / len(per_device)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s, "window_s": window_s, "idle_share": 1.0 - busy_s / window_s,
        "devices": len(per_device), "device_ops": [[name, ns * 1e-9] for name, ns in ops],
    }


def reduce_file(path: Path, top: int = 10) -> Optional[dict]:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)), top)
