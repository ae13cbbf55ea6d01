"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

- The window is the host event named ``WINDOW``: the harness wraps the
  measured window in a ``jax.profiler.TraceAnnotation`` of that name,
  so the window and the device events are on the trace's own clock.
- Busy time is the union of the intervals in which an operation ran on
  a device, from each device plane's ``XLA Ops`` line, clipped to the
  window and averaged over the devices.
- The top device operations are summed over all devices by family: the
  HLO instruction name without its numeric suffix (``%fusion.12`` and
  ``%fusion.7`` are both ``%fusion``).
- Idle gaps are the complement of the first device's busy union in the
  window.  Each is labelled by the requests in flight at its midpoint,
  from the harness's own records (seconds from the window's start,
  which the ``WINDOW`` annotation places on the trace's clock), or
  ``no query in flight``.

Run as a script on a trace file to print the reduction as JSON.
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
# "%fusion.12 = f32[...] fusion(...)" -> "%fusion": ops of one family add up
_FAMILY = re.compile(r"^(%?[A-Za-z_][\w\-]*?)(\.\d+)*( = |$)")
Interval = Tuple[float, float]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def reduce_planes(planes, inflight: Sequence[Tuple[float, float, str]] = (),
                  top: int = 10) -> Optional[Dict]:
    """``planes``: objects with ``.name`` and ``.lines``, each line with
    ``.name`` and ``.events`` (``.name``, ``.start_ns``, ``.duration_ns``),
    as ``jax.profiler.ProfileData`` gives them.  ``inflight``: the
    requests as ``(start s, end s, name)`` from the window's start.
    Returns None when the trace holds no window or no device operation."""
    window = None
    devices: List[List[Tuple[float, float, str]]] = []
    for plane in planes:
        if _is_device(plane.name):
            line = next((ln for ln in plane.lines if ln.name == OP_LINE), None)
            if line is not None:
                devices.append([
                    (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name)
                    for ev in line.events
                ])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
    devices = [d for d in devices if d]
    if window is None or not devices:
        return None
    w0, w1 = window
    busy = []
    op_time: Dict[str, float] = {}
    first = None
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events if e > w0 and s < w1]
        for s, e, n in clipped:
            m = _FAMILY.match(n)
            fam = m.group(1) if m else n
            op_time[fam] = op_time.get(fam, 0.0) + (e - s) * 1e-9
        union = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if first is None:
            first = union
    gaps = []
    prev = w0
    for s, e in first + [(w1, w1)]:
        if s > prev:
            mid = (0.5 * (prev + s) - w0) * 1e-9
            names = sorted({n for a, b, n in inflight if a <= mid <= b})
            gaps.append(("+".join(names) if names else "no query in flight", (s - prev) * 1e-9))
        prev = max(prev, e)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "device_ops": [[n, s] for n, s in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(gaps, key=lambda g: -g[1])[:top]],
    }


def reduce_file(path: str, inflight: Sequence[Tuple[float, float, str]] = (),
                top: int = 10) -> Optional[Dict]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, inflight, top)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
