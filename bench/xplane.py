"""Reduce a JAX profiler trace (`.xplane.pb`) to device busy and idle time.

What is read:

  window    the host span named `WINDOW` (a `jax.profiler.TraceAnnotation`
            the harness puts around the measured window); everything
            below is clipped to it.
  device    every plane named `/device:TPU:<i>`; on each, the events of
            its "XLA Ops" line (one per operation the chip ran).  Busy
            time is the union of their intervals, averaged over the
            devices; idle is the rest of the window.
  ops       device self time per operation, summed over devices: an
            event's duration less that of the events nested in it on the
            same line (a `while` holds the kernels of its body), keyed by
            the HLO name without its `%` and numeric suffix, so
            "%_fused_jit.22 = (...) custom-call(...)" counts as
            "_fused_jit".
  gaps      each idle interval of device 0 is charged to the innermost
            host span named `bench.*` that covers its midpoint (what the
            host was doing while the chip waited), or to "(none)".
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def short_name(hlo: str) -> str:
    """"%fusion.112 = f32[...] fusion(...)" -> "fusion"."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _self_times(events: List[Tuple[int, int, str]]):
    """(start, end, name, self ns) per event of one line, where an event
    that lies inside an earlier one is its child."""
    out = []
    stack: List[list] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][1]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= e - s
        stack.append([s, e, name, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_planes(planes) -> dict:
    """The reduction over planes shaped like `ProfileData.planes`: each
    with `.name` and `.lines`, each line with `.name` and `.events`, each
    event with `.name`, `.start_ns` and `.duration_ns`."""
    window = None
    host_spans: List[Tuple[int, int, str]] = []
    devices: Dict[int, List[Tuple[int, int, str]]] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if m is not None:
                    devices.setdefault(int(m.group(1)), []).append(
                        (s, e, ev.name))
                elif ev.name == WINDOW:
                    window = (s, e)
                elif ev.name.startswith(HOST_PREFIX):
                    host_spans.append((s, e, ev.name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    if not devices:
        raise ValueError("trace has no /device:TPU:<i> plane with "
                         f"{OPS_LINE!r} events")
    lo, hi = window
    ops: Dict[str, float] = {}
    busy_ns = []
    busy0: List[Tuple[int, int]] = []
    for dev in sorted(devices):
        clipped = []
        for s, e, name, own in _self_times(devices[dev]):
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            clipped.append(c)
            key = short_name(name)     # self time, in the window's share
            ops[key] = (ops.get(key, 0.0)
                        + own * (c[1] - c[0]) / (e - s) * 1e-9)
        merged = _union(clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        if not busy0 and merged:
            busy0 = merged
    gaps: Dict[str, float] = {}
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) // 2
        covering = [(hs, he, n) for hs, he, n in host_spans
                    if hs <= mid < he]
        name = (min(covering, key=lambda x: x[1] - x[0])[2]
                if covering else "(none)")
        gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "devices": len(devices), "ops": ops, "idle_gaps": gaps}


def reduce_file(path: str) -> dict:
    """Reduce an `.xplane.pb` file (or its gzip, `.xplane.pb.gz`)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return reduce_planes(
                ProfileData.from_serialized_xspace(f.read()).planes)
    return reduce_planes(ProfileData.from_file(path).planes)


def top(d: Dict[str, float], k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
