"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

* **window** — the host annotation :data:`WINDOW` the harness puts
  around the measured loop; everything is clipped to it.
* **busy** — the union of the intervals of the ``XLA Ops`` line of each
  device plane (``/device:TPU:<n>``), averaged over the devices that ran
  anything; idle share is ``1 - busy / window``.
* **device_ops** — the ten op names (HLO name before `` = ``) with the
  most device time in the window.
* **idle_gaps** — the device's idle time in the window by what the host
  was doing meanwhile: each idle instant goes to the innermost host
  event or program span covering it; summed per name, the ten largest.

The program's spans (:mod:`repro.obs`, Chrome events on the
``perf_counter`` clock) are placed on the profiler's clock through the
``WINDOW`` instant the harness records at the same moment as the
profiler annotation starts.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
TOP = 10
# Gaps this short lie between the ops of one program; naming each by a
# host event would only cost time.
SHORT_GAP_NS = 10_000
SHORT_GAP = "gaps under 10 us"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(iv: Interval, win: Interval) -> Optional[Interval]:
    a, b = max(iv[0], win[0]), min(iv[1], win[1])
    return (a, b) if b > a else None


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_profile(planes, spans: Sequence[dict] = ()) -> Optional[dict]:
    """The reduction of a trace's planes (``ProfileData.planes``)."""
    win = None
    host: List[Tuple[float, float, str]] = []
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in planes:
        if plane.name.startswith("/host"):
            for name, a, b in _events(plane):
                if name == WINDOW:
                    win = (a, b)
                else:
                    host.append((a, b, name))
        elif _DEVICE.match(plane.name):
            ops = [(op_name(n), a, b) for n, a, b in
                   _events(plane, "XLA Ops")]
            if ops:
                devices[plane.name] = ops
    if win is None or not devices:
        return None
    host += _span_events(spans, win[0])

    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for ops in devices.values():
        clipped = []
        for name, a, b in ops:
            c = clip((a, b), win)
            if c:
                clipped.append(c)
                op_time[name] += (c[1] - c[0]) / 1e9
        busy = union(clipped)
        busy_total += sum(b - a for a, b in busy)
        edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    return {
        "window_s": (win[1] - win[0]) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "devices": n,
        "device_ops": _top(op_time),
        "idle_gaps": _top(_attribute(gaps, host, win)),
    }


def _top(times: Dict[str, float]) -> List[list]:
    return [[k, v] for k, v in
            sorted(times.items(), key=lambda kv: -kv[1])[:TOP]]


def _span_events(spans: Sequence[dict], win_start_ns: float):
    """The program's complete spans, on the profiler's clock."""
    anchor = next((e["ts"] for e in spans
                   if e.get("ph") == "i" and e.get("name") == WINDOW), None)
    if anchor is None:
        return []
    off = win_start_ns - anchor * 1e3
    return [(e["ts"] * 1e3 + off, (e["ts"] + e["dur"]) * 1e3 + off,
             e["name"]) for e in spans if e.get("ph") == "X"]


def _attribute(gaps: List[Interval], host, win: Interval
               ) -> Dict[str, float]:
    """Idle seconds per host activity: each instant of a gap goes to the
    innermost (shortest) host event or program span covering it, else to
    ``host idle``; gaps under :data:`SHORT_GAP_NS` are summed under one
    name. Host events as long as the window say nothing and are left
    out."""
    span = win[1] - win[0]
    ev = sorted((a, b, name) for a, b, name in host if b - a < span)
    starts = [e[0] for e in ev]
    longest = max((b - a for a, b, _ in ev), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            out[SHORT_GAP] += (g1 - g0) / 1e9
            continue
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_left(starts, g1)
        cover = [(max(a, g0), min(b, g1), b - a, name)
                 for a, b, name in ev[lo:hi] if min(b, g1) > max(a, g0)]
        cuts = sorted({g0, g1, *(c[0] for c in cover),
                       *(c[1] for c in cover)})
        for s0, s1 in zip(cuts, cuts[1:]):
            inside = [c for c in cover if c[0] <= s0 and c[1] >= s1]
            name = (min(inside, key=lambda c: c[2])[3] if inside
                    else "host idle")
            out[name] += (s1 - s0) / 1e9
    return out


def reduce_dir(log_dir: str, spans: Sequence[dict] = ()) -> Optional[dict]:
    """Reduce the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    return reduce_profile(ProfileData.from_file(str(files[-1])).planes,
                          spans)
