"""Device time per step by the program's own scopes.

The program names its sublayers on the device (``repro.obs`` scopes:
``kv_cache``, ``attention``, ``pim.quantize``, ``pim.matmul``) and maps
every op of a registered program to its scope
(``repro.obs.device_scopes()``, keys ``"<module>/<op>"``; a ``while``,
``conditional`` or ``call`` is marked a container, since its body's ops
hold its time). The profiler's trace gives each op's device time but
not its scope, so the two are joined here:

* :func:`op_seconds` — the window-clipped device seconds of every op on
  the ``XLA Ops`` line, keyed ``"<module>/<op>"`` by the ``XLA
  Modules`` event that covers the op (its name before ``(``), averaged
  over the devices that ran anything, as ``busy_s`` is;
* :func:`reduce_dir` — :func:`xplane.reduce_dir` with ``op_seconds``
  added to the reduction;
* :func:`read` — one scope's device ms per step: the ``op_seconds`` of
  the non-container ops in that scope over the window's steps; the ops
  in no scope read as ``busy_s / steps`` less the four scopes, so the
  five add up to busy time per step and a missing scope shows as a
  growing remainder. Nothing without ``op_seconds``, a step count, or a
  program that maps its ops (a program without scopes).
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional, Sequence

import xplane

# Per-layer metric name -> the program's scope it reads.
SCOPE_METRICS = {
    "kv_cache_ms_per_step": "kv_cache",
    "attention_ms_per_step": "attention",
    "pim_quantize_ms_per_step": "pim.quantize",
    "pim_matmul_ms_per_step": "pim.matmul",
}
UNSCOPED_METRIC = "unscoped_device_ms_per_step"
CONTAINER = "container"


def op_seconds(planes) -> Optional[Dict[str, float]]:
    """``{"<module>/<op>": device seconds in the window}``."""
    win = None
    devices = []
    for plane in planes:
        if plane.name.startswith("/host"):
            for name, a, b in xplane._events(plane):
                if name == xplane.WINDOW:
                    win = (a, b)
        elif xplane._DEVICE.match(plane.name):
            ops = list(xplane._events(plane, "XLA Ops"))
            if ops:
                mods = sorted((a, b, name.split("(", 1)[0]) for name, a, b
                              in xplane._events(plane, "XLA Modules"))
                devices.append((ops, mods))
    if win is None or not devices:
        return None
    out: Dict[str, float] = defaultdict(float)
    for ops, mods in devices:
        starts = [m[0] for m in mods]
        for name, a, b in ops:
            c = xplane.clip((a, b), win)
            if c is None:
                continue
            i = bisect.bisect_right(starts, a) - 1
            module = mods[i][2] if i >= 0 and a < mods[i][1] else ""
            out[f"{module}/{xplane.op_name(name)}"] += (
                (c[1] - c[0]) / 1e9 / len(devices))
    return dict(out)


def reduce_dir(log_dir: str, spans: Sequence[dict] = ()) -> Optional[dict]:
    """:func:`xplane.reduce_dir`, with ``op_seconds`` in the reduction."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    data = ProfileData.from_file(str(files[-1]))
    dev = xplane.reduce_profile(data.planes, spans)
    if dev is not None:         # ``planes`` is read once per access
        dev["op_seconds"] = op_seconds(data.planes)
    return dev


def program_scopes() -> Dict[str, Optional[str]]:
    """The program's op -> scope map; empty where the program has none."""
    from repro import obs
    device_scopes = getattr(obs, "device_scopes", None)
    return device_scopes() if device_scopes is not None else {}


def scope_seconds(ops: Dict[str, float],
                  scopes: Dict[str, Optional[str]]) -> Dict[str, float]:
    """Device seconds per scope of the non-container ops (``None``: the
    ops in no scope, or not in the map)."""
    out: Dict[Optional[str], float] = defaultdict(float)
    for op, s in ops.items():
        scope = scopes.get(op)
        if scope != CONTAINER:
            out[scope] += s
    return dict(out)


def read(run, scope: Optional[str]) -> Optional[float]:
    """Device ms per step of ``scope``; ``None`` reads the remainder."""
    ops = (run.device or {}).get("op_seconds")
    steps = run.counts.get("steps")
    if not ops or not steps:
        return None
    scopes = program_scopes()
    if not scopes:
        return None
    per = scope_seconds(ops, scopes)
    if scope is not None:
        return 1e3 * per.get(scope, 0.0) / steps
    scoped = sum(per.get(s, 0.0) for s in SCOPE_METRICS.values())
    return 1e3 * (run.device["busy_s"] - scoped) / steps
