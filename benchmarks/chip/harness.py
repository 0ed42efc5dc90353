"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name, so a new cell is new files plus new entries in
``BENCHMARK.json``:

* ``configs/<config>.json`` — the configuration as it is run;
* ``traffic/<traffic>.json`` — the mix; its ``driver`` names
  ``drivers/<driver>.py``, the general generator and loop for that kind
  of cell;
* ``metrics/<metric>.py`` — one reader per per-layer metric,
  ``read(run) -> float | None``; a metric split by what it moves
  (``device_idle_share.decode``) may share the reader of its first
  part (``metrics/device_idle_share.py``);
* ``peaks.json`` — published peaks, keyed by ``device_kind``.

A driver module defines ``Cell(config, traffic, seed)`` with

* ``setup()`` — build the system under test, warm every shape the
  window uses;
* ``window(seconds) -> (e2e, counts)`` — the measured loop; ``e2e`` are
  host-clock end-to-end metrics, ``counts`` the work done (read by the
  per-layer metrics);
* ``release()`` — free the program's device state;
* ``controls`` — the names of the cell's controls;
* ``check(control=None) -> Check`` — compare what the window produced
  with the plain reference; ``check(name)`` makes the same comparison
  with control ``name`` put in the program's place.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

__all__ = ["Check", "Run", "load_module", "find_cell", "run_cell",
           "device_info", "peaks_for", "reader"]


@dataclass
class Check:
    """What a cell's check compared: ``numbers[name] = (value, limit)``.

    A run is correct when every value is at most its limit and nothing
    stopped the comparison (``error``)."""

    numbers: Dict[str, List[float]] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    error: Optional[str] = None
    readings: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.error is None and bool(self.numbers) and all(
            v <= lim for v, lim in self.numbers.values())

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": lim}
                for k, (v, lim) in self.numbers.items()}


@dataclass
class Run:
    """What a per-layer metric reader sees of one traced run."""

    e2e: Dict[str, float]
    counts: Dict[str, float]
    spans: List[dict]
    device: Optional[Dict[str, Any]]
    peaks: Dict[str, Any]


def load_module(path: Path, name: Optional[str] = None):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    """-> (workload entry, config entry, config dict, traffic dict)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    wl = cells[workload]
    centry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((ROOT / centry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{wl['traffic']}.json").read_text())
    return wl, centry, config, traffic


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones untraced, per-layer
    ones traced. A metric with a ``workloads`` key reports in those
    cells; one without it in every cell that reports what it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and (m.get("workloads") or m["moves"] in names)]


def reader(name: str):
    """The reader module of per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path)


def peaks_for(kind: str) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no published peaks for device kind {kind!r} "
                         f"in peaks.json (have {sorted(table)})")
    return table[kind]


def device_info(chips: int, require_chip: bool = True) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise SystemExit("benchmark: JAX found no accelerator (platform "
                         "'cpu'); the benchmark never runs on the CPU")
    if require_chip and len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def _traced_window(cell, seconds: float):
    """The window under the JAX profiler and the program's own spans;
    -> (e2e, counts, spans, device reduction)."""
    import jax

    from repro import obs

    import xplane
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        obs.reset_trace()
        obs.enable()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW):
                obs.instant(xplane.WINDOW)
                e2e, counts = cell.window(seconds)
        finally:
            jax.profiler.stop_trace()
            obs.disable()
        spans = obs.get_tracer().trace_dict()["traceEvents"]
        device = xplane.reduce_dir(log_dir, spans)
    return e2e, counts, spans, device


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None,
             cell_override: Optional[tuple] = None,
             require_chip: bool = True,
             patch: Optional[Callable] = None,
             control: Optional[str] = None) -> Dict[str, Any]:
    """One run; returns the result object that ``run.py`` prints.

    ``cell_override`` = (workload entry, config, traffic) replaces the
    files (tests run small cells on the CPU); ``patch(cell)`` runs after
    set-up and may break the timed path (the fault tests); ``control``
    puts that control in the program's place in the check.
    """
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if cell_override is None:
        wl, _, config, traffic = find_cell(bench, workload)
    else:
        wl, config, traffic = cell_override
    device = device_info(wl["chips"], require_chip)
    peaks = peaks_for(device["kind"]) if require_chip else {}
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")

    cell = driver.Cell(config, traffic, seed)
    cell.setup()
    if patch is not None:
        patch(cell)
    setup_s = time.perf_counter() - t_start

    if trace:
        e2e, counts, spans, dev = _traced_window(cell, seconds)
    else:
        e2e, counts = cell.window(seconds)
        spans, dev = [], None
    e2e["setup_s"] = setup_s
    device["memory_peak_bytes"] = memory_peak_bytes()
    cell.release()
    try:
        check = cell.check(control)
    except Exception as e:          # a check that cannot finish fails
        import traceback
        traceback.print_exc()
        check = Check(error=f"{type(e).__name__}: {e}")

    metrics: Dict[str, Dict[str, Any]] = {}
    run = Run(e2e, counts, spans, dev, peaks)
    for m in cell_metrics(bench, wl["name"], trace):
        if trace:
            value = reader(m["name"]).read(run)
        else:
            value = e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": check.correct, "attempted": check.attempted,
        "failed": check.failed, "metrics": metrics, "device": device}
    if trace and dev is not None:
        device["busy_s"] = dev["busy_s"]
        device["window_s"] = dev["window_s"]
        result["breakdown"] = {"device_ops": dev["device_ops"],
                               "idle_gaps": dev["idle_gaps"]}
    if check.error is not None:
        result["error"] = check.error
    result["compared"] = check.as_dict()
    return result


def print_result(result: Dict[str, Any]) -> None:
    """Compared numbers as the last lines on stderr; the result as the
    last line on stdout."""
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    if "error" in result:
        print(f"check error: {result['error']}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
