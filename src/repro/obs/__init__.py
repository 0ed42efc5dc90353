"""repro.obs — zero-dependency observability for the whole stack.

Four pieces, one import surface:

* **Spans** (:mod:`.trace`) — ``with obs.span("compile.fuse", op=...):``
  wall-time intervals from the compiler, cache, engine, executors, and
  serve loop, exported as Chrome trace-event JSON
  (``obs.export_trace(path)``; open in chrome://tracing or Perfetto).
  Disabled by default and near-free when disabled.
* **Metrics** (:mod:`.metrics`) — process-wide counters / gauges /
  streaming histograms; ``obs.dump()`` snapshots everything (a superset
  of ``Engine.stats()``), ``obs.write_metrics(path)`` saves it.
  Always on: recording a counter or latency sample is cheap enough to
  not need a switch. :data:`WEIGHT_PLANS` and :data:`PLAN_REUSES` name
  the serve step's counters of weight plans made and reused;
  :data:`POSITION_WRITES` and :data:`WHOLE_WRITES` the decode step's
  layer states written by position and whole, counted when it is traced.
* **Waterfall** (:mod:`.waterfall`) — modeled-cycle counter tracks
  (partition occupancy, gate activity, switching) derived from compiled
  programs, merged into the same trace file; plus the
  ``energy_proxy`` switching-activity scalar on ``ExecCost``.
* **Device scopes** (:mod:`.scopes`) — where a jitted program's device
  time goes. Model code marks its work with ``with
  obs.scope(obs.KV_CACHE):`` from one vocabulary, :data:`SCOPES`:
  ``kv_cache`` (the decode caches' reads and writes), ``attention``
  (projections, rope, scores, softmax, weighted sum), ``pim.quantize``
  (the PIM linears' quantize, dequantize and scale reductions) and
  ``pim.matmul`` (their integer product and zero-point corrections),
  ``moe.route`` (router, gates, top-k, sort, gather, combine) and
  ``moe.experts`` (the held experts' grouped products with their
  quantization); the innermost wins where they nest, save that
  ``moe.experts`` holds the ``pim.*`` ops inside it. Scopes are ``jax.named_scope``
  names: they label HLO metadata only, so the executable is unchanged,
  and an operator sees them in XProf as each op's ``tf_op``.
  ``obs.register_program(jitted, *args)`` (done by
  ``make_serve_step``) keeps a program's abstract arguments;
  ``obs.device_scopes()`` compiles the registered programs from the
  caches, only when asked, and maps each op, ``"<module>/<op>"`` as a
  profiler trace names it, to its scope (``None`` outside all, or
  ``obs.CONTAINER`` for a ``while``/``conditional``/``call`` whose body
  ops hold its time). Call it after a measured window, never inside.
  The compile listener (``obs.watch_compiles()``, started by the first
  registration) counts every backend compile in ``jax.compiles`` and,
  while the tracer is on, records each compile phase as a ``jax.compile``
  span, so a compile inside a traced window is named among its gaps.

Import layering: ``repro.obs`` depends only on :mod:`repro.core` — the
compiler/engine/pim layers all import it, so it must sit below them.
"""
from __future__ import annotations

from typing import Optional

from .logging import get_logger, setup_logging
from .metrics import (Counter, Gauge, Histogram, Registry,
                      WindowedHistogram, get_registry)
from .scopes import (ATTENTION, COMPILES, CONTAINER, KV_CACHE, MOE_EXPERTS,
                     MOE_ROUTE, PIM_MATMUL, PIM_QUANTIZE, SCOPES,
                     device_scopes, register_program, scope, watch_compiles)
from .trace import NULL_SPAN, PID_SPANS, Span, Tracer, get_tracer
from .waterfall import (cycle_occupancy, switching_activity,
                        switching_profile, waterfall_events)

__all__ = [
    # trace
    "span", "instant", "track", "enable", "disable", "enabled",
    "reset_trace", "add_events", "export_trace", "get_tracer", "Tracer",
    "Span", "NULL_SPAN", "PID_SPANS",
    # metrics
    "counter", "gauge", "histogram", "windowed_histogram", "dump",
    "write_metrics", "reset_metrics", "get_registry", "Registry",
    "Counter", "Gauge", "Histogram", "WindowedHistogram",
    # waterfall
    "cycle_occupancy", "switching_profile", "switching_activity",
    "waterfall_events",
    # device scopes
    "scope", "SCOPES", "KV_CACHE", "ATTENTION", "PIM_QUANTIZE",
    "PIM_MATMUL", "MOE_ROUTE", "MOE_EXPERTS", "CONTAINER", "COMPILES", "register_program",
    "device_scopes", "watch_compiles",
    # counter names
    "WEIGHT_PLANS", "PLAN_REUSES", "POSITION_WRITES", "WHOLE_WRITES",
    # logging
    "setup_logging", "get_logger",
]


# Counters of the weight-stationary serve step (repro.train.ServeStep).
WEIGHT_PLANS = "pim.weight_plans"    # weight plans made
PLAN_REUSES = "pim.plan_reuses"      # steps served from a stored plan
# Counters of the decode step's state writes, counted as the step is
# traced (repro.models.transformer.decode_step): layer states written
# one position a step (KV and latent caches), and written whole
# (recurrent states).
POSITION_WRITES = "kv_cache.position_writes"
WHOLE_WRITES = "kv_cache.whole_writes"


# --------------------------------------------------------------- spans ----
def span(name: str, cat: str = "repro", **args):
    """Module-level alias for ``get_tracer().span(...)`` — the form
    instrumented code uses. One attribute check when tracing is off."""
    t = get_tracer()
    if not t.enabled:
        return NULL_SPAN
    return t.span(name, cat, **args)


def instant(name: str, cat: str = "repro", **args) -> None:
    get_tracer().instant(name, cat, **args)


def track(name: str, cat: str = "repro", **values) -> None:
    """One sample of a wall-time counter track in the exported trace
    (e.g. ``obs.track("serve.sched", queue_depth=3, live=4)``). Distinct
    from :func:`counter`, which is the *metrics* counter instrument."""
    get_tracer().counter(name, cat, **values)


def enable() -> None:
    get_tracer().enable()


def disable() -> None:
    get_tracer().disable()


def enabled() -> bool:
    return get_tracer().enabled


def reset_trace() -> None:
    get_tracer().reset()


def add_events(events) -> None:
    get_tracer().add_events(events)


def export_trace(path: str) -> int:
    return get_tracer().export(path)


# ------------------------------------------------------------- metrics ----
def counter(name: str) -> Counter:
    return get_registry().counter(name)


def gauge(name: str) -> Gauge:
    return get_registry().gauge(name)


def histogram(name: str, cap: int = Histogram.DEFAULT_CAP) -> Histogram:
    return get_registry().histogram(name, cap)


def windowed_histogram(name: str, cap: int = Histogram.DEFAULT_CAP
                       ) -> WindowedHistogram:
    return get_registry().windowed_histogram(name, cap)


def dump() -> dict:
    return get_registry().dump()


def write_metrics(path: str, extra: Optional[dict] = None) -> dict:
    return get_registry().write(path, extra)


def reset_metrics() -> None:
    get_registry().reset()
