"""Device scopes: which part of a model each op on the device belongs to.

The vocabulary is :data:`SCOPES`. Model code marks its work with
``with obs.scope(obs.KV_CACHE):`` (a ``jax.named_scope``), which only
labels the ops' HLO metadata (``op_name=".../kv_cache/..."``), so the
compiled program runs the same with or without the labels. Where scopes
nest, the innermost name from the vocabulary wins: the cache write
inside the attention block counts as :data:`KV_CACHE`, and a PIM
linear's quantisation inside it as :data:`PIM_QUANTIZE`. One exception:
inside :data:`MOE_EXPERTS` the ``pim.*`` ops of the experts' grouped
products count as :data:`MOE_EXPERTS`, so ``pim.*`` stays the dense
linears'.

A jitted program is registered with :func:`register_program` (the
function and the abstract shapes of its arguments; nothing on the
device). :func:`device_scopes` compiles each registered program from the
caches, only when asked, and reads the optimized HLO: every op,
keyed ``"<module>/<op>"`` as a profiler trace names them (the ``XLA
Modules`` event's name before ``(``, the ``XLA Ops`` event's name
before `` = ``), maps to its scope, to ``None`` outside every scope, or
to :data:`CONTAINER` for a control-flow op (``while``, ``conditional``,
``call``) whose time its body's ops already hold. A fused op counts
under the scope of the fusion's root, the metadata XLA gives the fusion.

:func:`watch_compiles` registers one ``jax.monitoring`` duration
listener: it counts every backend compile in the ``jax.compiles``
counter, and while the tracer is on records each compile phase as a
completed ``jax.compile`` span (argument ``event``: the monitoring
event's name), so a compile inside a traced window shows by name among
the idle gaps.

JAX is imported only inside these functions, so ``repro.obs`` stays
importable without it.
"""
from __future__ import annotations

import re
import threading
from typing import Dict, Optional, Tuple

from .metrics import get_registry
from .trace import get_tracer

__all__ = ["KV_CACHE", "ATTENTION", "PIM_QUANTIZE", "PIM_MATMUL",
           "MOE_ROUTE", "MOE_EXPERTS", "SCOPES", "CONTAINER", "scope", "scope_of", "hlo_scopes",
           "register_program", "device_scopes", "watch_compiles",
           "COMPILE_EVENT", "COMPILES"]

KV_CACHE = "kv_cache"          # reads and writes of the decode caches
ATTENTION = "attention"        # projections, rope, scores, softmax, sum
PIM_QUANTIZE = "pim.quantize"  # PIM linears' quantize, dequantize, scales
PIM_MATMUL = "pim.matmul"      # PIM linears' integer product, corrections
MOE_ROUTE = "moe.route"        # router, gates, top-k, sort, gather, combine
MOE_EXPERTS = "moe.experts"    # held experts' grouped products, quantized
SCOPES = (KV_CACHE, ATTENTION, PIM_QUANTIZE, PIM_MATMUL, MOE_ROUTE,
          MOE_EXPERTS)

CONTAINER = "container"
_CONTAINER_OPS = frozenset({"while", "conditional", "call"})

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_PHASES = "/jax/core/compile/"
COMPILES = "jax.compiles"


def scope(name: str):
    """Label the ops traced inside the ``with`` block as scope ``name``
    (one of :data:`SCOPES`). Outside a JAX trace it labels nothing."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}; have {SCOPES}")
    import jax
    return jax.named_scope(name)


def scope_of(op_name: str) -> Optional[str]:
    """The innermost vocabulary scope in an HLO ``op_name`` path; a
    ``pim.*`` scope inside :data:`MOE_EXPERTS` reads as the latter."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            if part.startswith("pim.") and MOE_EXPERTS in parts:
                return MOE_EXPERTS
            return part
    return None


_MODULE = re.compile(r"^HloModule ([^,\s]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> Tuple[str, Dict[str, Optional[str]]]:
    """(module name, {op: scope}) of one optimized HLO module's text."""
    m = _MODULE.search(text)
    module = m.group(1) if m else ""
    ops: Dict[str, Optional[str]] = {}
    for line in text.splitlines():
        ins = _INSTR.match(line)
        if ins is None:
            continue
        name, rest = ins.groups()
        code = _OPCODE.search(" " + rest)
        if code and code.group(1) in _CONTAINER_OPS:
            ops[name] = CONTAINER
            continue
        meta = _OP_NAME.search(rest)
        ops[name] = scope_of(meta.group(1)) if meta else None
    return module, ops


class ProgramRegistry:
    """Jitted programs and abstract arguments; their scope maps, built
    once each on the first :meth:`scopes` call."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, tuple] = {}
        self._maps: Dict[str, Dict[str, Optional[str]]] = {}

    def register(self, fn, *args) -> None:
        import jax

        def abstract(x):
            if isinstance(x, jax.ShapeDtypeStruct):
                return x
            t = jax.typeof(x)
            return jax.ShapeDtypeStruct(t.shape, t.dtype,
                                        weak_type=t.weak_type)

        spec = jax.tree.map(abstract, args)
        # The same function at the same shapes compiles to one module:
        # keep the newest registration of it.
        key = f"{getattr(fn, '__name__', fn)!s}:{spec!r}"
        with self._lock:
            self._programs[key] = (fn, spec)
            self._maps.pop(key, None)

    def scopes(self) -> Dict[str, Optional[str]]:
        with self._lock:
            todo = [(k, p) for k, p in self._programs.items()
                    if k not in self._maps]
        for key, (fn, spec) in todo:
            module, ops = hlo_scopes(fn.lower(*spec).compile().as_text())
            with self._lock:
                self._maps[key] = {f"{module}/{op}": s
                                   for op, s in ops.items()}
        out: Dict[str, Optional[str]] = {}
        with self._lock:
            for m in self._maps.values():
                out.update(m)
        return out

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._maps.clear()


_PROGRAMS = ProgramRegistry()


def get_programs() -> ProgramRegistry:
    return _PROGRAMS


def register_program(fn, *args) -> None:
    """Register jitted ``fn`` with the shapes and dtypes of ``args``
    (arrays or ``ShapeDtypeStruct`` pytrees) for :func:`device_scopes`.
    Holds no array and compiles nothing; also starts
    :func:`watch_compiles`."""
    watch_compiles()
    _PROGRAMS.register(fn, *args)


def device_scopes() -> Dict[str, Optional[str]]:
    """``{"<module>/<op>": scope}`` of every registered program (see the
    module docstring). Compiles, from the caches, what was not compiled
    for this map before: call it after a measured window, never in it."""
    return _PROGRAMS.scopes()


_WATCHING = False
_WATCH_LOCK = threading.Lock()


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        get_registry().counter(COMPILES).inc()
    if event.startswith(_COMPILE_PHASES):
        tracer = get_tracer()
        if tracer.enabled:
            tracer.complete("jax.compile", duration, cat="jax", event=event)


def watch_compiles() -> None:
    """Register the compile listener (once per process)."""
    global _WATCHING
    with _WATCH_LOCK:
        if _WATCHING:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _WATCHING = True

