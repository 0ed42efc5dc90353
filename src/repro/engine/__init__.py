"""repro.engine — one device/executable API over the whole PIM stack.

The paper's pipeline is one flow: build a partitioned schedule, optimize
it, execute it with row-parallel SIMD (MultPIM Sections IV–VI). This
package is the single public surface over that flow — an
:class:`Engine` fronts the schedule builders, the optimizing compiler +
OpSpec-keyed program cache (memory and disk), the numpy/JAX/Pallas
executors and the cost model; an :class:`Executable` is one compiled
program you run many times on a chosen :class:`Backend`.

Quickstart (the 5 lines that replace six modules)::

    from repro.engine import get_engine
    eng = get_engine()
    exe = eng.compile(op="multpim", n=16, backend="pallas")
    print(exe.run({"a": [12345], "b": [321]})["out"])   # [3962745]
    print(exe.cost().cycles, eng.matvec([[3, 5]], [7, 9], 8)[0])

Everything composes from here: ``eng.compile(op="multpim"|"rime"|
"hajali"|"mac", n=...)`` returns an ``Executable`` with ``.run(batch)``
(integer arrays or ``(rows, bits)`` planes — marshalling is automatic),
``.program``, ``.packed``, ``.cost()`` and ``.verify()``;
``eng.compile_batch(op, n, k)`` co-schedules K copies into disjoint
partition/column ranges of one crossbar and returns a
:class:`BatchedExecutable` whose single pass serves K operand sets
(``cost().cycles_per_program`` is the cycles-per-MAC the throughput
benchmarks track);
``eng.multiply`` / ``eng.mac`` / ``eng.matvec`` / ``eng.inner_product``
are the high-level ops the examples and benchmarks share; the PIM serve
path's planner (:func:`repro.pim.plan_block`) compiles its crossbar
groups here. Backends are pluggable
(:func:`register_backend`) and selectable per compile or per run:
``"numpy"``, ``"jax:pack=true"``, ``"pallas:pack=true"`` (the Pallas
interpreter on the CPU, Mosaic on a TPU). With no backend named, the
platform picks one: numpy on the CPU, ``jax:pack=true`` on a TPU.

Legacy entry points (``repro.core.matvec.matvec``,
``repro.kernels.ops.crossbar_run_cached``) remain as thin deprecation
shims that delegate here — new code should talk to the Engine.
"""
from .backends import (DEFAULT_MACRO, Backend, JaxBackend, NumpyBackend,
                       PallasBackend, autotune_row_block, backend_names,
                       register_backend, resolve_backend)
from .engine import (DEFAULT_COSCHEDULE_K, OP_KINDS, Engine, GroupSpec,
                     get_engine)
from .executable import (BatchedExecutable, ExecCost, Executable,
                         GroupedExecutable)

# Re-exported so callers can build specs/cache keys without touching
# repro.compiler directly.
from repro.compiler.spec import OpSpec

__all__ = [
    "Engine", "get_engine", "OP_KINDS", "DEFAULT_COSCHEDULE_K",
    "GroupSpec", "Executable", "BatchedExecutable", "GroupedExecutable",
    "ExecCost", "OpSpec",
    "Backend", "NumpyBackend", "JaxBackend", "PallasBackend",
    "register_backend", "resolve_backend", "backend_names",
    "autotune_row_block", "DEFAULT_MACRO",
]
