"""Public jit'd entry points for the kernels (Pallas with jnp fallback).

``interpret=None`` derives the mode from the platform
(:func:`repro.runtime.resolve_interpret`): the Pallas
interpreter on the CPU, Mosaic lowering with the documented BlockSpecs
on a TPU.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.executor import PackedProgram

from .crossbar_step import crossbar_run_pallas
from .ref import crossbar_run_ref

__all__ = ["crossbar_run", "crossbar_run_cached", "crossbar_run_ref"]


def crossbar_run(state_bits: jnp.ndarray, packed: PackedProgram, *,
                 use_pallas: bool = True, interpret: Optional[bool] = None,
                 row_block: int = 256) -> jnp.ndarray:
    if use_pallas:
        return crossbar_run_pallas(state_bits, packed,
                                   row_block=row_block, interpret=interpret)
    return crossbar_run_ref(state_bits, packed)


def crossbar_run_cached(state_bits: jnp.ndarray, kind: str, n: int, *,
                        flags=None, use_pallas: bool = True,
                        interpret: Optional[bool] = None,
                        row_block: int = 256
                        ) -> jnp.ndarray:
    """Run a named program through the shared engine's program cache: the
    schedule is built, optimized, verified and packed once per OpSpec;
    this call only pays the crossbar step itself. ``state_bits`` must be
    ``(rows, packed.init_mask.shape[1])`` — see
    :meth:`repro.engine.Engine.compile` for the entry's layout.

    Deprecation shim: prefer ``get_engine().compile(kind, n,
    backend="pallas").run(...)`` (that path also marshals named inputs).
    """
    from repro.engine import get_engine
    exe = get_engine().compile(kind, n, flags=flags)
    return crossbar_run(state_bits, exe.packed, use_pallas=use_pallas,
                        interpret=interpret, row_block=row_block)

