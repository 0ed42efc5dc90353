"""The PIM linear: a product under MultPIM fixed-point semantics.

:func:`linear` quantizes the activations per call and multiplies them
by the weight's centred codes in one integer product
(:func:`~repro.pim.quant.qmatmul_planned`), bit-identical to what the
in-memory MultPIM-MAC computes on the quantized operands; the weight is
a :class:`~repro.pim.quant.PlannedWeight` (quantized once,
:func:`repro.models.transformer.plan_weights`) or a float weight planned
in the call. :func:`ragged_linear` is the MoE grouped GEMM over
per-expert segments (:func:`~repro.pim.quant.qragged_matmul_exact`).

Both are traceable and touch no crossbar: what the linears cost on the
crossbar is the planner's (:func:`repro.pim.planner.plan_block`).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import quant

__all__ = ["linear", "ragged_linear"]


def linear(x: jnp.ndarray, w, n_bits: int) -> jnp.ndarray:
    """``x`` (..., in) @ ``w`` (in, out) at ``n_bits``. A weight planned
    at another width raises."""
    lead = x.shape[:-1]
    xq = quant.quantize(x.reshape(-1, x.shape[-1]), n_bits)
    if not isinstance(w, quant.PlannedWeight):
        w = quant.plan_weight(w, n_bits)
    return quant.qmatmul_planned(xq, w).reshape(*lead, w.shape[-1])


def ragged_linear(xs: jnp.ndarray, we: jnp.ndarray, counts: jnp.ndarray,
                  n_bits: int) -> jnp.ndarray:
    """``xs`` (T, D) expert-sorted rows against ``we`` (E, D, F), row
    segments of ``counts`` (E,) lengths, each expert's GEMM at
    ``n_bits``; the stack is quantized in the call, with one scale."""
    return quant.qragged_matmul_exact(quant.quantize(xs, n_bits),
                                      quant.quantize(we, n_bits), counts)
