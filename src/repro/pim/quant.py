"""N-bit fixed-point quantization matching the PIM simulator's numerics.

MultPIM operates on unsigned N-bit fixed point. We use symmetric
per-channel affine quantization with an unsigned-offset trick so the
in-memory multiplier sees non-negative operands (the standard deployment
choice for PIM crossbars): ``q = clip(round(x/s) + 2^(n-1), 0, 2^n - 1)``
and matmuls correct the offset analytically.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro import obs

__all__ = ["QTensor", "quantize", "dequantize", "qmatmul_exact",
           "qragged_matmul_exact"]


class QTensor(NamedTuple):
    q: jnp.ndarray        # int32, in [0, 2^n)
    scale: jnp.ndarray    # per-channel or scalar, f32
    n_bits: int
    zero: int             # unsigned offset 2^(n-1)


def quantize(x: jnp.ndarray, n_bits: int = 8, axis=None) -> QTensor:
    with obs.scope(obs.PIM_QUANTIZE):
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
        scale = jnp.maximum(amax, 1e-8) / (2 ** (n_bits - 1) - 1)
        zero = 2 ** (n_bits - 1)
        q = jnp.clip(jnp.round(x / scale) + zero, 0, 2 ** n_bits - 1)
        return QTensor(q.astype(jnp.int32), scale.astype(jnp.float32),
                       n_bits, zero)


def dequantize(t: QTensor) -> jnp.ndarray:
    with obs.scope(obs.PIM_QUANTIZE):
        return (t.q.astype(jnp.float32) - t.zero) * t.scale


def qmatmul_exact(xq: QTensor, wq: QTensor) -> jnp.ndarray:
    """Integer matmul with offset correction; bit-identical to what the
    in-memory MultPIM-MAC mat-vec computes on the quantized operands.

    (x - zx) sx @ (w - zw) sw = sx sw [xq@wq - zx*sum(wq) - zw*sum(xq)
                                       + K*zx*zw]

    The product and the correction both accumulate in int32 (exact up
    to K ~ 2^31 / 2^(2n) elements — 131k at 8 bits, far beyond any
    d_model here); float32 accumulation would silently drop low bits
    once K * (2^n - 1)^2 passes 2^24, i.e. at real model widths.
    """
    xi = xq.q
    wi = wq.q
    k = xi.shape[-1]
    with obs.scope(obs.PIM_MATMUL):
        prod = xi @ wi                  # int32: exact
        corr = (xq.zero * jnp.sum(wi, axis=0, keepdims=True)
                + wq.zero * jnp.sum(xi, axis=-1, keepdims=True)
                - k * xq.zero * wq.zero)
        return (prod - corr).astype(jnp.float32) * xq.scale * wq.scale


def qragged_matmul_exact(xq: QTensor, wq: QTensor,
                         counts: jnp.ndarray) -> jnp.ndarray:
    """Ragged grouped-GEMM variant of :func:`qmatmul_exact` for the MoE
    dropless dispatch: ``xq.q`` is the (T, D) expert-sorted token block,
    ``wq.q`` the (E, D, F) per-expert weight stack (per-tensor scale so
    one offset correction covers every expert), ``counts`` the (E,)
    per-expert segment lengths. Row ``t`` multiplies against its
    segment's expert exactly as ``jax.lax.ragged_dot`` would on the
    float path, with the same analytic zero-point correction — so the
    per-expert GEMMs are bit-identical to what the in-memory
    MultPIM-MAC computes on the quantized operands.
    """
    import jax
    xi = xq.q
    wi = wq.q                                          # (E, D, F)
    k = xi.shape[-1]
    with obs.scope(obs.PIM_MATMUL):
        # int32 accumulation end-to-end (see qmatmul_exact): exact where
        # a float32 ragged_dot drifts once the per-row dot passes 2^24.
        prod = jax.lax.ragged_dot(xi, wi, counts)
        # Per-row sum_d w[expert(row), d, :]: expand the per-expert
        # column sums along the ragged segments (counts sum to T by
        # construction).
        wsum = jnp.repeat(jnp.sum(wi, axis=1), counts, axis=0,
                          total_repeat_length=xi.shape[0])
        corr = (xq.zero * wsum
                + wq.zero * jnp.sum(xi, axis=-1, keepdims=True)
                - k * xq.zero * wq.zero)
        return (prod - corr).astype(jnp.float32) * xq.scale * wq.scale
