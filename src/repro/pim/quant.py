"""N-bit fixed-point quantization matching the PIM simulator's numerics.

MultPIM operates on unsigned N-bit fixed point. We use symmetric
per-channel affine quantization with an unsigned-offset trick so the
in-memory multiplier sees non-negative operands (the standard deployment
choice for PIM crossbars): ``q = clip(round(x/s) + 2^(n-1), 0, 2^n - 1)``
and matmuls correct the offset analytically.

Weights that stay put while serving are quantized once
(:func:`plan_weight`) into a :class:`PlannedWeight`: centred codes
``q - 2^(n-1)`` (``int8`` up to 8 bits) and per-column scales, or one
scale for a whole MoE expert stack (:data:`STACK_AXES`). Against it
:func:`qmatmul_planned` and :func:`qragged_matmul_exact` form
:func:`qmatmul_exact`'s exact integer sum in one product, with no
offset correction; :func:`qmatmul_exact` stays as the reference that
the planned product is tested against.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

__all__ = ["QTensor", "quantize", "dequantize", "qmatmul_exact",
           "qragged_matmul_exact", "PlannedWeight", "plan_weight",
           "qmatmul_planned", "STACK_AXES"]


#: Rows a tile of :func:`qragged_matmul_exact` multiplies at a time.
RAGGED_TILE = 512

#: The axes that an (E, D, F) expert stack's one scale reduces over
#: (:func:`plan_weight`), each layer of a layer-stacked one on its own.
STACK_AXES = (-3, -2, -1)


class QTensor(NamedTuple):
    q: jnp.ndarray        # int32, in [0, 2^n)
    scale: jnp.ndarray    # per-channel or scalar, f32
    n_bits: int
    zero: int             # unsigned offset 2^(n-1)


def _scale(x: jnp.ndarray, n_bits: int, axis) -> jnp.ndarray:
    """The scale that maps the largest magnitude along ``axis`` (all of
    ``x`` when None) to ``2^(n-1) - 1``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.maximum(amax, 1e-8) / (2 ** (n_bits - 1) - 1)


def quantize(x: jnp.ndarray, n_bits: int = 8, axis=None) -> QTensor:
    with obs.scope(obs.PIM_QUANTIZE):
        scale = _scale(x, n_bits, axis)
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


def qragged_matmul_exact(xq: QTensor, w: PlannedWeight,
                         counts: jnp.ndarray, *,
                         tile: int = RAGGED_TILE) -> jnp.ndarray:
    """Ragged grouped-GEMM variant of :func:`qmatmul_exact` for the MoE
    dropless dispatch: ``xq.q`` is the (T, D) expert-sorted token block,
    ``w`` the (E, D, F) per-expert weight stack planned with one scale
    (:func:`plan_weight` over :data:`STACK_AXES`, so one offset covers
    every expert), ``counts`` the (E,) per-expert segment lengths. Row
    ``t`` multiplies against its segment's expert exactly as
    ``jax.lax.ragged_dot`` would on the float path; rows past the
    segments read 0 (a TPU ``ragged_dot`` does not zero them). A stack
    planned at another width than ``xq`` raises.

    The corrected integer sum ``sum (x - zx)(w - zw)`` of
    :func:`qmatmul_exact` is formed from the centred codes (``int8 x int8
    -> int32`` up to 8 bits, as :func:`qmatmul_planned`), so the
    per-expert GEMMs are bit-identical to what the in-memory MultPIM-MAC
    computes on the quantized operands. Up to ``tile`` rows (a decode
    step's) every expert takes all of them, its rows of other experts
    masked to 0, in one batched product. Past that, each expert's rows
    are laid out in tiles of ``tile`` rows of their own (the last one
    padded with zero rows), and the tiles multiply against their
    experts in one batched product: at most ``T / tile + E`` tiles, so
    the product and its temporaries grow with the rows, not with rows
    times experts.
    """
    if xq.n_bits != w.n_bits:
        raise ValueError(f"activations at {xq.n_bits} bits, stack "
                         f"planned at {w.n_bits}")
    t, d = xq.q.shape
    e, _, f = w.shape
    wc, w_scale = w.q, w.scale.reshape(())
    batched = (((2,), (1,)), ((0,), (0,)))
    with obs.scope(obs.PIM_QUANTIZE):
        xc = (xq.q - xq.zero).astype(wc.dtype)
    with obs.scope(obs.PIM_MATMUL):
        first_row = jnp.cumsum(counts) - counts
        i = jnp.arange(t)
        g = jnp.searchsorted(first_row + counts, i, side="right")
        if t <= tile:
            prods = jax.lax.dot_general(                   # (E, T, F)
                jnp.broadcast_to(xc, (e, t, d)), wc, batched,
                preferred_element_type=jnp.int32)
            mine = g[None, :, None] == jnp.arange(e)[:, None, None]
            prod = jnp.sum(jnp.where(mine, prods, 0), axis=0)
            return prod.astype(jnp.float32) * xq.scale * w_scale
        n = -(-t // tile) + e
        tiles = -(-counts // tile)                         # (E,)
        first_tile = jnp.cumsum(tiles) - tiles
        # Each tile's expert; tiles past the last one hold no row.
        te = jnp.minimum(jnp.searchsorted(first_tile + tiles, jnp.arange(n),
                                          side="right"), e - 1)
        # Slot s of tile s // tile takes its expert's row (s // tile -
        # first_tile) * tile + s % tile, or a zero row past the segment.
        se = jnp.repeat(te, tile)
        s = jnp.arange(n * tile)
        k = (s // tile - first_tile[se]) * tile + s % tile
        src = jnp.where(k < counts[se], first_row[se] + k, t)
        xt = jnp.take(xc, src, axis=0, mode="fill", fill_value=0)
        prods = jax.lax.dot_general(                       # (n, tile, F)
            xt.reshape(n, tile, d), wc[te], batched,
            preferred_element_type=jnp.int32)
        # And back: row i's slot, or none past the segments.
        gc = jnp.minimum(g, e - 1)
        slot = jnp.where(g < e, first_tile[gc] * tile + i - first_row[gc],
                         n * tile)
        prod = jnp.take(prods.reshape(n * tile, f), slot, axis=0,
                        mode="fill", fill_value=0)
        return prod.astype(jnp.float32) * xq.scale * w_scale


@dataclasses.dataclass(frozen=True)
class PlannedWeight:
    """A weight quantized once, for :func:`qmatmul_planned` or, an
    expert stack, :func:`qragged_matmul_exact`.

    ``q`` holds the centred codes ``q - 2^(n-1)`` of :func:`quantize`
    (``int8`` for ``n_bits <= 8``, else ``int32``), shaped like the float
    weight (layer-stacked ones too); ``scale`` the ``float32`` scales,
    per column ``(..., 1, out)`` or one a stack ``(..., 1, 1, 1)``. A
    pytree whose leaves are ``q`` and ``scale``; ``n_bits`` is static.
    """
    q: jnp.ndarray
    scale: jnp.ndarray
    n_bits: int

    @property
    def shape(self):
        return self.q.shape


jax.tree_util.register_dataclass(PlannedWeight, data_fields=["q", "scale"],
                                 meta_fields=["n_bits"])


def plan_weight(w: jnp.ndarray, n_bits: int, axis=-2) -> PlannedWeight:
    """``w`` float -> its :class:`PlannedWeight`, with scales over
    ``axis``: per column of a (..., in, out) weight by default, or one
    for each (E, D, F) expert stack over :data:`STACK_AXES`; each layer
    of a stack on its own. The codes and scales are exactly what
    :func:`quantize` gives one weight in the same setting (traced, or op
    by op), with ``axis=0`` per column and ``axis=None`` for a stack,
    less its offset."""
    zero = 2 ** (n_bits - 1)
    dtype = jnp.int8 if n_bits <= 8 else jnp.int32
    with obs.scope(obs.PIM_QUANTIZE):
        scale = _scale(w, n_bits, axis)
        # Clipped in centred form: op by op, no int32 copy of the weight.
        q = jnp.clip(jnp.round(w / scale), -zero, zero - 1).astype(dtype)
        return PlannedWeight(q, scale.astype(jnp.float32), n_bits)


def qmatmul_planned(xq: QTensor, w: PlannedWeight) -> jnp.ndarray:
    """:func:`qmatmul_exact` against a planned weight.

    ``sum (x - zx)(w - zw)`` is the corrected integer sum that
    :func:`qmatmul_exact` forms; with both operands centred it is one
    product (``int8 x int8 -> int32`` up to 8 bits). The scales apply
    in the same order, so with the same codes and scales the result is
    :func:`qmatmul_exact`'s.
    """
    if xq.n_bits != w.n_bits:
        raise ValueError(f"activations at {xq.n_bits} bits, weight "
                         f"planned at {w.n_bits}")
    with obs.scope(obs.PIM_QUANTIZE):
        xc = (xq.q - xq.zero).astype(w.q.dtype)
    with obs.scope(obs.PIM_MATMUL):
        prod = jax.lax.dot_general(
            xc, w.q, (((xc.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return prod.astype(jnp.float32) * xq.scale * w.scale
