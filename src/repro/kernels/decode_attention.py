"""Pallas TPU kernels: one token's attention over one layer of a stacked
decode cache, read where it lies.

The decode step's caches are layer stacks, ``(L, B, T, ...)``, carried
through the layer scan and written one position a step
(:func:`repro.models.attention.write_position`). XLA materializes a
dynamic slice that feeds a dot, and lays the stack out for the dot
inside the scan, so the jnp form of attention over ``stack[li]`` copies
whole layers. These kernels take the stack itself and the layer index as
a scalar-prefetch operand: each grid cell's ``index_map`` picks its
block of layer ``li``, so only the bytes attention needs leave HBM.

Both run a flash-decoding pass over the time axis (grid ``(B, T /
block)``, the time axis last and sequential): a running max, sum and
weighted sum in float32 scratch, normalized at the last block. A slot
is valid when its ring age ``(slot - t) mod T`` is below ``n_valid``
(the tokens written, capped at the ring and the window), as
:func:`repro.models.attention.decode_attend` masks it. Where no block
size divides ``T`` the last block runs past it (:func:`time_block`);
its rows past ``T`` are masked and zeroed.

* :func:`kv_decode_attention`: grouped K/V heads ``(L, B, T, Hkv, D)``.
  A query has one token, so the products run on the VPU over the
  blocks' native ``(Hkv, D)`` tiles: exact float32 products and sums.
* :func:`latent_decode_attention`: the absorbed latent form, scores
  ``q_lat . c + q_pe . k_pe`` and the latents' weighted sum, on the MXU
  at the caller's precision, each latent block read once for both. The
  rope keys are kept positions-last, ``(L, B, dr, T)``, so their blocks
  are lane-dense.

Under the interpreter (the CPU) they run as written, for the tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import resolve_interpret

__all__ = ["kv_decode_attention", "latent_decode_attention",
           "time_block"]

# Below every real score and above the mask's -2.38e38: a block whose
# slots are all masked then adds exp(mask - running max) = 0.
_M_INIT = -1e30
_MASKED = -2.3819763e38
BLOCK_BYTES = 2 << 20           # cache bytes a grid step reads (buffered twice)
VMEM_LIMIT = 64 << 20


def time_block(t: int, row_bytes: int, align: int = 1) -> int:
    """The time-axis block: ``t`` when a block of ``row_bytes`` a
    position within :data:`BLOCK_BYTES` holds it all, else the largest
    divisor of ``t`` that is a multiple of ``align`` and fits. Where
    that divisor is under half the largest multiple of ``align`` that
    fits (a prime ``t``, say), that multiple is the block and the last
    one runs past ``t``: a block must fit the VMEM whatever the cache
    length, and a tiny one would take a grid step per few positions."""
    cap = max(1, BLOCK_BYTES // max(row_bytes, 1))
    if t <= cap:
        return t
    cap = max(align, cap // align * align)
    div = next((bt for bt in range(cap, 0, -align) if t % bt == 0), 0)
    return div if 2 * div >= cap else cap


def _ages_valid(sc_ref, tb, bt: int, t: int, shape, dim: int):
    """Bool ``shape``: whether each position of time block ``tb`` (along
    ``dim``) is a valid ring slot, from the prefetched slot and count;
    positions past ``t`` (a ragged last block) are not."""
    kpos = tb * bt + jax.lax.broadcasted_iota(jnp.int32, shape, dim)
    age = sc_ref[1] - kpos
    age = jnp.where(age < 0, age + t, age)
    valid = age < sc_ref[2]
    return valid & (kpos < t) if t % bt else valid


def _kv_kernel(sc_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bt: int, t: int, groups: int, cap: Optional[float]):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _M_INIT, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    k = k_ref[...].astype(jnp.float32)                 # (bt, Hkv, D)
    v = v_ref[...].astype(jnp.float32)
    valid = _ages_valid(sc_ref, tb, bt, t, (bt, k.shape[1], 1), 0)
    if t % bt:                  # a ragged block: its rows past t weigh 0
        v = jnp.where(valid, v, 0.0)
    for g in range(groups):
        q = q_ref[g].astype(jnp.float32)               # (Hkv, D), scaled
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)   # (bt, Hkv, 1)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        s = jnp.where(valid, s, _MASKED)
        m_prev = m_ref[g]                              # (Hkv, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[g] = corr * l_ref[g] + jnp.sum(p, axis=0)
        acc_ref[g] = corr * acc_ref[g] + jnp.sum(p * v, axis=0)
        m_ref[g] = m_new

    @pl.when(tb == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def kv_decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        layer, slot, n_valid, *, cap: Optional[float] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attention of one token per sequence over layer ``layer`` of a K/V
    stack. ``q`` (B, G, Hkv, D), already scaled, query head ``h * G + g``
    at ``[:, g, h]``; ``k``/``v`` (L, B, T, Hkv, D); ``slot`` the newest
    ring slot and ``n_valid`` the valid slots (int32 scalars); ``cap``
    the scores' softcap. -> (B, G, Hkv, D) in ``q``'s dtype."""
    b, groups, hkv, d = q.shape
    t = k.shape[2]
    bt = time_block(t, 2 * hkv * d * k.dtype.itemsize)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32)
                         for x in (layer, slot, n_valid)])
    cache_spec = pl.BlockSpec((None, None, bt, hkv, d),
                              lambda i, j, sc: (sc[0], i, j, 0, 0))
    q_spec = pl.BlockSpec((None, groups, hkv, d),
                          lambda i, j, sc: (i, 0, 0, 0))
    kernel = functools.partial(_kv_kernel, bt=bt, t=t, groups=groups,
                               cap=cap)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pl.cdiv(t, bt)),
            in_specs=[q_spec, cache_spec, cache_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((groups, hkv, 1), jnp.float32),
                            pltpu.VMEM((groups, hkv, 1), jnp.float32),
                            pltpu.VMEM((groups, hkv, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(scalars, q, k, v)


def _latent_kernel(sc_ref, ql_ref, qp_ref, c_ref, kpe_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, bt: int, t: int, scale: float,
                   precision):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _M_INIT, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    c = c_ref[...]                                     # (bt, r)
    if t % bt:                  # a ragged block: its rows past t weigh 0
        c = jnp.where(_ages_valid(sc_ref, tb, bt, t, (bt, 1), 0), c, 0.0)
    s = (jax.lax.dot_general(ql_ref[...], c, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=jnp.float32)
         + jnp.dot(qp_ref[...], kpe_ref[...], precision=precision,
                   preferred_element_type=jnp.float32)
         ) * scale                                     # (H, bt)
    valid = _ages_valid(sc_ref, tb, bt, t, s.shape, 1)
    s = jnp.where(valid, s, _MASKED)
    m_prev = m_ref[...]                                # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + jnp.dot(
        p.astype(c.dtype), c, precision=precision,
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(tb == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "precision", "interpret"))
def latent_decode_attention(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                            c: jnp.ndarray, kpe: jnp.ndarray, layer, slot,
                            n_valid, *, scale: float, precision=None,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """The latents' softmax-weighted sum for one token per sequence over
    layer ``layer`` of a latent stack. ``q_lat`` (B, H, r) is the query
    taken into the latent, ``q_pe`` (B, H, dr) its rope part; ``c`` (L,
    B, T, r) and ``kpe`` (L, B, dr, T), positions last; scores ``(q_lat
    . c + q_pe . kpe) * scale``; ``slot``/``n_valid`` as
    :func:`kv_decode_attention`'s; ``precision`` that of the products.
    -> (B, H, r) in ``q_lat``'s dtype."""
    b, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    t = c.shape[2]
    bt = time_block(t, (r + dr) * c.dtype.itemsize, align=128)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32)
                         for x in (layer, slot, n_valid)])

    def q_spec(width):
        return pl.BlockSpec((None, h, width), lambda i, j, sc: (i, 0, 0))

    kernel = functools.partial(_latent_kernel, bt=bt, t=t, scale=scale,
                               precision=precision)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pl.cdiv(t, bt)),
            in_specs=[q_spec(r), q_spec(dr),
                      pl.BlockSpec((None, None, bt, r),
                                   lambda i, j, sc: (sc[0], i, j, 0)),
                      pl.BlockSpec((None, None, dr, bt),
                                   lambda i, j, sc: (sc[0], i, 0, j))],
            out_specs=q_spec(r),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, r), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(scalars, q_lat, q_pe, c, kpe)
