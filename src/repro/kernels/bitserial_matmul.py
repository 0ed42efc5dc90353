"""Pallas TPU kernel: CSAS-style bit-serial fixed-point matmul.

TPU-native adaptation of MultPIM's multiplier structure for the
Section-VI use case (fixed-point DNN mat-muls). The memristive CSAS
multiplier streams one bit of ``b`` per stage, forms a partial product,
and defers carries (carry-save). The TPU analogue:

* the *streamed operand* becomes bit-planes of the activations
  (``x = sum_j 2^j X_j`` with ``X_j in {0,1}``);
* each *stage* is an MXU matmul of one bit-plane tile against the
  weight tile — the paper's "partial product + carry-save add" becomes
  ``acc += 2^j * (X_j @ W)`` with the float accumulator playing the
  carry-save register (no carry propagation until the final store);
* the *broadcast* of b_k across partitions (Section III-A) becomes the
  MXU's systolic operand broadcast; the *shift* (Section III-B) becomes
  the power-of-two scale folded into the accumulate.

Block shapes are MXU-aligned (multiples of 128 on both matmul dims);
the grid walks (M/bm, N/bn, K/bk) with K innermost so the accumulator
tile stays VMEM-resident across the reduction.

Exactness: all values are small integers; f32 accumulation is exact up
to 2^24, asserted by the wrapper (inputs are n_bits <= 8 quantized and
K bounded accordingly), so the kernel is bit-identical to the PIM
simulator's fixed-point semantics (validated in tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.runtime import resolve_interpret

__all__ = ["bitserial_matmul_pallas"]


def _kernel(xp_ref, w_ref, o_ref, *, n_bits: int, n_k: int):
    # K is the innermost grid axis, so this output tile stays resident in
    # VMEM across the whole reduction (the "carry-save accumulator").
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = o_ref[...]
    for j in range(n_bits):   # unrolled: n_bits is small and static
        plane = xp_ref[j]
        acc += (2.0 ** j) * jnp.dot(plane, w_ref[...],
                                    preferred_element_type=jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "n_bits"))
def _run(x_planes, w, *, bm, bn, bk, interpret, n_bits):
    NB, M, K = x_planes.shape
    N = w.shape[1]
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, n_bits=n_bits, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((NB, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x_planes, w)


def bitserial_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray, n_bits: int = 8,
                            bm: int = 128, bn: int = 128, bk: int = 128,
                            interpret: Optional[bool] = None
                            ) -> jnp.ndarray:
    """``x`` (M, K) non-negative ints < 2^n_bits, ``w`` (K, N) f32.

    Returns f32 (M, N) == x @ w computed via bit-plane accumulation.
    """
    M, K = x.shape
    N = w.shape[1]
    assert K * (2 ** n_bits) < 2 ** 24, "f32 exactness bound"
    x = jnp.asarray(x, jnp.int32)
    planes = jnp.stack([((x >> j) & 1).astype(jnp.float32)
                        for j in range(n_bits)])
    m_pad = int(np.ceil(M / bm) * bm)
    k_pad = int(np.ceil(K / bk) * bk)
    n_pad = int(np.ceil(N / bn) * bn)
    planes = jnp.pad(planes, ((0, 0), (0, m_pad - M), (0, k_pad - K)))
    w_p = jnp.pad(w.astype(jnp.float32), ((0, k_pad - K), (0, n_pad - N)))
    out = _run(planes, w_p, bm=bm, bn=bn, bk=bk,
               interpret=resolve_interpret(interpret),
               n_bits=n_bits)
    return out[:M, :N]
