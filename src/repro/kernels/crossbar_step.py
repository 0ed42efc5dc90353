"""Pallas TPU kernel: batched stateful-logic execution over crossbar rows.

TPU adaptation of the paper's row-parallelism (Section II-A): crossbar
rows are the batch axis; one grid cell processes a VMEM-resident tile of
rows through ALL T cycles of a compiled PIM program.

Hardware mapping (this is the hw-codesign part — the memristive
gather/scatter has no direct TPU analogue, so it is re-expressed as
MXU work):

* *gather* of gate operands (columns ``in_cols[t,:,j]``) is a matmul of
  the state tile (Rb, C) against a one-hot matrix (C, M) built on the
  VPU from an iota comparison — no dynamic lane indexing, MXU-friendly;
* *gate evaluation* is branchless VPU select arithmetic over the (Rb, M)
  operand tiles (NOT/NOR/MIN3/NAND/OR/COPY share one sum-based form);
* *scatter* (MAGIC's pull-down write, ``new = old AND result``) is a
  second one-hot matmul plus a column mask: ``state *= min(res @ OH +
  (colmask == 0), 1)``; padded NOP ops write constant 1 into a scratch
  column, which the min() makes side-effect free.

Block shapes: rows are tiled by ``row_block`` (default 256, multiple of
the 8-sublane f32 tile); the full padded column axis (multiple of 128
lanes) stays resident. VMEM footprint per tile ~= (Rb + 3M) * C * 4B +
tables; for MultPIM-32 (C=512 padded, T=611, M<=33) that is ~1.9 MB —
comfortably inside the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.executor import PackedProgram
from repro.core.isa import Gate
from repro.runtime import resolve_interpret

__all__ = ["crossbar_run_pallas", "crossbar_run_pallas_packed", "op_stream"]


def _gate_eval(gid, x0, x1, x2):
    """Branchless gate evaluation; operands are (Rb, M) f32 in {0,1}."""
    s2 = x0 + x1
    s3 = s2 + x2
    res_not = 1.0 - x0
    res_nor = (s2 == 0).astype(jnp.float32)
    res_min3 = (s3 <= 1.0).astype(jnp.float32)
    res_nand = 1.0 - x0 * x1
    res_or = (s2 >= 1.0).astype(jnp.float32)
    gid = gid[None, :]
    out = jnp.ones_like(x0)  # NOP
    out = jnp.where(gid == int(Gate.NOT), res_not, out)
    out = jnp.where(gid == int(Gate.NOR), res_nor, out)
    out = jnp.where(gid == int(Gate.MIN3), res_min3, out)
    out = jnp.where(gid == int(Gate.NAND), res_nand, out)
    out = jnp.where(gid == int(Gate.OR), res_or, out)
    out = jnp.where(gid == int(Gate.COPY), x0, out)
    return out


def _kernel(state_ref, gate_ref, in0_ref, in1_ref, in2_ref, out_ref,
            init_ref, o_ref, *, n_cycles: int, n_cols: int):
    state = state_ref[...]
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)

    def one_hot(idx):  # (M,) int32 -> (M, C) f32
        return (col_iota == idx[:, None]).astype(jnp.float32)

    def body(t, st):
        st = jnp.maximum(st, init_ref[t][None, :])
        gid = gate_ref[t]
        x0 = jnp.dot(st, one_hot(in0_ref[t]).T,
                     preferred_element_type=jnp.float32)
        x1 = jnp.dot(st, one_hot(in1_ref[t]).T,
                     preferred_element_type=jnp.float32)
        x2 = jnp.dot(st, one_hot(in2_ref[t]).T,
                     preferred_element_type=jnp.float32)
        res = _gate_eval(gid, x0, x1, x2)
        oh_out = one_hot(out_ref[t])
        contrib = jnp.dot(res, oh_out, preferred_element_type=jnp.float32)
        colmask = jnp.sum(oh_out, axis=0)[None, :]
        upd = jnp.minimum(contrib + (colmask == 0).astype(jnp.float32), 1.0)
        return st * upd

    state = jax.lax.fori_loop(0, n_cycles, body, state)
    o_ref[...] = state


@functools.partial(jax.jit, static_argnames=("row_block", "interpret",
                                             "t", "m", "c"))
def _run(state, gate_id, in0, in1, in2, out_col, init_mask, *,
         row_block: int, interpret: bool, t: int, m: int, c: int):
    rows = state.shape[0]
    grid = (rows // row_block,)
    kernel = functools.partial(_kernel, n_cycles=t, n_cols=c)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((t, m), lambda i: (0, 0)),
            pl.BlockSpec((t, m), lambda i: (0, 0)),
            pl.BlockSpec((t, m), lambda i: (0, 0)),
            pl.BlockSpec((t, m), lambda i: (0, 0)),
            pl.BlockSpec((t, m), lambda i: (0, 0)),
            pl.BlockSpec((t, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c), jnp.float32),
        interpret=interpret,
    )(state, gate_id, in0, in1, in2, out_col, init_mask)


# ------------------------------------------------ bit-plane packed ----
#
# The packed variant trades the one-hot-matmul mapping for word-wide
# bitwise execution. Crossbar rows are packed 32-per-uint32 word
# (repro.core.bits.pack_rows) and the state is held *column-major*:
# ``(C, Wr, 128)`` int32, so one crossbar column of a tile is a full
# ``(SUBLANES, 128)`` vreg reached by a dynamic index on the untiled
# leading axis — Mosaic never indexes the lane axis dynamically.
#
# The program runs as a flat stream of self-describing entries read from
# SMEM (:func:`op_stream`): every gate is rewritten as a (possibly
# complemented) 3-input majority over state columns, with two constant
# columns (all-zeros, all-ones) appended to the state,
#
#   res = maj(x_a, x_b, x_c) ^ inv        new = (old & res) | set
#
# so NOT/NOR/MIN3/NAND/OR/COPY, a MAGIC AND-write, an INIT SET and a
# padding NOP are one branch-free loop body. Ops of one cycle touch
# disjoint partitions (Program.validate), so executing a cycle's ops in
# sequence is exact; the stream builder checks that per cycle. The
# stream is tiled over an ``arbitrary`` grid axis in SMEM blocks of
# ``STREAM_CHUNK`` entries while the state tile stays in its VMEM output
# block across the chunks.

SUBLANES = 8            # words per tile = SUBLANES * 128 (one vreg/column)
STREAM_CHUNK = 2048     # stream entries per SMEM block
_COL_BITS = 14          # column index field width (C + 2 < 16384)


def _majority_operands(gid: int, ins, zero: int, one: int):
    """``(a, b, c, inv)`` with ``maj(a, b, c) ^ inv`` == gate ``gid``."""
    x0, x1, x2 = (int(v) for v in ins)
    if gid == Gate.NOT:
        return x0, x0, x0, 1
    if gid == Gate.NOR:
        return x0, x1, one, 1
    if gid == Gate.MIN3:
        return x0, x1, x2, 1
    if gid == Gate.NAND:
        return x0, x1, zero, 1
    if gid == Gate.OR:
        return x0, x1, one, 0
    if gid == Gate.COPY:
        return x0, x0, x0, 0
    raise ValueError(f"gate id {gid} has no packed encoding")


def op_stream(packed: PackedProgram) -> np.ndarray:
    """``packed`` as the kernel's ``(n_chunks * STREAM_CHUNK * 2,)``
    int32 entry stream (memoized on ``packed``). Entry ``i`` is the word
    pair ``a | b << 16`` and ``c | inv << 14 | set << 15 | out << 16``;
    columns ``C`` and ``C + 1`` of the kernel state are the constant
    zero and one columns. The tail is padded with NOPs."""
    hit = getattr(packed, "_pallas_stream", None)
    if hit is not None:
        return hit
    c = packed.init_mask.shape[1]
    zero, one = c, c + 1
    if one >= 1 << _COL_BITS:
        raise ValueError(f"{c} columns exceed the packed kernel's "
                         f"{_COL_BITS}-bit column field")
    ents = []
    for t in range(packed.n_cycles):
        set_cols = np.flatnonzero(packed.init_mask[t])
        ents.extend((zero, zero, zero, int(col), 1, 1) for col in set_cols)
        real = packed.gate_id[t] != int(Gate.NOP)
        outs = packed.out_col[t][real]
        ins = packed.in_cols[t][real]
        for j, (gid, out) in enumerate(zip(packed.gate_id[t][real], outs)):
            others = np.delete(outs, j)
            if np.isin(ins[j], others).any():
                raise ValueError(
                    f"cycle {t}: an op reads a column another op of the "
                    f"same cycle writes; the packed kernel runs a "
                    f"cycle's ops in sequence")
            a, b, cc, inv = _majority_operands(int(gid), ins[j], zero, one)
            ents.append((a, b, cc, int(out), inv, 0))
    n = max(1, len(ents))
    pad = -(-n // STREAM_CHUNK) * STREAM_CHUNK - len(ents)
    ents.extend([(zero, zero, zero, zero, 1, 0)] * pad)   # NOP
    e = np.asarray(ents, dtype=np.int64)
    w0 = e[:, 0] | (e[:, 1] << 16)
    w1 = e[:, 2] | (e[:, 4] << 14) | (e[:, 5] << 15) | (e[:, 3] << 16)
    stream = np.stack([w0, w1], axis=1).reshape(-1)
    stream = stream.astype(np.uint32).view(np.int32)
    packed._pallas_stream = stream
    return stream


def _packed_kernel(tab_ref, st_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _load():
        o_ref[...] = st_ref[...]

    srl = jax.lax.shift_right_logical

    def body(i, carry):
        w0 = tab_ref[2 * i]
        w1 = tab_ref[2 * i + 1]
        xa = o_ref[w0 & 0xFFFF]
        xb = o_ref[srl(w0, 16)]
        xc = o_ref[w1 & ((1 << _COL_BITS) - 1)]
        inv = -(srl(w1, _COL_BITS) & 1)
        set_ = -(srl(w1, 15) & 1)
        res = ((xa & xb) | (xc & (xa | xb))) ^ inv
        out = srl(w1, 16)
        o_ref[out] = (o_ref[out] & res) | set_
        return carry

    jax.lax.fori_loop(0, STREAM_CHUNK, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_packed(words, stream, *, interpret: bool):
    """``(W, C)`` uint32 words -> final words, one Pallas launch."""
    n_words, c = words.shape
    tile = SUBLANES * 128
    w_pad = -(-max(n_words, 1) // tile) * tile
    st = jax.lax.bitcast_convert_type(words, jnp.int32).T
    st = jnp.pad(st, ((0, 0), (0, w_pad - n_words)))
    st = jnp.concatenate([st, jnp.zeros((1, w_pad), jnp.int32),
                          jnp.full((1, w_pad), -1, jnp.int32)])
    st = st.reshape(c + 2, w_pad // 128, 128)
    n_chunks = stream.shape[0] // (2 * STREAM_CHUNK)
    block = (c + 2, SUBLANES, 128)
    out = pl.pallas_call(
        _packed_kernel,
        grid=(w_pad // tile, n_chunks),
        in_specs=[
            pl.BlockSpec((2 * STREAM_CHUNK,), lambda i, j: (j,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(block, lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(st.shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(stream, st)
    out = out[:c].reshape(c, w_pad)[:, :n_words].T
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def crossbar_run_pallas_packed(state_words: jnp.ndarray,
                               packed: PackedProgram, *,
                               interpret: Optional[bool] = None
                               ) -> jnp.ndarray:
    """Run a packed PIM program on bit-plane packed ``(W, C)`` uint32
    words (:func:`repro.core.bits.pack_rows` with ``word_bits=32``);
    returns the final ``(W, C)`` uint32 words. Words are tiled
    ``SUBLANES * 128`` at a time; ``interpret`` defaults to the
    platform (:func:`repro.runtime.resolve_interpret`).
    """
    # Device-resident stream memoized per program: decode traffic re-runs
    # the same program, so the encode and the upload happen once.
    stream = getattr(packed, "_pallas_stream_dev", None)
    if stream is None:
        stream = jnp.asarray(op_stream(packed))
        packed._pallas_stream_dev = stream
    return _run_packed(state_words, stream,
                       interpret=resolve_interpret(interpret))


def crossbar_run_pallas(state_bits: jnp.ndarray, packed: PackedProgram,
                        row_block: int = 256,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Run a packed PIM program on a (rows, cols) {0,1} state tensor.

    Rows are padded to ``row_block`` and columns to a 128-lane multiple;
    returns uint8 (rows, packed.init_mask.shape[1]).
    """
    rows, cols = state_bits.shape
    c_pad = int(np.ceil(cols / 128) * 128)
    r_pad = int(np.ceil(rows / row_block) * row_block)
    st = jnp.zeros((r_pad, c_pad), jnp.float32)
    st = st.at[:rows, :cols].set(state_bits.astype(jnp.float32))

    T, M = packed.gate_id.shape
    init = np.zeros((T, c_pad), np.float32)
    init[:, :packed.init_mask.shape[1]] = packed.init_mask
    out = _run(st,
               jnp.asarray(packed.gate_id),
               jnp.asarray(packed.in_cols[:, :, 0]),
               jnp.asarray(packed.in_cols[:, :, 1]),
               jnp.asarray(packed.in_cols[:, :, 2]),
               jnp.asarray(packed.out_col),
               jnp.asarray(init),
               row_block=row_block, interpret=resolve_interpret(interpret),
               t=T, m=M, c=c_pad)
    return out[:rows, :cols].astype(jnp.uint8)
