"""Pure-jnp oracles for the Pallas kernels (the allclose references).

Two executors live here:

* :func:`crossbar_run_ref` — the original per-cell scan: state is
  ``(rows, C)`` uint8 {0,1}, one lane per cell, one scan step per cycle.
* :func:`crossbar_run_ref_packed` — the bit-plane packed scan: rows are
  packed 32-per-``uint32`` word (:func:`repro.core.bits.pack_rows`;
  32-bit words because JAX runs with x64 disabled and TPUs are 32-bit
  machines), every gate evaluates word-wide with pure bitwise ops
  (``NOR = ~(x0|x1)``, ``MIN3 = ~majority3`` — minority-of-3 is the
  complement of majority-of-3), and consecutive cycles are macro-fused
  (:mod:`repro.compiler.macrocycle`) so the scan runs
  ``ceil(T/factor)`` steps with a ``factor``-deep unrolled body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.executor import PackedProgram, gate_eval_packed
from repro.core.isa import Gate

__all__ = ["crossbar_run_ref", "crossbar_run_ref_packed",
           "crossbar_run_ref_packed_faulty",
           "packed_scan_body", "packed_device_tables"]


def crossbar_run_ref(state_bits: jnp.ndarray, packed: PackedProgram
                     ) -> jnp.ndarray:
    """lax.scan executor over the packed tables (uint8 semantics)."""
    tables = (jnp.asarray(packed.gate_id), jnp.asarray(packed.in_cols),
              jnp.asarray(packed.out_col), jnp.asarray(packed.init_mask))

    def step(st, tabs):
        gid, ics, ocs, imask = tabs
        st = jnp.where(imask, jnp.uint8(1), st)
        x0 = st[:, ics[:, 0]].astype(jnp.int32)
        x1 = st[:, ics[:, 1]].astype(jnp.int32)
        x2 = st[:, ics[:, 2]].astype(jnp.int32)
        s3 = x0 + x1 + x2
        res = jnp.select(
            [gid == int(Gate.NOT), gid == int(Gate.NOR),
             gid == int(Gate.MIN3), gid == int(Gate.NAND),
             gid == int(Gate.OR), gid == int(Gate.COPY)],
            [1 - x0, ((x0 + x1) == 0).astype(jnp.int32),
             (s3 <= 1).astype(jnp.int32), 1 - x0 * x1,
             ((x0 + x1) >= 1).astype(jnp.int32), x0],
            default=jnp.int32(1),
        ).astype(jnp.uint8)
        st = st.at[:, ocs].min(res)
        return st, None

    pad = packed.init_mask.shape[1] - state_bits.shape[1]
    st = jnp.pad(state_bits.astype(jnp.uint8), ((0, 0), (0, pad)))
    st, _ = jax.lax.scan(step, st, tables)
    return st[:, :state_bits.shape[1]]


def packed_scan_body(st, gate_id, in_cols, out_col, init_words, *,
                     factor: int):
    """The packed-scan computation itself, **not** jitted — composable
    inside larger jitted programs (the resident execution path fuses
    stage + MAC scans plus the inter-pass column moves into a single
    dispatch). ``st`` is ``(W, C)`` uint32 words at the full packed
    table width; the table args come from :func:`packed_device_tables`.
    """
    def step(st, tabs):
        gids, icss, ocss, inis = tabs
        for j in range(factor):
            gid, ics, ocs, ini = gids[j], icss[j], ocss[j], inis[j]
            st = st | ini[None, :]          # batched SET: word-wide OR
            # All gathers before the write: ops in a cycle are
            # simultaneous and observe pre-cycle state.
            x0 = st[:, ics[:, 0]]
            x1 = st[:, ics[:, 1]]
            x2 = st[:, ics[:, 2]]
            res = gate_eval_packed(jnp, gid[None, :], x0, x1, x2)
            # Gather-AND-scatter write: XLA keeps this in place inside
            # the scan, where a full-ones update plane would copy the
            # whole state per cycle. Duplicate output columns exist only
            # at the side-effect-free scratch column (NOP padding),
            # where any single write is as good as the AND of all.
            st = st.at[:, ocs].set(st[:, ocs] & res)
        return st, None

    st, _ = jax.lax.scan(step, st, (gate_id, in_cols, out_col, init_words))
    return st


_packed_scan = functools.partial(jax.jit,
                                 static_argnames=("factor",))(packed_scan_body)


def packed_device_tables(packed: PackedProgram, macro: int = 1):
    """``(tables, factor)`` for :func:`packed_scan_body`: the macro-fused
    dense tables as device arrays, memoized per ``(program, factor)`` on
    the packed object — decode traffic re-runs the same program, so the
    host->device upload happens once, and jit caches keyed on these
    arrays stay warm across calls."""
    from repro.compiler.macrocycle import fuse_macrocycles
    mt = fuse_macrocycles(packed, macro)
    cache = getattr(packed, "_jax_table_cache", None)
    if cache is None:
        cache = {}
        packed._jax_table_cache = cache
    tabs = cache.get(mt.factor)
    if tabs is None:
        tabs = (jnp.asarray(mt.gate_id), jnp.asarray(mt.in_cols),
                jnp.asarray(mt.out_col), jnp.asarray(mt.init_words))
        cache[mt.factor] = tabs
    return tabs, mt.factor


def crossbar_run_ref_packed(state_words: jnp.ndarray, packed: PackedProgram,
                            macro: int = 1) -> jnp.ndarray:
    """Bit-plane packed lax.scan executor (see module docstring).

    ``state_words`` is ``(W, C)`` uint32 from
    :func:`repro.core.bits.pack_rows` with ``word_bits=32``; returns the
    final ``(W, C)`` uint32 words (``C`` = the packed table width).
    ``macro`` is the macro-cycle fusion factor: the scan runs over
    ``ceil(T/macro)`` fused steps, each unrolling ``macro`` cycles.
    """
    tabs, factor = packed_device_tables(packed, macro)
    pad = packed.init_mask.shape[1] - state_words.shape[1]
    st = jnp.pad(state_words.astype(jnp.uint32), ((0, 0), (0, pad)))
    st = _packed_scan(st, *tabs, factor=factor)
    return st[:, :state_words.shape[1]]


@jax.jit
def _faulty_scan(st, gate_id, in_cols, out_col, init_words, flips, sa0, sa1):
    """Cycle-at-a-time packed scan with fault masks threaded through:
    the jnp twin of :func:`repro.faults.numpy_kernel_packed_faulty`
    (same cycle semantics — SET, gather, gate^flip, AND-write, stuck).
    Tables are factor-1 :func:`packed_device_tables`; ``flips`` is
    ``(T, W, M)`` per-cycle flip words, the stuck maps ``(W, C)``."""
    st = (st & ~sa0) | sa1
    def step(st, tabs):
        gids, icss, ocss, inis, flip = tabs
        gid, ics, ocs, ini = gids[0], icss[0], ocss[0], inis[0]
        st = st | ini[None, :]
        x0 = st[:, ics[:, 0]]
        x1 = st[:, ics[:, 1]]
        x2 = st[:, ics[:, 2]]
        res = gate_eval_packed(jnp, gid[None, :], x0, x1, x2, flip=flip)
        # Flips are drawn only on real gate slots (gate_id != NOP), so
        # duplicate scratch writes stay all-ones and any-winner .set
        # matches numpy's AND-accumulating scatter bit for bit.
        st = st.at[:, ocs].set(st[:, ocs] & res)
        st = (st & ~sa0) | sa1
        return st, None

    st, _ = jax.lax.scan(step, st,
                         (gate_id, in_cols, out_col, init_words, flips))
    return st


def crossbar_run_ref_packed_faulty(state_words: jnp.ndarray,
                                   packed: PackedProgram, model,
                                   rows: int) -> jnp.ndarray:
    """One *faulty* pass of ``packed`` over 32-bit packed state: draws
    the pass's fault tensors from ``model``
    (:func:`repro.faults.pass_fault_tensors` — advances the model's
    monotone pass counter) and runs the fault-injecting scan. Always
    cycle-at-a-time: flip sites index per-cycle tables, so macro fusion
    is bypassed on this path. Serves both the jax and pallas backends
    when a fault model is active (injection is a simulation study — the
    Pallas kernel remains the fault-free performance path).
    """
    from repro.faults.inject import pass_fault_tensors
    flips, sa0, sa1 = pass_fault_tensors(model, packed, rows, 32)
    tabs, _ = packed_device_tables(packed, 1)
    pad = packed.init_mask.shape[1] - state_words.shape[1]
    st = jnp.pad(state_words.astype(jnp.uint32), ((0, 0), (0, pad)))
    st = _faulty_scan(st, *tabs, jnp.asarray(flips), jnp.asarray(sa0),
                      jnp.asarray(sa1))
    return st[:, :state_words.shape[1]]

