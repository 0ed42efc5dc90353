"""Execution backends: one protocol over numpy / JAX scan / Pallas.

A :class:`Backend` turns a packed program plus an initial crossbar state
``(rows, C)`` of {0,1} into the final state, bit-identically across
implementations (the engine test suite asserts parity). All three stock
backends interpret the *same* dense tables
(:class:`~repro.core.executor.PackedProgram`), so a compiled
:class:`~repro.engine.Executable` can hop backends without recompiling.

Stock registry entries:

* ``"numpy"``  — pure-numpy interpreter over the packed tables (the
  debugging / small-batch reference; no JAX import needed);
* ``"jax"``    — jitted ``lax.scan`` over the tables
  (:func:`repro.kernels.ref.crossbar_run_ref`);
* ``"pallas"`` — the Mosaic TPU kernel
  (:func:`repro.kernels.crossbar_step.crossbar_run_pallas`), with a
  ``row_block`` row-tiling policy (rows are the SIMD batch axis). It
  runs under the Pallas interpreter exactly when JAX's default backend
  is the CPU (:func:`repro.runtime.resolve_interpret`); an
  explicit ``interpret=true`` is honoured on any platform.

With no backend given, :func:`resolve_backend` derives one from the
platform (:func:`platform_default_spec`): the numpy reference on the
CPU, the packed jax scan on an accelerator.

Every stock backend additionally carries a **bit-plane packing policy**
(``pack=True``, spec-selectable as e.g. ``"jax:pack=true"``): crossbar
rows — the SIMD batch axis — are packed 64-per-``uint64`` word (numpy)
or 32-per-``uint32`` (JAX/Pallas, which run 32-bit), and every gate
evaluates word-wide with pure bitwise ops
(:func:`repro.core.executor.gate_eval_packed`) instead of one uint8 lane
per cell. Packing is internal to ``run_state`` — the ``(rows, C)``
{0,1} contract is unchanged and bit-parity with the unpacked
interpreters is asserted by the test suite — so ``Executable``,
``BatchedExecutable`` and ``GroupedExecutable`` all benefit without API
changes. The JAX packed scan also macro-fuses consecutive cycles
(``macro=``, :mod:`repro.compiler.macrocycle`) so it executes
``O(T/factor)`` scan steps instead of one per cycle; the packed Pallas
kernel runs the program as one flat op stream and needs no fusion.

Every stock backend also carries a **fault policy** (``faults=<key>``,
e.g. ``"jax:pack=true,faults=flip@1e-5@7"``): the key resolves through
:func:`repro.faults.get_fault_model` to a seeded device-error model
whose transient flips and stuck-at maps are injected as bitwise masks
into the packed interpreters (:func:`backend_fault_model` is the single
resolution point). ``faults=none`` (or omitting the option) resolves to
no model and leaves every path bit-identical to a fault-free build —
regression-tested. Fault injection requires the packed representation:
jax/pallas demand ``pack=true``, and the numpy backend transparently
promotes to its 64-bit packed interpreter.

``resolve_backend`` accepts a Backend instance, a registered name, or a
``"name:key=val,key=val"`` spec string — e.g. ``"pallas:pack=true"``
or ``"jax:pack=true,macro=8"`` — so CLI flags map directly onto backend
policy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Protocol, Union, runtime_checkable

import numpy as np

from repro import obs
from repro.compiler.macrocycle import DEFAULT_MACRO_FACTOR as DEFAULT_MACRO
from repro.core.bits import pack_rows, unpack_rows
from repro.core.executor import PackedProgram, gate_eval_packed
from repro.core.isa import Gate

__all__ = ["Backend", "NumpyBackend", "JaxBackend", "PallasBackend",
           "ResidentIndex", "supports_resident", "register_backend",
           "resolve_backend", "backend_names", "backend_fault_model",
           "platform_default_spec",
           "autotune_row_block",
           "DEFAULT_ROW_BLOCK", "MAX_ROW_BLOCK", "DEFAULT_MACRO"]


def backend_fault_model(backend):
    """The backend's resolved :class:`repro.faults.FaultModel`, or
    ``None`` when faults are inactive — the single place a ``faults=``
    spec becomes behavior. Inactive covers: no ``faults`` field, a
    ``none``/``off`` key, and a model whose every rate is zero (so an
    explicitly-zeroed model still takes the fault-free fast path and
    stays bit-identical)."""
    spec = getattr(backend, "faults", None)
    if spec is None:
        return None
    from repro.faults import get_fault_model
    model = get_fault_model(spec)
    if model is None or not model.active():
        return None
    return model


@runtime_checkable
class Backend(Protocol):
    """Executes packed programs over batched crossbar state."""

    name: str

    def run_state(self, packed: PackedProgram,
                  state: np.ndarray) -> np.ndarray:
        """``state`` (rows, C) {0,1} with C == packed table width; returns
        the final (rows, C) state after all cycles."""
        ...


# ------------------------------------------------------------- resident ----
@dataclass(frozen=True)
class ResidentIndex:
    """Static column wiring of a resident MAC chain (mac/stage/recomb),
    precomputed by :class:`~repro.engine.executable.ResidentExecutable`
    from the three compiled programs' input/output maps. Every transfer
    between programs is a device-side column gather/scatter between
    freshly-zeroed states — no physical column aliasing is assumed, so
    the wiring survives the optimizer's column remapping.
    """

    c_mac: int          # packed table widths (incl. scratch column)
    c_stage: int
    c_rec: int
    ab_cols: np.ndarray      # mac inputs a ++ b       (new operand planes)
    un_cols: np.ndarray      # mac input un            (fresh lanes -> 1)
    slo_cols: np.ndarray     # mac input s_lo          (fresh lanes -> 0)
    cn_cols: np.ndarray      # mac input c_lo_n        (always 1; c_lo = 0
    #                          stays at the zeroed alloc — see staging.py)
    stage_src: np.ndarray    # mac outputs s_hi ++ c_hi ++ lo
    stage_dst: np.ndarray    # stage inputs s_hi ++ c_hi ++ lo
    mac_src: np.ndarray      # stage outputs un ++ s_lo
    mac_dst: np.ndarray      # mac inputs   un ++ s_lo
    rec_dst: np.ndarray      # recomb inputs s_hi ++ c_hi ++ lo
    rec_out: np.ndarray      # recomb output out (2n bits)
    # Optional residue-check wiring (detect mode, repro.faults): the
    # compiled "residue" program reads the same carry-save planes as
    # recomb and emits the 5-bit (mod-3 ++ mod-7) residue pair.
    c_res: int = 0
    res_dst: Optional[np.ndarray] = None  # residue inputs s_hi++c_hi++lo
    res_out: Optional[np.ndarray] = None  # residue outputs r3 ++ r7


def _until_ready(out):
    """``out``, waited for while the tracer is on: the ``backend.kernel``
    span around a dispatch then ends when the kernel does, and
    ``backend.unpack`` holds only the host's unpacking. With tracing off
    nothing waits."""
    if obs.enabled():
        out.block_until_ready()
    return out


class _ChainBase:
    """Shared packing helpers for the resident chains. A chain owns the
    live device state representation for ``rows`` parallel MAC chains
    (rows are the crossbar's SIMD axis — serve slots, matvec rows);
    ``first``/``step`` advance every lane one MAC pass, ``drain`` runs
    the recombination program on a *separate* state and unpacks only its
    ``out`` planes — the single host transfer of a chain's lifetime.
    With a ``residue`` program attached (detect mode), ``residue(dev)``
    likewise runs the mod-3/mod-7 check on a separate state and unpacks
    only its 5 result planes.
    """

    def __init__(self, mac, stage, recomb, idx: ResidentIndex, rows: int,
                 word_bits: Optional[int], residue=None):
        self.mac, self.stage, self.recomb = mac, stage, recomb
        self.res = residue
        self.idx = idx
        self.rows = rows
        self.word_bits = word_bits

    def _pack(self, planes: np.ndarray) -> np.ndarray:
        if self.word_bits is None:
            return np.asarray(planes, dtype=np.uint8)
        return pack_rows(np.asarray(planes, dtype=np.uint8),
                         self.word_bits)

    def _pack_mask(self, mask: np.ndarray) -> np.ndarray:
        """(rows,) bool -> the per-lane broadcast column: (rows, 1) uint8
        lanes unpacked, (W, 1) packed words with one bit per fresh lane."""
        return self._pack(np.asarray(mask, dtype=np.uint8)[:, None])


class _NumpyChain(_ChainBase):
    """Eager numpy resident chain (unpacked uint8 or 64-wide packed).

    An active fault model promotes the chain to the 64-bit packed
    representation regardless of ``pack`` (fault masks are packed
    words) and routes every program pass through the fault-injecting
    kernel."""

    def __init__(self, backend: "NumpyBackend", mac, stage, recomb,
                 idx: ResidentIndex, rows: int, residue=None):
        self.model = backend_fault_model(backend)
        packed_words = backend.pack or self.model is not None
        super().__init__(mac, stage, recomb, idx, rows,
                         64 if packed_words else None, residue=residue)
        self.backend = backend
        if packed_words:
            self._w = -(-rows // 64)
            self._full = ~np.uint64(0)
            self._dt = np.uint64
        else:
            self._w = rows
            self._full = np.uint8(1)
            self._dt = np.uint8

    def _zeros(self, c: int) -> np.ndarray:
        return np.zeros((self._w, c), dtype=self._dt)

    def _run(self, packed: PackedProgram, st: np.ndarray) -> np.ndarray:
        with obs.span("backend.kernel", backend=self.backend.name,
                      rows=self.rows, cycles=packed.n_cycles,
                      faulty=self.model is not None):
            if self.model is not None:
                from repro.faults.inject import (numpy_kernel_packed_faulty,
                                                 pass_fault_tensors)
                flips, sa0, sa1 = pass_fault_tensors(
                    self.model, packed, self.rows, 64)
                return numpy_kernel_packed_faulty(packed, st, flips,
                                                  sa0, sa1)
            if self.word_bits is None:
                return NumpyBackend._kernel_unpacked(packed, st)
            return NumpyBackend._kernel_packed(packed, st)

    def first(self, planes: np.ndarray) -> np.ndarray:
        idx = self.idx
        st = self._zeros(idx.c_mac)
        st[:, idx.un_cols] = self._full
        st[:, idx.cn_cols] = self._full
        st[:, idx.ab_cols] = self._pack(planes)
        return self._run(self.mac, st)

    def step(self, dev: np.ndarray, planes: np.ndarray,
             fresh: np.ndarray) -> np.ndarray:
        idx = self.idx
        sst = self._zeros(idx.c_stage)
        sst[:, idx.stage_dst] = dev[:, idx.stage_src]
        sst = self._run(self.stage, sst)
        st = self._zeros(idx.c_mac)
        st[:, idx.mac_dst] = sst[:, idx.mac_src]
        st[:, idx.cn_cols] = self._full
        if fresh.any():
            fw = self._pack_mask(fresh)
            st[:, idx.un_cols] |= fw
            st[:, idx.slo_cols] &= ~fw if self.word_bits else 1 - fw
        st[:, idx.ab_cols] = self._pack(planes)
        return self._run(self.mac, st)

    def drain(self, dev: np.ndarray) -> np.ndarray:
        idx = self.idx
        rst = self._zeros(idx.c_rec)
        rst[:, idx.rec_dst] = dev[:, idx.stage_src]
        rst = self._run(self.recomb, rst)
        out = rst[:, idx.rec_out]
        if self.word_bits is None:
            return out
        with obs.span("backend.unpack", backend=self.backend.name,
                      rows=self.rows):
            return unpack_rows(np.ascontiguousarray(out), self.rows)

    def residue(self, dev: np.ndarray) -> np.ndarray:
        idx = self.idx
        rst = self._zeros(idx.c_res)
        rst[:, idx.res_dst] = dev[:, idx.stage_src]
        rst = self._run(self.res, rst)
        out = rst[:, idx.res_out]
        if self.word_bits is None:
            return out
        with obs.span("backend.unpack", backend=self.backend.name,
                      rows=self.rows):
            return unpack_rows(np.ascontiguousarray(out), self.rows)


class _JaxChain(_ChainBase):
    """Packed jax resident chain: the inter-pass column moves, the stage
    scan, the fresh-lane masks, the new-operand scatter and the MAC scan
    fuse into **one** jitted dispatch per pass (column index arrays are
    closure constants; per-step data is just the packed operand planes
    and the fresh-lane word). State stays a device ``(W, C)`` uint32
    array between passes — no host transfer until ``drain``.
    """

    def __init__(self, backend, mac, stage, recomb, idx: ResidentIndex,
                 rows: int, residue=None):
        super().__init__(mac, stage, recomb, idx, rows, 32,
                         residue=residue)
        self.backend = backend
        self.name = backend.name
        import jax
        import jax.numpy as jnp

        from repro.kernels.ref import packed_device_tables, packed_scan_body
        macro = _macro_factor(backend.macro)
        mac_t, mac_f = packed_device_tables(mac, macro)
        stg_t, stg_f = packed_device_tables(stage, macro)
        rec_t, rec_f = packed_device_tables(recomb, macro)
        W = -(-rows // 32)
        FULL = jnp.uint32(0xFFFFFFFF)

        def _first(planes_w):
            st = jnp.zeros((W, idx.c_mac), jnp.uint32)
            st = st.at[:, idx.un_cols].set(FULL)
            st = st.at[:, idx.cn_cols].set(FULL)
            st = st.at[:, idx.ab_cols].set(planes_w)
            return packed_scan_body(st, *mac_t, factor=mac_f)

        def _step(dev, planes_w, fresh_w):
            sst = jnp.zeros((W, idx.c_stage), jnp.uint32)
            sst = sst.at[:, idx.stage_dst].set(dev[:, idx.stage_src])
            sst = packed_scan_body(sst, *stg_t, factor=stg_f)
            st = jnp.zeros((W, idx.c_mac), jnp.uint32)
            st = st.at[:, idx.mac_dst].set(sst[:, idx.mac_src])
            st = st.at[:, idx.cn_cols].set(FULL)
            st = st.at[:, idx.un_cols].set(st[:, idx.un_cols] | fresh_w)
            st = st.at[:, idx.slo_cols].set(st[:, idx.slo_cols] & ~fresh_w)
            st = st.at[:, idx.ab_cols].set(planes_w)
            return packed_scan_body(st, *mac_t, factor=mac_f)

        def _drain(dev):
            rst = jnp.zeros((W, idx.c_rec), jnp.uint32)
            rst = rst.at[:, idx.rec_dst].set(dev[:, idx.stage_src])
            rst = packed_scan_body(rst, *rec_t, factor=rec_f)
            return rst[:, idx.rec_out]

        # Donating the previous pass's state buffer lets XLA reuse it in
        # place on accelerators; CPU jax would only warn, so skip there.
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._first = jax.jit(_first)
        self._step = jax.jit(_step, donate_argnums=donate)
        self._drain = jax.jit(_drain)
        if residue is not None:
            res_t, res_f = packed_device_tables(residue, macro)

            def _residue(dev):
                rst = jnp.zeros((W, idx.c_res), jnp.uint32)
                rst = rst.at[:, idx.res_dst].set(dev[:, idx.stage_src])
                rst = packed_scan_body(rst, *res_t, factor=res_f)
                return rst[:, idx.res_out]

            self._residue = jax.jit(_residue)

    def _kernel_span(self, programs: str, cycles: int):
        return obs.span("backend.kernel", backend=self.name,
                        rows=self.rows, cycles=cycles, fused=programs)

    def first(self, planes: np.ndarray):
        words = self._pack(planes)
        with self._kernel_span("mac", self.mac.n_cycles):
            return _until_ready(self._first(words))

    def step(self, dev, planes: np.ndarray, fresh: np.ndarray):
        words, fresh_w = self._pack(planes), self._pack_mask(fresh)
        with self._kernel_span("stage+mac",
                               self.stage.n_cycles + self.mac.n_cycles):
            return _until_ready(self._step(dev, words, fresh_w))

    def drain(self, dev) -> np.ndarray:
        with self._kernel_span("recomb", self.recomb.n_cycles):
            out = _until_ready(self._drain(dev))
        with obs.span("backend.unpack", backend=self.name, rows=self.rows):
            return unpack_rows(np.asarray(out), self.rows)

    def residue(self, dev) -> np.ndarray:
        with self._kernel_span("residue", self.res.n_cycles):
            out = _until_ready(self._residue(dev))
        with obs.span("backend.unpack", backend=self.name, rows=self.rows):
            return unpack_rows(np.asarray(out), self.rows)


class _EagerPackedChain(_ChainBase):
    """32-bit packed resident chain with *eager* jnp column moves
    between program passes; subclasses pick the per-pass kernel via
    ``_run``. State stays a device ``(W, C)`` uint32 array between
    passes, exactly like :class:`_JaxChain`'s — only the dispatch
    granularity differs (one launch per program instead of one fused
    jit per pass)."""

    def __init__(self, backend, mac, stage, recomb, idx: ResidentIndex,
                 rows: int, residue=None):
        super().__init__(mac, stage, recomb, idx, rows, 32,
                         residue=residue)
        self.backend = backend
        import jax.numpy as jnp
        self._jnp = jnp
        self._w = -(-rows // 32)
        self._full = jnp.uint32(0xFFFFFFFF)

    def _run(self, packed: PackedProgram, st):  # pragma: no cover
        raise NotImplementedError

    def first(self, planes: np.ndarray):
        jnp, idx = self._jnp, self.idx
        st = jnp.zeros((self._w, idx.c_mac), jnp.uint32)
        st = st.at[:, idx.un_cols].set(self._full)
        st = st.at[:, idx.cn_cols].set(self._full)
        st = st.at[:, idx.ab_cols].set(self._pack(planes))
        return self._run(self.mac, st)

    def step(self, dev, planes: np.ndarray, fresh: np.ndarray):
        jnp, idx = self._jnp, self.idx
        sst = jnp.zeros((self._w, idx.c_stage), jnp.uint32)
        sst = sst.at[:, idx.stage_dst].set(dev[:, idx.stage_src])
        sst = self._run(self.stage, sst)
        st = jnp.zeros((self._w, idx.c_mac), jnp.uint32)
        st = st.at[:, idx.mac_dst].set(sst[:, idx.mac_src])
        st = st.at[:, idx.cn_cols].set(self._full)
        fw = jnp.asarray(self._pack_mask(fresh))
        st = st.at[:, idx.un_cols].set(st[:, idx.un_cols] | fw)
        st = st.at[:, idx.slo_cols].set(st[:, idx.slo_cols] & ~fw)
        st = st.at[:, idx.ab_cols].set(self._pack(planes))
        return self._run(self.mac, st)

    def drain(self, dev) -> np.ndarray:
        jnp, idx = self._jnp, self.idx
        rst = jnp.zeros((self._w, idx.c_rec), jnp.uint32)
        rst = rst.at[:, idx.rec_dst].set(dev[:, idx.stage_src])
        rst = self._run(self.recomb, rst)
        with obs.span("backend.unpack", backend=self.backend.name,
                      rows=self.rows):
            return unpack_rows(np.asarray(rst[:, idx.rec_out]), self.rows)

    def residue(self, dev) -> np.ndarray:
        jnp, idx = self._jnp, self.idx
        rst = jnp.zeros((self._w, idx.c_res), jnp.uint32)
        rst = rst.at[:, idx.res_dst].set(dev[:, idx.stage_src])
        rst = self._run(self.res, rst)
        with obs.span("backend.unpack", backend=self.backend.name,
                      rows=self.rows):
            return unpack_rows(np.asarray(rst[:, idx.res_out]), self.rows)


class _PallasChain(_EagerPackedChain):
    """Packed Pallas resident chain: each program pass is one Pallas
    kernel launch over the eager-chain state representation."""

    def _run(self, packed: PackedProgram, st):
        from repro.kernels.crossbar_step import crossbar_run_pallas_packed
        with obs.span("backend.kernel", backend=self.backend.name,
                      rows=self.rows, cycles=packed.n_cycles):
            return _until_ready(crossbar_run_pallas_packed(
                st, packed, interpret=self.backend.interpret))


class _FaultyJaxChain(_EagerPackedChain):
    """Resident chain under an active fault model, serving both the jax
    and pallas backends: every program pass runs the cycle-at-a-time
    fault-injecting packed scan
    (:func:`repro.kernels.ref.crossbar_run_ref_packed_faulty`), drawing
    that pass's transient flips and the epoch's stuck maps from the
    backend's model."""

    def __init__(self, backend, mac, stage, recomb, idx: ResidentIndex,
                 rows: int, residue=None):
        super().__init__(backend, mac, stage, recomb, idx, rows,
                         residue=residue)
        self.model = backend_fault_model(backend)

    def _run(self, packed: PackedProgram, st):
        from repro.kernels.ref import crossbar_run_ref_packed_faulty
        with obs.span("backend.kernel", backend=self.backend.name,
                      rows=self.rows, cycles=packed.n_cycles, faulty=True):
            return _until_ready(crossbar_run_ref_packed_faulty(
                st, packed, self.model, self.rows))


# ---------------------------------------------------------------- numpy ----
@dataclass(frozen=True)
class NumpyBackend:
    """Reference interpreter over the packed tables (no JAX import).

    ``pack=True`` switches to the bit-plane packed interpreter: 64
    crossbar rows per ``uint64`` word, word-wide bitwise gate
    evaluation, ``np.bitwise_and.at`` AND-scatter. (Macro-cycle fusion
    is a dispatch-count optimization and does not apply to the eager
    numpy loop.)

    ``faults=<key>`` activates a device-error model (see
    :func:`backend_fault_model`); fault masks are packed words, so an
    active model always runs the 64-bit packed fault-injecting
    interpreter, even with ``pack=False``.
    """

    pack: bool = False
    faults: Optional[str] = None
    name: str = "numpy"

    def run_state(self, packed: PackedProgram, state: np.ndarray) -> np.ndarray:
        """Interpret the packed tables over ``state`` (rows, C) {0,1}."""
        model = backend_fault_model(self)
        if model is not None:
            return self._run_packed_faulty(packed, state, model)
        if self.pack:
            return self._run_packed(packed, state)
        with obs.span("backend.kernel", backend=self.name,
                      rows=state.shape[0], cycles=packed.n_cycles):
            return self._run_unpacked(packed, state)

    def _run_packed_faulty(self, packed: PackedProgram, state: np.ndarray,
                           model) -> np.ndarray:
        from repro.faults.inject import (numpy_kernel_packed_faulty,
                                         pass_fault_tensors)
        state = np.asarray(state, dtype=np.uint8)
        rows = state.shape[0]
        with obs.span("backend.pack", backend=self.name, rows=rows):
            st = pack_rows(state, 64)
        flips, sa0, sa1 = pass_fault_tensors(model, packed, rows, 64)
        with obs.span("backend.kernel", backend=self.name, rows=rows,
                      cycles=packed.n_cycles, faulty=True):
            st = numpy_kernel_packed_faulty(packed, st, flips, sa0, sa1)
        with obs.span("backend.unpack", backend=self.name, rows=rows):
            return unpack_rows(st, rows)

    def _run_unpacked(self, packed: PackedProgram,
                      state: np.ndarray) -> np.ndarray:
        st = np.asarray(state, dtype=np.uint8).copy()
        return self._kernel_unpacked(packed, st)

    @staticmethod
    def _kernel_unpacked(packed: PackedProgram,
                         st: np.ndarray) -> np.ndarray:
        """The interpreter loop alone — ``st`` (rows, C) uint8 is mutated
        in place and returned. Shared by :meth:`run_state` and the
        resident chains (which own their state arrays and emit their own
        spans, so no pack/copy here)."""
        gate_id, in_cols = packed.gate_id, packed.in_cols
        out_col = packed.out_col
        for t in range(packed.n_cycles):
            imask = packed.init_mask[t]
            if imask.any():
                st[:, imask] = 1
                continue
            # Gather all inputs first (ops within a cycle are simultaneous).
            gid, ics, ocs = gate_id[t], in_cols[t], out_col[t]
            x0 = st[:, ics[:, 0]].astype(np.int32)
            x1 = st[:, ics[:, 1]].astype(np.int32)
            x2 = st[:, ics[:, 2]].astype(np.int32)
            s3 = x0 + x1 + x2
            res = np.select(
                [gid == int(Gate.NOT), gid == int(Gate.NOR),
                 gid == int(Gate.MIN3), gid == int(Gate.NAND),
                 gid == int(Gate.OR), gid == int(Gate.COPY)],
                [1 - x0, (x0 + x1 == 0).astype(np.int32),
                 (s3 <= 1).astype(np.int32), 1 - x0 * x1,
                 (x0 + x1 >= 1).astype(np.int32), x0],
                default=np.int32(1),
            ).astype(np.uint8)
            # AND-write; the validator guarantees distinct real outputs,
            # duplicates only target the side-effect-free scratch column.
            np.minimum.at(st, (slice(None), ocs), res)
        return st

    def _run_packed(self, packed: PackedProgram,
                    state: np.ndarray) -> np.ndarray:
        state = np.asarray(state, dtype=np.uint8)
        rows = state.shape[0]
        with obs.span("backend.pack", backend=self.name, rows=rows):
            st = pack_rows(state, 64)
        with obs.span("backend.kernel", backend=self.name, rows=rows,
                      cycles=packed.n_cycles):
            st = self._kernel_packed(packed, st)
        with obs.span("backend.unpack", backend=self.name, rows=rows):
            return unpack_rows(st, rows)

    @staticmethod
    def _kernel_packed(packed: PackedProgram, st: np.ndarray) -> np.ndarray:
        """The packed interpreter loop alone — ``st`` (W, C) uint64 words
        are mutated in place and returned. Shared by :meth:`run_state`
        and the resident chains."""
        full = ~np.uint64(0)
        gate_id, in_cols, out_col = (packed.gate_id, packed.in_cols,
                                     packed.out_col)
        for t in range(packed.n_cycles):
            imask = packed.init_mask[t]
            if imask.any():
                st[:, imask] = full
                continue
            gid, ics, ocs = gate_id[t], in_cols[t], out_col[t]
            # Gathers before the write: ops in a cycle are simultaneous.
            res = gate_eval_packed(np, gid[None, :], st[:, ics[:, 0]],
                                   st[:, ics[:, 1]], st[:, ics[:, 2]])
            # Exact AND accumulation, duplicate scratch writes included.
            np.bitwise_and.at(st, (slice(None), ocs), res)
        return st

    def resident_chain(self, mac: PackedProgram, stage: PackedProgram,
                       recomb: PackedProgram, idx: ResidentIndex,
                       rows: int, residue: Optional[PackedProgram] = None
                       ) -> _NumpyChain:
        """Build a resident MAC chain over this backend's interpreter."""
        return _NumpyChain(self, mac, stage, recomb, idx, rows,
                           residue=residue)


# ------------------------------------------------------------------ JAX ----
def _macro_factor(macro: Optional[int]) -> int:
    """Shared macro-fusion policy for the packed scan/grid paths (the
    only callers): an explicit ``macro`` wins, else ``DEFAULT_MACRO``."""
    return max(1, int(macro)) if macro is not None else DEFAULT_MACRO


@dataclass(frozen=True)
class JaxBackend:
    """Jitted ``lax.scan`` over the packed tables.

    ``pack=True`` runs the bit-plane packed scan (32 rows per ``uint32``
    word, :func:`repro.kernels.ref.crossbar_run_ref_packed`) with
    ``macro``-deep macro-cycle fusion (``None`` = the stock
    ``DEFAULT_MACRO`` when packed, no fusion otherwise).

    ``faults=<key>`` activates a device-error model (see
    :func:`backend_fault_model`); requires ``pack=True`` and runs the
    cycle-at-a-time fault-injecting scan (macro fusion is bypassed —
    flip draws index per-cycle tables).
    """

    pack: bool = False
    macro: Optional[int] = None
    faults: Optional[str] = None
    name: str = "jax"

    def _require_pack_for_faults(self, model):
        if model is not None and not self.pack:
            raise ValueError(
                f"fault injection on the {self.name} backend requires "
                f"pack=true (spec '{self.name}:pack=true,"
                f"faults={self.faults}') — fault masks are packed words")

    def run_state(self, packed: PackedProgram, state: np.ndarray) -> np.ndarray:
        """Run the jitted scan over ``state`` (rows, C) {0,1}."""
        import jax.numpy as jnp

        from repro.kernels.ref import (crossbar_run_ref,
                                       crossbar_run_ref_packed,
                                       crossbar_run_ref_packed_faulty)
        model = backend_fault_model(self)
        self._require_pack_for_faults(model)
        if self.pack:
            rows = state.shape[0]
            with obs.span("backend.pack", backend=self.name, rows=rows):
                words = pack_rows(np.asarray(state, dtype=np.uint8), 32)
            with obs.span("backend.kernel", backend=self.name, rows=rows,
                          cycles=packed.n_cycles,
                          faulty=model is not None):
                if model is not None:
                    final = crossbar_run_ref_packed_faulty(
                        jnp.asarray(words), packed, model, rows)
                else:
                    final = crossbar_run_ref_packed(
                        jnp.asarray(words), packed,
                        macro=_macro_factor(self.macro))
                _until_ready(final)
            with obs.span("backend.unpack", backend=self.name, rows=rows):
                return unpack_rows(np.asarray(final), rows)
        with obs.span("backend.kernel", backend=self.name,
                      rows=state.shape[0], cycles=packed.n_cycles):
            final = crossbar_run_ref(jnp.asarray(state, dtype=jnp.uint8),
                                     packed)
            return np.asarray(final)

    def resident_chain(self, mac: PackedProgram, stage: PackedProgram,
                       recomb: PackedProgram, idx: ResidentIndex,
                       rows: int, residue: Optional[PackedProgram] = None):
        """Build a packed device-resident MAC chain (needs pack=true)."""
        if not self.pack:
            raise ValueError("resident execution on the jax backend "
                             "requires pack=true (spec 'jax:pack=true')")
        if backend_fault_model(self) is not None:
            return _FaultyJaxChain(self, mac, stage, recomb, idx, rows,
                                   residue=residue)
        return _JaxChain(self, mac, stage, recomb, idx, rows,
                         residue=residue)


# --------------------------------------------------------------- Pallas ----
DEFAULT_ROW_BLOCK = 256
MAX_ROW_BLOCK = 512


def autotune_row_block(rows: int, max_block: int = MAX_ROW_BLOCK) -> int:
    """Row-tiling policy from the batch shape: the smallest power of two
    covering ``rows`` (so a small batch is one tile with minimal padding),
    clamped to [8, ``max_block``] — 8 is the f32 sublane tile, 512 keeps
    the state tile comfortably inside VMEM for the widest programs."""
    b = 8
    while b < rows and b < max_block:
        b <<= 1
    return b


@dataclass(frozen=True)
class PallasBackend:
    """Mosaic TPU kernel. ``interpret=None`` (the default) derives the
    mode from the platform — the Pallas interpreter on the CPU, Mosaic
    on a TPU; an explicit ``True``/``False`` is honoured.

    ``row_block`` is the row-tiling policy: crossbar rows (the SIMD batch
    axis) are processed in VMEM-resident tiles of this many rows.
    ``None`` (the default) means *autotune*: each ``run`` picks the
    block from its batch's rows-bucket (the pow2 tile class of
    :func:`autotune_row_block`, reported in ``cost().row_block``), so a
    small warmup batch never pins a tile for later wide batches; an
    explicit value (e.g. ``"pallas:row_block=512"``) is always honored.

    ``pack=True`` runs the bit-plane packed kernel
    (:func:`repro.kernels.crossbar_step.crossbar_run_pallas_packed`):
    rows are packed 32-per-``uint32`` word, held column-major in tiles
    of ``SUBLANES * 128`` words, and gates evaluate bitwise on the VPU
    from an SMEM op stream (``row_block`` applies to the unpacked
    kernel only).

    ``faults=<key>`` activates a device-error model (requires
    ``pack=True``); faulty passes run the shared cycle-at-a-time
    fault-injecting jnp scan rather than the Pallas kernel — fault
    injection is a simulation study, the kernel stays the fault-free
    performance path.
    """

    interpret: Optional[bool] = None
    row_block: Optional[int] = None
    pack: bool = False
    faults: Optional[str] = None
    name: str = "pallas"

    _require_pack_for_faults = JaxBackend._require_pack_for_faults

    def run_state(self, packed: PackedProgram, state: np.ndarray) -> np.ndarray:
        """Run the Pallas kernel over ``state`` (rows, C) {0,1}."""
        import jax.numpy as jnp

        from repro.kernels.crossbar_step import (crossbar_run_pallas,
                                                 crossbar_run_pallas_packed)
        model = backend_fault_model(self)
        self._require_pack_for_faults(model)
        if self.pack:
            rows = state.shape[0]
            with obs.span("backend.pack", backend=self.name, rows=rows):
                words = pack_rows(np.asarray(state, dtype=np.uint8), 32)
            with obs.span("backend.kernel", backend=self.name, rows=rows,
                          cycles=packed.n_cycles,
                          faulty=model is not None):
                if model is not None:
                    from repro.kernels.ref import \
                        crossbar_run_ref_packed_faulty
                    final = crossbar_run_ref_packed_faulty(
                        jnp.asarray(words), packed, model, rows)
                else:
                    final = crossbar_run_pallas_packed(
                        jnp.asarray(words), packed,
                        interpret=self.interpret)
                _until_ready(final)
            with obs.span("backend.unpack", backend=self.name, rows=rows):
                return unpack_rows(np.asarray(final), rows)
        with obs.span("backend.kernel", backend=self.name,
                      rows=state.shape[0], cycles=packed.n_cycles):
            final = crossbar_run_pallas(jnp.asarray(state, dtype=jnp.uint8),
                                        packed,
                                        row_block=self.row_block
                                        or DEFAULT_ROW_BLOCK,
                                        interpret=self.interpret)
            return np.asarray(final)

    def resident_chain(self, mac: PackedProgram, stage: PackedProgram,
                       recomb: PackedProgram, idx: ResidentIndex,
                       rows: int, residue: Optional[PackedProgram] = None):
        """Build a packed device-resident MAC chain (needs pack=true)."""
        if not self.pack:
            raise ValueError("resident execution on the pallas backend "
                             "requires pack=true (spec 'pallas:pack=true')")
        if backend_fault_model(self) is not None:
            return _FaultyJaxChain(self, mac, stage, recomb, idx, rows,
                                   residue=residue)
        return _PallasChain(self, mac, stage, recomb, idx, rows,
                            residue=residue)


def supports_resident(backend) -> bool:
    """Whether ``backend`` can host a resident MAC chain. Stock policy:
    numpy always (packed and unpacked interpreters both have kernel-only
    entry points); jax/pallas only packed (the resident representation
    *is* the 32-bit word-packed state). Custom backends opt in by
    defining ``resident_chain``."""
    if getattr(backend, "resident_chain", None) is None:
        return False
    if isinstance(backend, (JaxBackend, PallasBackend)):
        return bool(backend.pack)
    return True


# -------------------------------------------------------------- registry ----
_REGISTRY: Dict[str, Callable[..., Backend]] = {
    "numpy": NumpyBackend,
    "jax": JaxBackend,
    "pallas": PallasBackend,
}


def register_backend(name: str, factory: Callable[..., Backend]) -> None:
    """Add a backend factory (``factory(**options) -> Backend``)."""
    _REGISTRY[name] = factory


def backend_names() -> list:
    """Registered backend names, sorted."""
    return sorted(_REGISTRY)


def _parse_value(v: str):
    low = v.lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return int(v)
    except ValueError:
        return v


def platform_default_spec() -> str:
    """The backend spec a caller that names none gets: the numpy
    reference on the CPU (what the tests hold every device path to),
    the packed jax scan on an accelerator."""
    from repro.runtime import on_cpu
    return "numpy" if on_cpu() else "jax:pack=true"


def resolve_backend(spec: Union[None, str, Backend],
                    default: Optional[Backend] = None) -> Backend:
    """Backend instance from a name/spec-string/instance (see module doc)."""
    if spec is None:
        if default is not None:
            return default
        spec = platform_default_spec()
    if not isinstance(spec, str):
        return spec
    name, _, opts = spec.partition(":")
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend '{name}' "
                       f"(registered: {backend_names()})")
    kwargs = {}
    if opts:
        for item in opts.split(","):
            k, _, v = item.partition("=")
            kwargs[k.strip()] = _parse_value(v.strip())
    try:
        return _REGISTRY[name](**kwargs)
    except TypeError as e:
        raise ValueError(
            f"backend spec '{spec}': {e} — options the '{name}' backend "
            f"accepts are its constructor fields "
            f"(e.g. numpy: pack, faults; jax: pack, macro, faults; "
            f"pallas: interpret, row_block, pack, faults)") from e
