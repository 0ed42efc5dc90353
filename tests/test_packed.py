"""Bit-plane packed execution: pack/unpack, gate parity, backend parity.

The packed backends (``pack=True``) must be bit-identical to the
unpacked interpreters everywhere: per-gate truth tables, ragged row
tails (rows not a multiple of the 64/32-bit word), every macro-cycle
fusion factor, and through ``compile_batch`` / ``compile_group``.
"""
import numpy as np
import pytest

from repro.compiler.macrocycle import fuse_macrocycles
from repro.core.bits import from_bits, mask, pack_rows, to_bits, unpack_rows
from repro.core.executor import pack_program, run_numpy
from repro.core.isa import GATE_ARITY, Gate, Op, eval_gate
from repro.core.program import Layout, ProgramBuilder
from repro.engine import Engine
from repro.engine.backends import resolve_backend

pytestmark = pytest.mark.core

PACKED_SPECS = ["numpy:pack=true", "jax:pack=true", "pallas:pack=true"]


# ------------------------------------------------------ pack/unpack ----
@pytest.mark.parametrize("word_bits", [64, 32])
@pytest.mark.parametrize("rows", [1, 7, 32, 63, 64, 65, 100, 128, 130])
def test_pack_unpack_roundtrip(rows, word_bits):
    rng = np.random.default_rng(rows)
    bits = rng.integers(0, 2, (rows, 37)).astype(np.uint8)
    words = pack_rows(bits, word_bits)
    assert words.shape == (-(-rows // word_bits), 37)
    assert words.dtype == (np.uint64 if word_bits == 64 else np.uint32)
    assert (unpack_rows(words, rows) == bits).all()


def test_pack_rows_bit_layout():
    """Row r lands in bit r % word of word r // word, little-endian."""
    bits = np.zeros((70, 2), np.uint8)
    bits[0, 0] = 1          # word 0, bit 0
    bits[63, 0] = 1         # word 0, bit 63
    bits[65, 1] = 1         # word 1, bit 1
    words = pack_rows(bits, 64)
    assert words[0, 0] == (1 | (1 << 63))
    assert words[1, 1] == 2
    assert words[1, 0] == 0


def test_pack_rows_zero_rows():
    words = pack_rows(np.zeros((0, 5), np.uint8), 64)
    assert words.shape == (0, 5)
    assert unpack_rows(words, 0).shape == (0, 5)


# ------------------------------------------- int marshalling parity ----
def test_to_bits_vectorized_matches_object_path():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 16, 50)
    fast = to_bits(vals, 16)                       # int64 fast path
    slow = to_bits(np.array([int(v) for v in vals], dtype=object), 16)
    assert (fast == slow).all()
    # negative values wrap two's-complement identically
    assert (to_bits(np.array([-3, -1]), 8)
            == to_bits(np.array([-3, -1], dtype=object), 8)).all()


def test_from_bits_exact_python_ints():
    vals = np.array([0, 1, (1 << 40) + 5, mask(48)], dtype=object)
    back = from_bits(to_bits(vals, 48))
    assert [int(v) for v in back] == [int(v) for v in vals]
    assert all(isinstance(v, int) for v in back.tolist())
    # beyond-64-bit fallback stays exact
    big = (1 << 100) + 12345
    assert int(from_bits(to_bits(np.array([big], dtype=object), 120))[0]) \
        == big


# ------------------------------------------------- per-gate parity ----
def _gate_program(gate: Gate):
    """One partition, inputs x0..x2, INIT'd output cell, single gate op."""
    lay = Layout()
    p = lay.new_partition()
    xs = [lay.add_cell(p, f"x{i}") for i in range(3)]
    out = lay.add_cell(p, "y")
    b = ProgramBuilder(lay, name=f"gate-{gate.name}")
    for i, c in enumerate(xs):
        b.declare_input(f"x{i}", [c])
    b.declare_output("y", [out])
    b.init([out])
    arity = GATE_ARITY[gate]
    b.cycle([Op(gate, tuple(xs[:arity]) or (xs[0],), out)])
    return b.build(validate=False)


@pytest.mark.parametrize("gate", [Gate.NOT, Gate.NOR, Gate.MIN3,
                                  Gate.NAND, Gate.OR, Gate.COPY])
def test_every_gate_packed_parity(gate):
    """All 8 input combinations, replicated to a ragged 70-row batch, on
    every packed backend — against both run_numpy and eval_gate."""
    prog = _gate_program(gate)
    packed = pack_program(prog)
    combos = np.array([[(i >> j) & 1 for j in range(3)]
                       for i in range(8)], np.uint8)
    rows = np.tile(combos, (9, 1))[:70]            # 70 % 64 != 0 != % 32
    inputs = {f"x{i}": rows[:, i:i + 1] for i in range(3)}
    ref = run_numpy(prog, inputs)["y"][:, 0]
    arity = GATE_ARITY[gate]
    want = [eval_gate(gate, tuple(int(x) for x in r[:max(arity, 1)]))
            for r in rows]
    assert list(ref) == want
    state = np.zeros((70, packed.init_mask.shape[1]), np.uint8)
    for name, cols in prog.input_map.items():
        state[:, cols] = inputs[name]
    for spec in PACKED_SPECS:
        final = resolve_backend(spec).run_state(packed, state)
        assert list(final[:, prog.output_map["y"][0]]) == want, spec


def test_packed_and_write_semantics():
    """No-init AND (X-MAGIC input overwriting): a gate result AND-writes
    into whatever the output cell already holds."""
    lay = Layout()
    p = lay.new_partition()
    x = lay.add_cell(p, "x")
    y = lay.add_cell(p, "y")
    b = ProgramBuilder(lay)
    b.declare_input("x", [x])
    b.declare_input("y", [y])          # pre-loaded, NOT re-initialized
    b.declare_output("y", [y])
    b.cycle([Op(Gate.NOT, (x,), y)])   # y <- y AND NOT(x)
    prog = b.build(validate=False)
    packed = pack_program(prog)
    rows = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.uint8)
    state = np.zeros((4, packed.init_mask.shape[1]), np.uint8)
    state[:, [x, y]] = rows
    want = [int(yv & (1 - xv)) for xv, yv in rows]
    for spec in ["numpy"] + PACKED_SPECS:
        final = resolve_backend(spec).run_state(packed, state)
        assert list(final[:, y]) == want, spec


# ------------------------------------------------- program parity ----
@pytest.mark.parametrize("rows", [3, 33, 70])
@pytest.mark.parametrize("op,n", [("multpim", 4), ("multpim", 8),
                                  ("rime", 8), ("mac", 8)])
def test_ragged_rows_packed_parity(op, n, rows):
    """Full programs at row counts straddling the 32/64-bit word sizes:
    the zero-padded tail must never leak into real rows."""
    eng = Engine()
    exe = eng.compile(op, n)
    rng = np.random.default_rng(rows * n)
    batch = {name: rng.integers(0, 1 << w, rows)
             for name, w in exe.input_widths.items()}
    ref = exe.run(batch, backend="numpy")
    for spec in PACKED_SPECS:
        got = exe.run(batch, backend=spec)
        for k in ref:
            assert all(int(a) == int(b) for a, b in zip(ref[k], got[k])), \
                (spec, k)


@pytest.mark.parametrize("macro", [1, 3, 8, 1000])
def test_macro_factor_parity(macro):
    """Any fusion depth of the packed scan (including one larger than
    the program) is bit-identical to the unpacked reference, as is the
    packed Pallas kernel, which runs a flat op stream and has no fusion
    knob."""
    eng = Engine()
    exe = eng.compile("multpim", 8)
    rng = np.random.default_rng(macro)
    batch = {"a": rng.integers(0, 256, 50), "b": rng.integers(0, 256, 50)}
    ref = exe.run(batch, backend="numpy")
    for spec in (f"jax:pack=true,macro={macro}", "pallas:pack=true"):
        got = exe.run(batch, backend=spec)
        assert all(int(a) == int(b)
                   for a, b in zip(ref["out"], got["out"])), spec
    with pytest.raises(ValueError, match="pallas"):
        resolve_backend(f"pallas:pack=true,macro={macro}")


def test_fuse_macrocycles_shapes_and_memo():
    eng = Engine()
    packed = eng.compile("multpim", 4).packed
    t = packed.n_cycles
    mt = fuse_macrocycles(packed, 8)
    assert mt.factor == 8
    assert mt.n_macro == -(-t // 8)
    assert mt.gate_id.shape == (mt.n_macro, 8, packed.max_ops)
    assert mt.in_cols.shape == (mt.n_macro, 8, packed.max_ops, 3)
    assert mt.init_words.shape == mt.init_mask.shape
    # padding slots are NOPs writing the scratch column, no inits
    flat_gid = mt.gate_id.reshape(-1, packed.max_ops)
    assert (flat_gid[t:] == int(Gate.NOP)).all()
    assert not mt.init_mask.reshape(-1, mt.init_mask.shape[2])[t:].any()
    assert (mt.init_words == np.where(mt.init_mask, np.uint32(0xFFFFFFFF),
                                      np.uint32(0))).all()
    # memoized per (packed, factor); oversized factors clamp to T
    assert fuse_macrocycles(packed, 8) is mt
    assert fuse_macrocycles(packed, 10 ** 6).factor == t


# ------------------------------------- co-scheduled executables ----
@pytest.mark.parametrize("spec", PACKED_SPECS)
def test_compile_batch_packed_parity(spec):
    """Packing benefits BatchedExecutable without API changes: the fused
    K-MAC pass is bit-identical to the unpacked backend."""
    eng = Engine()
    bex = eng.compile_batch("mac", 4, 2)
    rng = np.random.default_rng(7)
    group = []
    for j in range(2):
        a = rng.integers(0, 16, 33)
        x = rng.integers(0, 16, 33)
        group.append(eng._mac_inputs(4, a, x, np.zeros(33, object),
                                     np.zeros(33, object)))
    ref = bex.run(group, backend="numpy")
    got = bex.run(group, backend=spec)
    for r, g in zip(ref, got):
        for k in r:
            assert np.array_equal(np.asarray(r[k]), np.asarray(g[k])), k


@pytest.mark.parametrize("spec", PACKED_SPECS)
def test_compile_group_packed_parity(spec):
    """Heterogeneous GroupedExecutable under a packed backend matches
    the unpacked pass and independent single-op runs."""
    eng = Engine()
    gex = eng.compile_group([("mac", 4, 1), ("multpim", 4)])
    rng = np.random.default_rng(11)
    a = rng.integers(0, 16, 40)
    x = rng.integers(0, 16, 40)
    mac_in = eng._mac_inputs(4, a, x, np.zeros(40, object),
                             np.zeros(40, object))
    mul_in = {"a": rng.integers(0, 16, 40), "b": rng.integers(0, 16, 40)}
    ref = gex.run([mac_in, mul_in], backend="numpy")
    got = gex.run([mac_in, mul_in], backend=spec)
    for r, g in zip(ref, got):
        for k in r:
            assert np.array_equal(np.asarray(r[k]), np.asarray(g[k])), k
    want = [(int(p) * int(q)) & 0xFF for p, q in zip(mul_in["a"],
                                                     mul_in["b"])]
    assert [int(v) for v in got[1]["out"]] == want


# --------------------------------------------------- policy surface ----
def test_pack_spec_strings_and_cost_reporting():
    bk = resolve_backend("jax:pack=true,macro=4")
    assert bk.pack is True and bk.macro == 4
    assert resolve_backend("pallas:pack=true").pack is True
    assert resolve_backend("numpy").pack is False
    # options a backend doesn't take fail with a spec error, not a
    # bare TypeError (numpy has no macro knob — no scan to fuse)
    with pytest.raises(ValueError, match="numpy"):
        resolve_backend("numpy:pack=true,macro=8")
    eng = Engine(backend="jax:pack=true")
    exe = eng.compile("multpim", 4)
    assert exe.cost().pack is True
    assert eng.compile("multpim", 4, backend="numpy").cost().pack is False
    out = exe.run({"a": [3, 5], "b": [7, 9]})
    assert [int(v) for v in out["out"]] == [21, 45]
