"""Multi-program co-scheduling: allocator/relocation invariants,
K-program bit-parity vs sequential runs on every backend, heterogeneous
compile_group parity, column-budget chain allocation, co-scheduled
matvec, batched LM-head accounting, Pallas row_block autotune."""
import numpy as np
import pytest

from repro.compiler import (CapacityError, PartitionAllocator,
                            column_budget_counts, coschedule)
from repro.core.matvec import multpim_mac
from repro.core.multpim import multpim_multiplier
from repro.engine import (BatchedExecutable, Engine, GroupedExecutable,
                          GroupSpec, autotune_row_block, get_engine,
                          resolve_backend)

pytestmark = pytest.mark.core

BACKENDS = ["numpy", "jax", "pallas"]


def _mac_bits(rng, rows, n):
    return {name: rng.integers(0, 2, (rows, n), dtype=np.uint8)
            for name in ("a", "b", "un", "s_lo", "c_lo", "c_lo_n")}


# ---------------------------------------------- relocation invariants ----
def test_coschedule_never_aliases_partition_or_column_ranges():
    """Regression: co-scheduled programs must occupy pairwise-disjoint
    partition and column ranges — checked at the placement level AND by
    walking every op/init/IO column of the fused program."""
    prog = multpim_mac(4)
    fused, placements = coschedule([prog] * 4)
    for i, p in enumerate(placements):
        for q in placements[i + 1:]:
            assert p.col_hi < q.col_lo or q.col_hi < p.col_lo
            assert (p.partition_hi < q.partition_lo
                    or q.partition_hi < p.partition_lo)
    # every column a copy touches lies inside its own ranges
    lay = fused.layout
    for i, p in enumerate(placements):
        pfx = p.prefix
        cols = set()
        for name, cs in list(fused.input_map.items()) + \
                list(fused.output_map.items()):
            if name.startswith(pfx):
                cols.update(cs)
        assert cols, f"copy {i} has no I/O columns"
        for c in cols:
            assert p.col_lo <= c <= p.col_hi
            assert p.partition_lo <= lay.partition_of(c) <= p.partition_hi
    # op spans never cross a placement boundary
    bounds = [(p.col_lo, p.col_hi) for p in placements]
    for cyc in fused.cycles:
        for op in cyc.ops:
            owners = {next(i for i, (lo, hi) in enumerate(bounds)
                           if lo <= c <= hi) for c in op.cols}
            assert len(owners) == 1, f"op {op} spans copies {owners}"
    fused.validate()


def test_coschedule_k_copies_same_cycle_count():
    """K aligned copies merge with no cycle overhead: the fused stream
    has exactly the single program's length (that's the K-fold
    cycles-per-MAC win)."""
    prog = multpim_mac(8)
    for k in (2, 4):
        fused, _ = coschedule([prog] * k)
        assert fused.n_cycles == prog.n_cycles
        assert fused.n_partitions == k * prog.n_partitions


def test_coschedule_heterogeneous_streams_stay_ordered():
    """Different programs (different lengths/structures) still merge into
    one legal program; each copy's outputs stay correct."""
    from repro.core.bits import to_bits, from_bits
    from repro.core.executor import run_numpy
    p4, p2 = multpim_multiplier(4), multpim_multiplier(2)
    fused, _ = coschedule([p4, p2])
    assert max(p4.n_cycles, p2.n_cycles) <= fused.n_cycles \
        <= p4.n_cycles + p2.n_cycles
    rng = np.random.default_rng(0)
    a4, b4 = rng.integers(0, 16, 8), rng.integers(0, 16, 8)
    a2, b2 = rng.integers(0, 4, 8), rng.integers(0, 4, 8)
    out = run_numpy(fused, {"g0/a": to_bits(a4, 4), "g0/b": to_bits(b4, 4),
                            "g1/a": to_bits(a2, 2), "g1/b": to_bits(b2, 2)})
    assert [int(v) for v in from_bits(out["g0/out"])] == \
        [int(x) * int(y) for x, y in zip(a4, b4)]
    assert [int(v) for v in from_bits(out["g1/out"])] == \
        [int(x) * int(y) for x, y in zip(a2, b2)]


def test_allocator_capacity():
    prog = multpim_mac(4)
    alloc = PartitionAllocator(max_cols=2 * prog.layout.n_cols + 1)
    assert alloc.capacity(prog) == 2
    with pytest.raises(CapacityError):
        coschedule([prog] * 3,
                   allocator=PartitionAllocator(
                       max_cols=2 * prog.layout.n_cols + 1))
    with pytest.raises(CapacityError):
        coschedule([prog] * 3,
                   allocator=PartitionAllocator(max_partitions=8))


# --------------------------------------------------- batched executable ----
@pytest.mark.parametrize("backend", BACKENDS)
def test_compile_batch_bit_parity_vs_sequential_runs(backend):
    """K-program co-schedule == K independent Executable.run calls,
    bit-for-bit, on numpy/jax/pallas."""
    k, n, rows = 3, 8, 16
    eng = get_engine()
    bex = eng.compile_batch("mac", n, k)
    exe = eng.compile("mac", n)
    rng = np.random.default_rng(42)
    groups = [_mac_bits(rng, rows, n) for _ in range(k)]
    fused_out = bex.run(groups, backend=backend)
    for i, g in enumerate(groups):
        want = exe.run(g, backend=backend)
        for name, arr in want.items():
            np.testing.assert_array_equal(fused_out[i][name], arr,
                                          err_msg=f"{backend} copy {i} "
                                                  f"output {name}")


def test_batched_run_mixed_marshalling_matches_independent_runs():
    """A group that passed integers gets integer outputs back even when
    another group passed bit planes (per-group marshalling, exactly as
    K independent Executable.run calls would behave)."""
    eng = get_engine()
    k, n = 2, 4
    bex = eng.compile_batch("multpim", n, k)
    exe = eng.compile("multpim", n)
    rng = np.random.default_rng(5)
    ints = {"a": rng.integers(0, 1 << n, 6), "b": rng.integers(0, 1 << n, 6)}
    planes = {"a": rng.integers(0, 2, (6, n), dtype=np.uint8),
              "b": rng.integers(0, 2, (6, n), dtype=np.uint8)}
    got = bex.run([ints, planes])
    want = [exe.run(ints), exe.run(planes)]
    for g, w in zip(got, want):
        for name in w:
            np.testing.assert_array_equal(np.asarray(g[name], dtype=object),
                                          np.asarray(w[name], dtype=object))
    assert int(got[0]["out"][0]) == int(ints["a"][0]) * int(ints["b"][0])
    assert got[1]["out"].shape == (6, 2 * n)        # planes stay planes


def test_compile_batch_memoizes_fused_entry():
    eng = Engine()
    b1 = eng.compile_batch("mac", 8, 2)
    b2 = eng.compile_batch("mac", 8, 2)
    assert b1.inner.packed is b2.inner.packed
    b3 = eng.compile_batch("mac", 8, 3)
    assert b3.inner.packed is not b1.inner.packed
    assert isinstance(b1, BatchedExecutable)


def test_compile_batch_refuses_stale_fused_entry():
    """Regression: clearing the program cache recompiles the base entry;
    the fused memo keyed on an equal OpSpec must not serve a program
    built from the evicted entry."""
    from repro.compiler import ProgramCache
    cache = ProgramCache(use_disk=False)
    eng = Engine(cache=cache)
    b1 = eng.compile_batch("mac", 4, 2)
    cache.clear()
    b2 = eng.compile_batch("mac", 4, 2)
    assert b2.base_entry is not b1.base_entry       # base recompiled
    assert b2.inner.entry is not b1.inner.entry     # fused rebuilt too
    rng = np.random.default_rng(0)
    groups = [_mac_bits(rng, 4, 4) for _ in range(2)]
    for a, b in zip(b1.run(groups), b2.run(groups)):
        for name in a:
            np.testing.assert_array_equal(a[name], b[name])


def test_compile_batch_cost_reports_cycles_per_mac():
    eng = get_engine()
    k = 4
    bex = eng.compile_batch("mac", 8, k)
    one = eng.compile("mac", 8)
    cost = bex.cost()
    assert cost.programs == k
    assert cost.cycles == one.n_cycles             # aligned merge: no overhead
    assert cost.cycles_per_program == pytest.approx(one.n_cycles / k)
    assert cost.as_dict()["cycles_per_program"] == cost.cycles_per_program


def test_compile_batch_rejects_bad_shapes():
    eng = get_engine()
    bex = eng.compile_batch("mac", 4, 2)
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        bex.run([_mac_bits(rng, 4, 4)])            # wrong K
    with pytest.raises(KeyError):
        bex.run([{"a": [1]}, {"a": [1]}])          # missing inputs
    with pytest.raises(CapacityError):
        eng.compile_batch("mac", 8, 100)           # > crossbar columns


# ------------------------------------------------ heterogeneous groups ----
@pytest.mark.parametrize("backend", BACKENDS)
def test_compile_group_heterogeneous_bit_parity(backend):
    """compile_group([mac, multiply, ...]) == the same ops run
    sequentially as single-op executables, bit-for-bit, on every
    backend (the full-block serving acceptance check)."""
    eng = get_engine()
    gex = eng.compile_group([("mac", 8, 2), ("multpim", 4),
                             GroupSpec("rime", 4, label="rime4")])
    assert isinstance(gex, GroupedExecutable)
    assert gex.k == 4
    rng = np.random.default_rng(7)
    rows = 6
    macs = [_mac_bits(rng, rows, 8) for _ in range(2)]
    mul = {"a": rng.integers(0, 16, rows), "b": rng.integers(0, 16, rows)}
    rim = {"a": rng.integers(0, 16, rows), "b": rng.integers(0, 16, rows)}
    got = gex.run(macs + [mul, rim], backend=backend)
    want = ([eng.compile("mac", 8).run(m, backend=backend) for m in macs]
            + [eng.compile("multpim", 4).run(mul, backend=backend),
               eng.compile("rime", 4).run(rim, backend=backend)])
    for i, (g, w) in enumerate(zip(got, want)):
        for name, arr in w.items():
            np.testing.assert_array_equal(
                np.asarray(g[name], dtype=object),
                np.asarray(arr, dtype=object),
                err_msg=f"{backend} slot {i} output {name}")


def test_compile_group_slots_use_their_own_input_names():
    """Slot i's expected inputs are its *own* base program's — a MAC
    slot wants the carry-save planes, a multiplier slot just a/b."""
    eng = get_engine()
    gex = eng.compile_group([("mac", 4), ("multpim", 4)])
    rng = np.random.default_rng(0)
    with pytest.raises(KeyError):
        # multiplier operands fed to the MAC slot
        gex.run([{"a": [1], "b": [1]},
                 {"a": [1], "b": [1]}])
    out = gex.run([_mac_bits(rng, 3, 4), {"a": [3, 5, 7], "b": [2, 2, 2]}])
    assert [int(v) for v in out[1]["out"]] == [6, 10, 14]
    assert {"lo", "s_hi", "c_hi"} <= set(out[0])


def test_compile_group_op_cost_rows():
    eng = get_engine()
    gex = eng.compile_group([("mac", 8, 2), ("multpim", 4)])
    rows = gex.op_costs()
    assert [r["label"] for r in rows] == ["mac/n8", "mac/n8", "multpim/n4"]
    assert all(r["fused_cycles"] == gex.n_cycles for r in rows)
    assert all(r["own_cycles"] <= gex.n_cycles for r in rows)
    assert (sum(r["cols"] for r in rows)
            == gex.program.layout.n_cols)
    assert gex.cost().programs == 3
    # heterogeneous merge is never longer than the sum of the members
    assert gex.n_cycles <= sum({r["label"]: r["own_cycles"]
                                for r in rows}.values()) * 2


def test_compile_group_memoizes_and_refreshes():
    from repro.compiler import ProgramCache
    cache = ProgramCache(use_disk=False)
    eng = Engine(cache=cache)
    g1 = eng.compile_group([("mac", 4), ("multpim", 4)])
    g2 = eng.compile_group([("mac", 4), ("multpim", 4)])
    assert g1.inner.packed is g2.inner.packed      # fused artifact reused
    assert eng.compile_group([("multpim", 4), ("mac", 4)]
                             ).inner.packed is not g1.inner.packed
    cache.clear()                                  # base entries evicted
    g3 = eng.compile_group([("mac", 4), ("multpim", 4)])
    assert g3.inner.entry is not g1.inner.entry    # fused rebuilt too


def test_compile_group_rejects_bad_specs():
    eng = get_engine()
    with pytest.raises(ValueError):
        eng.compile_group([])
    with pytest.raises(TypeError):
        eng.compile_group(["mac"])                 # width required
    with pytest.raises(ValueError):
        eng.compile_group([("mac", 8, 0)])         # copies >= 1
    with pytest.raises(CapacityError):
        eng.compile_group([("mac", 8, 100)])       # > crossbar columns


# ------------------------------------------- column-budget chain policy ----
def test_column_budget_counts_packs_by_width_not_uniform_k():
    """The heterogeneous-K policy: a wide and a narrow program packed
    into one budget get different copy counts (narrow op fills the
    leftover), and weights skew the split toward the heavier stream."""
    wide = multpim_mac(8)       # ~107 cols
    narrow = multpim_multiplier(4)
    w, nw = wide.layout.n_cols, narrow.layout.n_cols
    counts = column_budget_counts([wide, narrow], max_cols=w + 3 * nw,
                                  weights=[1, 2])
    assert counts[0] == 1 and counts[1] >= 2       # not uniform
    used = counts[0] * w + counts[1] * nw
    assert used <= w + 3 * nw
    # equal budget, skewed weights -> skewed chains
    even = column_budget_counts([narrow, narrow], max_cols=8 * nw)
    assert even == [4, 4]
    skew = column_budget_counts([narrow, narrow], max_cols=8 * nw,
                                weights=[3, 1])
    assert skew[0] > skew[1] and sum(skew) == 8


def test_column_budget_counts_edge_cases():
    prog = multpim_multiplier(4)
    w = prog.layout.n_cols
    assert column_budget_counts([prog], None) == [1]
    assert column_budget_counts([prog], None, weights=[3.0]) == [3]
    with pytest.raises(CapacityError):
        column_budget_counts([prog, prog], max_cols=w)   # 1 each can't fit
    with pytest.raises(ValueError):
        column_budget_counts([], max_cols=100)
    with pytest.raises(ValueError):
        column_budget_counts([prog], max_cols=w, weights=[0.0])
    with pytest.raises(ValueError):
        column_budget_counts([prog], max_cols=w, weights=[1, 2])
    # partition bound honored too
    assert column_budget_counts(
        [prog, prog], max_cols=100 * w,
        max_partitions=2 * prog.n_partitions) == [1, 1]


def test_engine_group_counts_respects_policy_cap():
    eng = Engine(coschedule_k=2)
    counts = eng.group_counts([("mac", 8), ("mac", 8)])
    assert sum(counts) <= 2 * 2                    # coschedule_k per member
    assert all(c >= 1 for c in counts)
    # weights flow through to the split
    a, b = eng.group_counts([("mac", 8), ("mac", 8)], weights=[10, 1])
    assert a >= b


# -------------------------------------------------- co-scheduled matvec ----
@pytest.mark.parametrize("n,e,k", [(8, 8, 4), (8, 7, 3), (4, 5, 2)])
def test_matvec_coscheduled_exact_and_cheaper(n, e, k):
    """Co-scheduled inner products are exact (vs both the integer truth
    and the sequential path) and charge fewer cycles."""
    eng = get_engine()
    rng = np.random.default_rng(e * k)
    A = rng.integers(0, 1 << (n - 2), (5, e))
    x = rng.integers(0, 1 << (n - 2), e)
    want = A.astype(object) @ x.astype(object)
    mask = (1 << (2 * n)) - 1
    res_seq, cyc_seq = eng.matvec(A, x, n, k=1)
    res_co, cyc_co = eng.matvec(A, x, n, k=k)
    assert [int(r) for r in res_co] == [int(w) & mask for w in want]
    assert [int(r) for r in res_co] == [int(r) for r in res_seq]
    assert cyc_co < cyc_seq
    # >= 1.5x cycles-per-MAC reduction at the serving group sizes (the
    # PR target; k=2 at tiny e is dominated by the chain-merge tail)
    if k >= 3 and e >= 2 * k:
        assert cyc_seq / cyc_co >= 1.5


def test_matvec_default_is_coscheduled():
    """Inner products issue co-scheduled MAC groups by default."""
    eng = get_engine()
    rng = np.random.default_rng(1)
    A = rng.integers(0, 60, (3, 8))
    x = rng.integers(0, 60, 8)
    res_d, cyc_d = eng.matvec(A, x, 8)
    res_s, cyc_s = eng.matvec(A, x, 8, k=1)
    assert [int(a) for a in res_d] == [int(b) for b in res_s]
    assert cyc_d < cyc_s


def test_oversized_mac_falls_back_to_sequential():
    """Regression: a MAC too wide for even one crossbar copy must not
    raise from the default paths — max_coschedule_k reports 0 and
    inner_product falls back to the plain compile."""
    from repro.core.costmodel import CrossbarSpec
    one_cols = get_engine().compile("mac", 8).program.layout.n_cols
    tiny = Engine(crossbar=CrossbarSpec(cols=one_cols - 1))
    assert tiny.max_coschedule_k("mac", 8) == 0
    rng = np.random.default_rng(0)
    A = rng.integers(0, 50, (2, 3))
    v = rng.integers(0, 50, 3)
    res, _ = tiny.matvec(A, v, 8)                 # k clamps to 1
    assert [int(r) for r in res] == \
        [int(w_) for w_ in (A.astype(object) @ v.astype(object))]
    with pytest.raises(CapacityError):
        tiny.compile_batch("mac", 8, 2)           # explicit K still errors


# ------------------------------------------------------- row_block tune ----
def test_autotune_row_block_policy():
    assert autotune_row_block(1) == 8
    assert autotune_row_block(8) == 8
    assert autotune_row_block(9) == 16
    assert autotune_row_block(300) == 512
    assert autotune_row_block(10000) == 512


def test_engine_autotunes_pallas_row_block_per_rows_bucket():
    eng = Engine(backend="pallas")
    exe = eng.compile("multpim", 4)
    assert exe.cost().row_block is None            # not tuned yet
    exe.run({"a": [3, 5, 7], "b": [5, 6, 7]})
    assert exe.cost().row_block == 8               # 3 rows -> 8-row tile
    # A wider batch tunes from its own rows-bucket: the small warmup
    # batch above does NOT pin the 8-row tile (first-batch-wins is gone).
    exe2 = eng.compile("multpim", 2)
    out = exe2.run({"a": list(range(20)) * 2, "b": [3] * 40})
    assert [int(v) for v in out["out"][:4]] == [0, 3, 6, 9]
    assert exe2.cost().row_block == 64             # 40 rows -> 64-row tile
    # Same shape class keeps the same block (stable jit cache per
    # bucket: same tile -> same traced shapes).
    exe2.run({"a": [1] * 33, "b": [2] * 33})
    assert exe2.cost().row_block == 64


def test_explicit_row_block_is_honored_over_autotune():
    eng = Engine(backend="pallas:row_block=64")
    exe = eng.compile("multpim", 4)
    exe.run({"a": [1], "b": [1]})
    assert exe.cost().row_block == 64              # policy, not batch shape
    bk = resolve_backend("pallas:interpret=true,row_block=64")
    assert bk.row_block == 64
