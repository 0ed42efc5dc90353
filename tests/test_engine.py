"""repro.engine: device/executable facade — backend parity, marshalling,
OpSpec canonicalization, disk persistence, legacy-path equivalence."""
import numpy as np
import pytest

from repro.compiler import (OpSpec, PassConfig, ProgramCache, cache_stats,
                            clear_cache, compile_cached)
from repro.core.bits import from_bits, to_bits
from repro.engine import (Engine, Executable, get_engine, resolve_backend)

pytestmark = pytest.mark.core

BACKENDS = ["numpy", "jax", "pallas"]          # pallas: interpret=True on CPU


def _mask(n):
    return (1 << n) - 1


# ------------------------------------------------- backend parity ----
@pytest.mark.parametrize("n", [4, 8, 16])
@pytest.mark.parametrize("op", ["multpim", "rime"])
def test_multiplier_backend_parity(op, n):
    """Executable.run is bit-identical across numpy/jax/pallas backends,
    through both the int-marshalling and raw bit-plane paths."""
    eng = get_engine()
    exe = eng.compile(op, n)
    rng = np.random.default_rng(n)
    rows = 16
    a = rng.integers(0, 1 << n, rows)
    b = rng.integers(0, 1 << n, rows)

    outs = {bk: exe.run({"a": a, "b": b}, backend=bk)["out"]
            for bk in BACKENDS}
    want = [(int(x) * int(y)) & _mask(2 * n) for x, y in zip(a, b)]
    for bk, out in outs.items():
        assert [int(v) for v in out] == want, f"{op}/N={n} on {bk}"

    # bit-plane inputs -> bit-plane outputs, same values
    bits = exe.run({"a": to_bits(a, n), "b": to_bits(b, n)},
                   backend="numpy")["out"]
    assert bits.shape == (rows, 2 * n)
    assert [int(v) for v in from_bits(bits)] == want


@pytest.mark.parametrize("n", [4, 8, 16])
def test_mac_backend_parity(n):
    """The Section-VI MAC agrees across backends, int-marshalled."""
    eng = get_engine()
    rng = np.random.default_rng(7 * n)
    rows = 8
    a = rng.integers(0, 1 << n, rows)
    b = rng.integers(0, 1 << n, rows)
    s = rng.integers(0, 1 << (2 * n - 2), rows)
    c = rng.integers(0, 1 << (2 * n - 2), rows)
    results = [eng.mac(a, b, s, c, n, backend=bk) for bk in BACKENDS]
    lo0, sh0, ch0 = results[0]
    for x, y, si, ci, l, s2, c2 in zip(a, b, s, c, lo0, sh0, ch0):
        want = (int(x) * int(y) + int(si) + int(ci)) & _mask(2 * n)
        assert (int(l) + ((int(s2) + int(c2)) << n)) & _mask(2 * n) == want
    for lo, sh, ch in results[1:]:
        assert [int(v) for v in lo] == [int(v) for v in lo0]
        assert [int(v) for v in sh] == [int(v) for v in sh0]
        assert [int(v) for v in ch] == [int(v) for v in ch0]


def test_int_marshalling_rejects_ambiguous_shapes():
    exe = get_engine().compile("multpim", 4)
    with pytest.raises(ValueError):
        exe.run({"a": np.zeros((2, 3)), "b": [1, 2]})     # wrong bit width
    with pytest.raises(ValueError):
        exe.run({"a": 3 * np.ones((2, 4)), "b": [1, 2]})  # not {0,1} planes
    with pytest.raises(KeyError):
        exe.run({"a": [1, 2]})                            # missing input


def test_executable_surface():
    exe = get_engine().compile("multpim", 8)
    assert exe.n_cycles == exe.program.n_cycles
    assert exe.packed.gate_id.shape[0] == exe.n_cycles
    cost = exe.cost()
    assert cost.cycles == exe.n_cycles
    assert cost.memristors == exe.program.n_memristors
    assert cost.latency_us > 0 and cost.energy_uj > 0
    assert exe.verify().ok
    assert exe.input_widths == {"a": 8, "b": 8}


def test_backend_spec_strings():
    bk = resolve_backend("pallas:interpret=true,row_block=64")
    assert bk.interpret is True and bk.row_block == 64
    assert resolve_backend("numpy").name == "numpy"
    with pytest.raises(KeyError):
        resolve_backend("tpu-v9")


# -------------------------------------- OpSpec canonicalization ----
def test_permuted_flags_hit_same_cache_entry():
    """Regression: dict flags used to be order-sensitive/unhashable in
    edge cases; OpSpec canonicalizes (sorted, frozen)."""
    clear_cache()
    e1 = compile_cached("multpim", 8, flags={"skip_last_stages": True,
                                             "name": "x"})
    e2 = compile_cached("multpim", 8, flags={"name": "x",
                                             "skip_last_stages": True})
    e3 = compile_cached(OpSpec.make("multpim", 8,
                                    {"name": "x", "skip_last_stages": True}))
    assert e1 is e2 is e3
    st = cache_stats()
    assert st["entries"] == 1 and st["misses"] == 1 and st["hits"] == 2


def test_builders_receive_thawed_flag_values():
    """Regression: canonicalization must not leak frozen forms into the
    builder call — dict-valued flags arrive as dicts, lists as lists."""
    seen = {}

    def builder(n, windows=None, taps=None):
        seen.update(windows=windows, taps=taps)
        from repro.core.multpim import multpim_multiplier
        return multpim_multiplier(n)

    import repro.compiler.cache as cache_mod
    import pytest as _pytest
    mp = _pytest.MonkeyPatch()
    try:
        mp.setattr(cache_mod, "BUILDERS", dict(cache_mod.BUILDERS))
        mp.setattr(cache_mod, "_CUSTOM_KINDS", set(cache_mod._CUSTOM_KINDS))
        cache_mod.register_builder("flagged", builder)
        ProgramCache().get_or_compile(
            "flagged", 4, flags={"windows": {"a": 1}, "taps": [3, 1]})
    finally:
        mp.undo()
    assert seen["windows"] == {"a": 1} and seen["taps"] == [3, 1]


def test_opspec_identity_and_hash():
    s1 = OpSpec.make("multpim", 8, {"b": 1, "a": [1, {"z": 2}]})
    s2 = OpSpec.make("multpim", 8, {"a": [1, {"z": 2}], "b": 1})
    assert s1 == s2 and hash(s1) == hash(s2)
    assert s1.content_hash() == s2.content_hash()
    # different flags / pass config / width -> different identity
    assert OpSpec.make("multpim", 8).content_hash() != s1.content_hash()
    assert (OpSpec.make("multpim", 8, config=PassConfig(remap=False))
            != OpSpec.make("multpim", 8))
    {s1: "hashable"}     # usable as a dict key


def test_engine_op_aliases_share_entries():
    clear_cache()
    eng = get_engine()
    a = eng.compile("mac", 8)
    b = eng.compile("multpim_mac", 8)
    assert a.entry is b.entry


# ------------------------------------------------- disk persistence ----
def test_disk_cache_cold_start_skips_compile_and_verify(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    warm = ProgramCache()
    e1 = warm.get_or_compile("multpim", 4)
    assert warm.stats()["compiles"] == 1
    assert list(tmp_path.glob("multpim_n4_*.npz"))

    cold = ProgramCache()                       # fresh process stand-in
    e2 = cold.get_or_compile("multpim", 4)
    st = cold.stats()
    assert st["disk_hits"] == 1 and st["compiles"] == 0
    assert e2.from_disk and e2.verified is not None and e2.verified.ok
    for f in ("gate_id", "in_cols", "out_col", "init_mask"):
        np.testing.assert_array_equal(getattr(e1.packed, f),
                                      getattr(e2.packed, f))
    # the reloaded program still multiplies, on every backend
    eng = Engine(cache=cold)
    exe = eng.compile("multpim", 4)
    out = exe.run({"a": [3, 15], "b": [5, 15]})
    assert [int(v) for v in out["out"]] == [15, 225]


def test_disk_cache_disable_and_clear(tmp_path, monkeypatch):
    from repro.compiler.diskcache import (cache_dir, clear_disk_cache,
                                          disk_stats)
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    ProgramCache().get_or_compile("multpim", 4)
    assert disk_stats()["entries"] == 1
    assert clear_disk_cache() == 1
    assert disk_stats()["entries"] == 0
    monkeypatch.setenv("REPRO_CACHE_DIR", "off")
    assert cache_dir() is None
    c = ProgramCache()
    c.get_or_compile("multpim", 4)
    assert c.stats()["disk_hits"] == 0 and disk_stats()["entries"] == 0


def test_custom_builders_never_touch_disk(tmp_path, monkeypatch):
    """A runtime-registered builder must not spill to (or load from) the
    shared disk cache — its content hash would collide with the stock
    kind's and poison other processes."""
    import repro.compiler.cache as cache_mod
    from repro.compiler import register_builder
    from repro.core.multpim import multpim_multiplier
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(cache_mod, "_CUSTOM_KINDS", set())
    monkeypatch.setattr(cache_mod, "BUILDERS", dict(cache_mod.BUILDERS))
    register_builder("my_variant", lambda n, **kw: multpim_multiplier(n))
    c = ProgramCache()
    c.get_or_compile("my_variant", 4)
    assert not list(tmp_path.glob("my_variant*"))
    c2 = ProgramCache()
    c2.get_or_compile("my_variant", 4)
    assert c2.stats()["disk_hits"] == 0 and c2.stats()["compiles"] == 1


def test_disk_cache_corrupt_file_recompiles(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    ProgramCache().get_or_compile("multpim", 4)
    path = next(tmp_path.glob("*.npz"))
    path.write_bytes(b"not an npz")
    c = ProgramCache()
    e = c.get_or_compile("multpim", 4)
    assert c.stats()["compiles"] == 1 and not e.from_disk


# -------------------------------------------- legacy-path parity ----
def test_engine_matvec_matches_pre_redesign_path():
    """engine.matvec == the pre-redesign core.matvec semantics: the raw
    (uncompiled) schedule executed per call, and the exact product."""
    eng = get_engine()
    rng = np.random.default_rng(3)
    A = rng.integers(0, 60, (6, 4))
    x = rng.integers(0, 60, 4)
    res_new, cyc_new = eng.matvec(A, x, 8)
    res_raw, cyc_raw = eng.matvec(A, x, 8, use_compiler=False)
    want = A.astype(object) @ x.astype(object)
    assert [int(r) for r in res_new] == [int(w) for w in want]
    assert [int(r) for r in res_raw] == [int(w) for w in want]
    # legacy shim delegates to the same engine, bit-identically
    from repro.core.matvec import matvec as legacy_matvec
    res_shim, cyc_shim = legacy_matvec(A, x, 8)
    assert [int(r) for r in res_shim] == [int(r) for r in res_new]
    assert cyc_shim == cyc_new


def test_engine_linear_matches_pre_redesign_pim_linear():
    """The PIM linear (``repro.pim.linear``) gives the pre-redesign
    result: quantize -> exact int matmul -> dequantize."""
    import jax.numpy as jnp

    from repro import pim
    from repro.pim.quant import qmatmul_exact, quantize
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 12)), jnp.float32)
    want = qmatmul_exact(quantize(x, 8), quantize(w, 8, axis=0))
    got = pim.linear(x, w, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_run_many_identity_stable_tables():
    """Compile once, run many: repeated compiles hand back the same
    packed table objects (keeps executor jit caches warm)."""
    eng = get_engine()
    e1 = eng.compile("multpim", 8)
    e2 = eng.compile("multpim", 8)
    assert e1.packed is e2.packed
    assert e1.program is e2.program
