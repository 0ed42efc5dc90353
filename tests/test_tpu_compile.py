"""The main path's kernels compile for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers a kernel or jitted step
with shapes placed on one device of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what Mosaic/XLA cannot
lower (dynamic lane slices, oversized VMEM or SMEM blocks). The topology
is described inside a module fixture, so collection never loads the TPU
library and the file skips where it cannot be described.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.engine import Engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def eng():
    return Engine(backend="numpy")


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op,n,k", [("multpim", 32, 1), ("mac", 8, 1),
                                    ("mac", 8, 4)])
def test_packed_pallas_kernel_compiles(one_chip, eng, op, n, k):
    """The packed kernel at 1024 words (32,768 crossbar rows): column-
    major state with dynamic leading-axis column access and the op
    stream in SMEM blocks."""
    from repro.kernels.crossbar_step import _run_packed, op_stream
    exe = eng.compile(op, n) if k == 1 else eng.compile_batch(op, n, k)
    packed = exe.packed
    stream = op_stream(packed)
    words = _spec(one_chip, (1024, packed.init_mask.shape[1]), jnp.uint32)
    tab = _spec(one_chip, stream.shape, jnp.int32)
    _assert_kernel(_run_packed.lower(words, tab, interpret=False).compile())


def test_unpacked_pallas_kernel_compiles(one_chip, eng):
    """The one-hot-matmul kernel: multpim N=32, 4096 rows, row_block 256."""
    from repro.kernels.crossbar_step import _run
    p = eng.compile("multpim", 32).packed
    t, m = p.gate_id.shape
    c = -(-p.init_mask.shape[1] // 128) * 128
    tab = _spec(one_chip, (t, m), jnp.int32)
    args = (_spec(one_chip, (4096, c), jnp.float32), tab, tab, tab, tab,
            tab, _spec(one_chip, (t, c), jnp.float32))
    compiled = _run.lower(*args, row_block=256, interpret=False, t=t, m=m,
                          c=c).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("macro", [1, 8])
@pytest.mark.parametrize("op,n", [("multpim", 32), ("mac", 8)])
def test_packed_scan_compiles(one_chip, eng, op, n, macro):
    """The packed ``lax.scan`` executor at 1024 words."""
    from repro.kernels.ref import _packed_scan, packed_device_tables
    p = eng.compile(op, n).packed
    tabs, factor = packed_device_tables(p, macro)
    st = _spec(one_chip, (1024, p.init_mask.shape[1]), jnp.uint32)
    tab_specs = [_spec(one_chip, x.shape, x.dtype) for x in tabs]
    compiled = _packed_scan.lower(st, *tab_specs, factor=factor).compile()
    assert compiled.memory_analysis() is not None


def test_resident_jax_chain_step_compiles(one_chip, eng):
    """The fused resident pass (stage scan + column moves + MAC scan) at
    12,288 rows, qwen3-8b's d_ff."""
    rows = 12288
    rex = eng.resident(8, rows=rows, backend="jax:pack=true")
    chain, idx = rex.chain, rex.index
    w = -(-rows // 32)
    dev = _spec(one_chip, (w, idx.c_mac), jnp.uint32)
    planes = _spec(one_chip, (w, len(idx.ab_cols)), jnp.uint32)
    fresh = _spec(one_chip, (w, 1), jnp.uint32)
    compiled = chain._step.lower(dev, planes, fresh).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= w * idx.c_mac * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 30     # v5e HBM
