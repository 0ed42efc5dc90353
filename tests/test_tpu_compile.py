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


@pytest.mark.parametrize("cell,t", [("deepseek-7b", 1024),
                                    ("deepseek-v2-lite", 4096),
                                    ("deepseek-7b", 1031),
                                    ("deepseek-v2-lite", 4099)])
def test_decode_attention_kernels_compile(one_chip, cell, t):
    """The decode-attention kernels over the decode cells' stacks:
    deepseek-7b's K/V (6 layers, batch 8, 1024 positions, 32 heads of
    128) and deepseek-v2-lite's latents (8 layers, batch 32, 4096
    positions, rank 512, rope keys of 64 positions-last); and at prime
    cache lengths, where the last time block runs past the cache."""
    from repro.kernels.decode_attention import (kv_decode_attention,
                                                latent_decode_attention)
    i32 = _spec(one_chip, (), jnp.int32)
    if cell == "deepseek-7b":
        kv = _spec(one_chip, (6, 8, t, 32, 128), jnp.float32)
        q = _spec(one_chip, (8, 1, 32, 128), jnp.float32)
        lowered = kv_decode_attention.lower(q, kv, kv, i32, i32, i32,
                                            interpret=False)
    else:
        c = _spec(one_chip, (8, 32, t, 512), jnp.float32)
        kpe = _spec(one_chip, (8, 32, 64, t), jnp.float32)
        q_lat = _spec(one_chip, (32, 16, 512), jnp.float32)
        q_pe = _spec(one_chip, (32, 16, 64), jnp.float32)
        lowered = latent_decode_attention.lower(
            q_lat, q_pe, c, kpe, i32, i32, i32, scale=0.1,
            precision=jax.lax.Precision.HIGHEST, interpret=False)
    _assert_kernel(lowered.compile())


@pytest.mark.parametrize("arch", ["gemma2-9b", "deepseek-v2-lite"])
def test_decode_step_copies_no_layer_of_a_cache(one_chip, arch):
    """A whole decode step for one chip over stacked layers, at the
    cells' cache widths (heads of 128; latents of 512 with rope keys of
    64) and smoke widths elsewhere: gemma2's windowed ring beside a
    global layer, and MLA's latents behind a dense layer. Attention is
    the kernel; no copy or dynamic slice in the optimized HLO yields a
    layer's cache or a stack, and the temporaries hold less than one
    layer's cache. (At these sizes the compiler may keep a small stack in
    fast memory through the scan: its asynchronous ``copy-start`` /
    ``copy-done`` between memory spaces is not a copy of the cache.)"""
    import re

    from repro.configs import MLAConfig, get_config
    from repro.models import build_model
    cfg = get_config(arch, smoke=True)
    if arch == "gemma2-9b":
        cfg = cfg.scaled(n_layers=4, window=128, n_heads=2, n_kv_heads=1,
                         head_dim=128)
    else:
        cfg = cfg.scaled(n_layers=4, layer_pattern="dmmm", n_heads=2,
                         mla=MLAConfig(512, None, 128, 64, 128))
    m = build_model(cfg)
    on = lambda t: jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), t)
    params = on(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    states = on(jax.eval_shape(lambda: m.init_decode_state(8, 512)))
    tok = _spec(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(m.decode_step, donate_argnums=(3,)).lower(
        params, tok, tok, states).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    caches = [a for a in jax.tree.leaves(states) if a.ndim >= 3]
    shapes = set()
    for a in caches:
        shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    moved = []
    for name, dims, op in re.findall(
            r"%([\w.\-]+) = f32\[([\d,]*)\]\S* ([\w\-]+)\(", text):
        kind = op if op != "fusion" else name
        if op in ("copy-start", "copy-done"):
            continue
        if ("copy" in kind or "dynamic-slice" in kind) and tuple(
                int(x) for x in dims.split(",") if x) in shapes:
            moved.append((name, dims))
    assert not moved, moved
    layer = min(a.size // a.shape[0] * 4 for a in caches)
    assert compiled.memory_analysis().temp_size_in_bytes < layer
