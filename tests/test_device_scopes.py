"""repro.obs device scopes: the serve step's op -> scope map, scopes that
change only HLO metadata, a registration that compiles nothing, the
compile listener, and ``backend.kernel`` spans that end on the device."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.engine import Engine
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models import plan_weights
from repro.obs import scopes
from repro.train import make_serve_step

pytestmark = pytest.mark.core


def tiny_pim_decoder():
    cfg = get_config("deepseek-7b").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=512)
    cfg = dataclasses.replace(cfg, pim_linear_mode="pim", pim_linear_bits=8,
                              pim_block_mode="ffn")
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.key(0))


def tiny_pim_moe_decoder():
    """deepseek-v2-lite's smoke config (latent attention, held experts)
    with PIM FFNs: the MoE scopes sit in its step."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite", smoke=True),
                              pim_linear_mode="pim", pim_linear_bits=8,
                              pim_block_mode="ffn")
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.key(0))


def serve_args(model, batch, cache_len=16):
    states = model.init_decode_state(batch, cache_len)
    tok = jnp.zeros((batch, 1), jnp.int32)
    pos = jnp.full((batch, 1), 3, jnp.int32)
    return states, tok, pos


@pytest.fixture()
def programs():
    """An empty program registry for one test, cleared again after."""
    reg = scopes.get_programs()
    reg.clear()
    yield reg
    reg.clear()


def test_serve_step_scopes_cover_the_four_scopes(programs):
    model, params = tiny_pim_decoder()
    states, tok, pos = serve_args(model, 2)
    _, jit_for = make_serve_step(model, make_host_mesh(1))
    step = jit_for(params, states, {"token": tok, "position": pos})
    step(params, states, tok, pos)
    m = obs.device_scopes()
    assert all(k.startswith("jit_serve_step/") for k in m)
    found = set(m.values())
    for s in (obs.KV_CACHE, obs.ATTENTION, obs.PIM_QUANTIZE,
              obs.PIM_MATMUL):
        assert s in found, f"no op in scope {s}"
    assert not found & {obs.MOE_ROUTE, obs.MOE_EXPERTS}   # no experts
    containers = [k for k, v in m.items() if v == obs.CONTAINER]
    assert containers and all("/while" in k for k in containers)
    assert obs.device_scopes() == m          # built once, then kept


def test_moe_serve_step_scopes_route_and_experts(programs):
    """A MoE decoder with PIM FFNs: the router's work reads as
    ``moe.route``, the held experts' grouped products with their
    quantization as ``moe.experts`` (not ``pim.*``), and the dense PIM
    linears (shared experts, dense layer, head) still as ``pim.*``."""
    model, params = tiny_pim_moe_decoder()
    states, tok, pos = serve_args(model, 2)
    _, jit_for = make_serve_step(model, make_host_mesh(1))
    step = jit_for(params, states, {"token": tok, "position": pos})
    step(params, states, tok, pos)
    found = set(obs.device_scopes().values())
    for s in obs.SCOPES:
        assert s in found, f"no op in scope {s}"


def test_pim_ops_inside_the_experts_read_as_the_experts():
    assert scopes.scope_of("jit(f)/moe.experts/pim.quantize/max") \
        == obs.MOE_EXPERTS
    assert scopes.scope_of("jit(f)/moe.experts/pim.matmul/dot") \
        == obs.MOE_EXPERTS
    assert scopes.scope_of("jit(f)/moe.route/sort") == obs.MOE_ROUTE
    assert scopes.scope_of("jit(f)/attention/pim.matmul/dot") \
        == obs.PIM_MATMUL


def test_hlo_scopes_innermost_scope_and_containers():
    text = "\n".join([
        "HloModule jit_step, is_scheduled=true",
        "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
        '  %a.1 = f32[4]{0} add(%p, %p), metadata={op_name='
        '"jit(step)/attention/kv_cache/dynamic_update_slice"}',
        '  %while.3 = (s32[], f32[4]{0}) while(%t), condition=%c, '
        'body=%b, metadata={op_name="jit(step)/kv_cache/while"}',
        '  ROOT %fusion.2 = f32[4]{0:T(8,128)S(1)} fusion(%a.1), '
        'kind=kLoop, calls=%f, metadata={op_name="jit(step)/mul"}',
        "  %copy-done = f32[4]{0} copy-done((f32[4]{0}) %copy-start)",
        "}"])
    module, ops = scopes.hlo_scopes(text)
    assert module == "jit_step"
    assert ops == {"a.1": obs.KV_CACHE, "while.3": obs.CONTAINER,
                   "fusion.2": None, "copy-done": None}


def test_registration_takes_scalars_arrays_and_shapes(programs):
    def f(a, b, c):
        with obs.scope(obs.PIM_MATMUL):
            return a * b + c.sum()

    step = jax.jit(f)
    obs.register_program(step, 2.0, np.ones((3,), np.float32),
                         jax.ShapeDtypeStruct((4,), jnp.float32))
    m = obs.device_scopes()
    assert m and all(k.startswith("jit_f/") for k in m)
    assert obs.PIM_MATMUL in m.values()


def test_scope_names_only_from_the_vocabulary():
    with pytest.raises(ValueError, match="unknown device scope"):
        obs.scope("ffn")


def _stripped_hlo(model, params, batch):
    states, tok, pos = serve_args(model, batch)
    serve_step, _ = make_serve_step(model, make_host_mesh(1))
    text = jax.jit(serve_step, donate_argnums=(1,)).lower(
        params, states, tok, pos).compile().as_text()
    # The source tables printed before the computations, and the
    # metadata that points into them, name source lines alone.
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "\n", text)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("build", [tiny_pim_decoder, tiny_pim_moe_decoder])
def test_scopes_change_only_metadata(monkeypatch, build):
    """The optimized serve step with and without the scopes differs in
    HLO metadata alone: the executable the hot path runs is the same."""
    model, params = build()
    scoped = _stripped_hlo(model, params, 3)
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    plain = _stripped_hlo(model, params, 3)
    assert "ENTRY" in plain and "FileNames" not in plain
    assert not any(s in plain for s in obs.SCOPES)
    assert scoped == plain


def test_registration_compiles_nothing_and_records_no_span(
        programs, monkeypatch):
    """Building and running the serve step with tracing off compiles as
    often with the registration as without it, and records no span."""
    obs.watch_compiles()
    compiles = obs.counter(obs.COMPILES)
    model, params = tiny_pim_decoder()
    plan_weights(model.cfg, params)     # compiles once per weight shape
    tracer = obs.get_tracer()
    assert not tracer.enabled
    events_before = len(tracer)

    def build_and_run(batch):
        """-> (compiles while building, compiles in all)."""
        states, tok, pos = serve_args(model, batch)
        start = compiles.value
        _, jit_for = make_serve_step(model, make_host_mesh(1))
        step = jit_for(params, states, {"token": tok, "position": pos})
        built = compiles.value - start
        step(params, states, tok, pos)[0].block_until_ready()
        return built, compiles.value - start

    registered = build_and_run(5)
    monkeypatch.setattr(obs, "register_program", lambda *a: None)
    plain = build_and_run(6)
    assert registered == plain == (0, 1)
    assert len(tracer) == events_before


def test_compile_listener_spans_only_while_enabled():
    obs.watch_compiles()
    tracer = obs.get_tracer()
    compiles = obs.counter(obs.COMPILES)
    x = jnp.arange(7.0)
    n0 = compiles.value
    jax.jit(lambda a: a * 3 + 1)(x).block_until_ready()
    assert compiles.value == n0 + 1
    assert not any(e.get("name") == "jax.compile"
                   for e in tracer.trace_dict()["traceEvents"])
    tracer.reset()
    tracer.enable()
    try:
        jax.jit(lambda a: a * 5 - 2)(x).block_until_ready()
    finally:
        tracer.disable()
    spans = [e for e in tracer.trace_dict()["traceEvents"]
             if e.get("name") == "jax.compile"]
    tracer.reset()
    assert compiles.value == n0 + 2
    assert scopes.COMPILE_EVENT in {e["args"]["event"] for e in spans}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in spans)


@pytest.mark.parametrize("backend", ["jax:pack=true", "pallas:pack=true"])
def test_backend_kernel_span_ends_when_the_kernel_is_ready(
        backend, monkeypatch):
    """With tracing on, the array a chain's dispatch returns inside
    ``backend.kernel`` is ready when the span closes."""
    import repro.kernels.crossbar_step as xs
    rex = Engine(backend).resident(8, rows=4)
    chain = rex.chain
    outs = []

    def keep(fn):
        def wrapped(*a, **k):
            out = fn(*a, **k)
            outs.append(out)
            return out
        return wrapped

    if backend.startswith("jax"):
        for name in ("_first", "_step", "_drain"):
            monkeypatch.setattr(chain, name, keep(getattr(chain, name)))
    else:
        monkeypatch.setattr(xs, "crossbar_run_pallas_packed",
                            keep(xs.crossbar_run_pallas_packed))
    tracer = obs.get_tracer()
    ready_at_close = []
    record = tracer._record

    def on_record(name, *a, **k):
        if name == "backend.kernel":
            ready_at_close.append(outs[-1].is_ready())
        record(name, *a, **k)

    monkeypatch.setattr(tracer, "_record", on_record)
    rng = np.random.default_rng(3)
    tracer.reset()
    tracer.enable()
    try:
        for _ in range(3):
            rex.step(rng.integers(0, 40, 4), rng.integers(0, 40, 4))
        rex.drain()
    finally:
        tracer.disable()
        tracer.reset()
    assert len(ready_at_close) >= 4 and all(ready_at_close)
