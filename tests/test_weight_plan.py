"""Weight-stationary PIM linears: weights planned once give the per-step
path's results bit for bit, from an int8 x int8 product, and the serve
step plans once per weight set."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.pim
from repro import obs
from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_host_mesh
from repro.models import build_model, plan_weights
from repro.pim import PlannedWeight, plan_weight
from repro.pim import quant
from repro.train import make_serve_step

pytestmark = pytest.mark.pim


def _linear(x, w, n_bits):
    return repro.pim.linear(x, w, n_bits)


# As plan_weights runs it: compiled, so its scales round as in a step.
_plan = jax.jit(plan_weight, static_argnums=1)


@pytest.mark.parametrize("n_bits", [8, 4])
@pytest.mark.parametrize("shape", [(64, 176), (64, 1000)],
                         ids=["ffn", "head"])
def test_planned_linear_bit_identical(n_bits, shape):
    """A batch-8 decode row through an FFN- and a head-shaped weight: the
    PIM linear on the float weight (planned in the call) and on its
    plan both give :func:`qmatmul_exact`'s result, bit for bit."""
    rng = np.random.default_rng(n_bits)
    x = jnp.asarray(rng.standard_normal((8, 1, shape[0])), jnp.float32)
    w = jnp.asarray(rng.standard_normal(shape) * 0.05, jnp.float32)
    want = jax.jit(lambda x, w: quant.qmatmul_exact(
        quant.quantize(x[:, 0], n_bits),
        quant.quantize(w, n_bits, axis=0))[:, None])(x, w)
    run = jax.jit(lambda x, w: _linear(x, w, n_bits))
    for got in (run(x, w), run(x, _plan(w, n_bits))):
        assert got.shape == want.shape == (8, 1, shape[1])
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_planned_weight_codes_and_scales():
    """Each layer of a stack is quantized on its own, per column, as
    ``quantize`` with ``axis=0`` does one weight, jitted and op by op;
    the codes are centred."""
    w = jax.random.normal(jax.random.key(1), (3, 32, 24))
    p = _plan(w, 8)
    assert p.q.dtype == jnp.int8 and p.scale.shape == (3, 1, 24)
    per_step = jax.jit(lambda w: quant.quantize(w, 8, axis=0))
    for i in range(3):
        ref = per_step(w[i])
        np.testing.assert_array_equal(np.asarray(p.q[i], np.int32),
                                      np.asarray(ref.q) - ref.zero)
        np.testing.assert_array_equal(p.scale[i], ref.scale)
    eager = plan_weight(w, 8)            # op by op, as an eager prefill
    for i in range(3):
        ref = quant.quantize(w[i], 8, axis=0)
        np.testing.assert_array_equal(np.asarray(eager.q[i], np.int32),
                                      np.asarray(ref.q) - ref.zero)
        np.testing.assert_array_equal(eager.scale[i], ref.scale)
    assert plan_weight(w, 12).q.dtype == jnp.int32


@pytest.mark.parametrize("n_bits,dtype", [(8, "i8"), (4, "i8"),
                                          (12, "i32")])
def test_planned_product_dtypes_in_lowered_hlo(n_bits, dtype):
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    w = jax.eval_shape(lambda: plan_weight(jnp.zeros((64, 128)), n_bits))
    text = jax.jit(lambda x, w: _linear(x, w, n_bits)).lower(
        x, w).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert len(dots) == 1
    assert (f"(tensor<8x64x{dtype}>, tensor<64x128x{dtype}>) -> "
            f"tensor<8x128xi32>") in dots[0]


def test_planned_weight_rejects_other_widths_and_pallas():
    """A weight planned at 8 bits refuses a product at 4."""
    w = plan_weight(jnp.ones((16, 8)), 8)
    x = jnp.ones((2, 16))
    with pytest.raises(ValueError, match="planned at 8"):
        _linear(x, w, 4)


def _cfg(linear="pim", block="ffn", **over):
    cfg = get_config("deepseek-7b").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=512, **over)
    return dataclasses.replace(cfg, pim_linear_mode=linear,
                               pim_linear_bits=8, pim_block_mode=block)


def _planned_paths(plan):
    leaves = jax.tree_util.tree_flatten_with_path(
        plan, is_leaf=lambda a: isinstance(a, PlannedWeight))[0]
    return {jax.tree_util.keystr(p) for p, a in leaves
            if isinstance(a, PlannedWeight)}


@pytest.mark.parametrize("linear,block,planned", [
    ("pim", "ffn", {"['lm_head']", "['scan'][0]['mlp']['w1']",
                    "['scan'][0]['mlp']['w2']", "['scan'][0]['mlp']['w3']"}),
    ("pim", "none", {"['lm_head']"}),
    ("pim", "full", {"['lm_head']", "['scan'][0]['mlp']['w1']",
                     "['scan'][0]['mlp']['w2']", "['scan'][0]['mlp']['w3']",
                     "['scan'][0]['wq']", "['scan'][0]['wk']",
                     "['scan'][0]['wv']", "['scan'][0]['wo']"}),
    ("off", "ffn", {"['scan'][0]['mlp']['w1']", "['scan'][0]['mlp']['w2']",
                    "['scan'][0]['mlp']['w3']"}),
    ("off", "none", set()),
])
def test_plan_follows_pim_scopes(linear, block, planned):
    """The planned weights are those the PIM-mode linears quantize; the
    other leaves are the same objects, and with nothing in ``pim`` mode
    the plan is the params themselves."""
    cfg = _cfg(linear, block)
    params = jax.jit(build_model(cfg).init)(jax.random.key(0))
    plan = plan_weights(cfg, params)
    assert _planned_paths(plan) == planned
    if not planned:
        assert plan is params
    was = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, a in jax.tree_util.tree_flatten_with_path(
            plan, is_leaf=lambda a: isinstance(a, PlannedWeight))[0]:
        if not isinstance(a, PlannedWeight):
            assert a is was[path], jax.tree_util.keystr(path)


def test_plan_of_a_tied_head_and_a_moe_block():
    """A tied head is planned as ``lm_head`` from ``embed.T``; the MoE
    block plans its shared FFN and q/k/v, not ``wo`` (a plain matmul),
    and its expert stacks ``we1``, ``we3``, ``we2`` with one scale for
    each layer of a stack: ``quantize``'s over the layer's whole (E, D,
    F) stack, codes less the offset."""
    cfg = dataclasses.replace(get_config("deepseek-moe-16b", smoke=True),
                              pim_linear_mode="pim", pim_block_mode="full")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    plan = plan_weights(cfg, params)
    paths = _planned_paths(plan)
    moe = [p for p in paths if "shared" in p]
    assert moe, paths
    stacks = {k: [p for p in paths if f"['{k}']" in p]
              for k in ("we1", "we2", "we3")}
    assert all(stacks.values()), paths
    leaves = jax.tree_util.tree_flatten_with_path(
        plan, is_leaf=lambda a: isinstance(a, PlannedWeight))[0]
    was = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, w in leaves:
        if not any(f"['{k}']" in jax.tree_util.keystr(path)
                   for k in stacks):
            continue
        f = was[path]
        assert w.q.shape == f.shape and w.q.dtype == jnp.int8
        assert w.scale.shape == f.shape[:-3] + (1, 1, 1)
        for i in np.ndindex(f.shape[:-3]):     # each layer of the stack
            ref = quant.quantize(f[i], 8)
            np.testing.assert_array_equal(np.asarray(w.q[i], np.int32),
                                          np.asarray(ref.q) - ref.zero)
            np.testing.assert_array_equal(w.scale[i].reshape(()),
                                          ref.scale)
    tied = dataclasses.replace(_cfg(), tie_embeddings=True)
    tparams = jax.jit(build_model(tied).init)(jax.random.key(0))
    assert "lm_head" not in tparams
    head = plan_weights(tied, tparams)["lm_head"]
    ref = _plan(tparams["embed"].T, 8)
    np.testing.assert_array_equal(head.q, ref.q)


def _moe_cfg(block):
    return dataclasses.replace(get_config("deepseek-v2-lite", smoke=True),
                               pim_linear_mode="pim", pim_linear_bits=8,
                               pim_block_mode=block)


@pytest.mark.parametrize("block", ["ffn", "full"])
def test_planned_step_quantizes_no_weight(block, monkeypatch):
    """Tracing the serve step on the plan quantizes activations only:
    no dense PIM linear quantizes a weight per step, and no MoE block an
    expert stack (``pim.stack_quantizations`` stays, the stacks are read
    planned); on the float params both are planned per call."""
    axes, planned = [], []
    real, real_plan = quant.quantize, quant.plan_weight

    def spy(x, n_bits=8, axis=None):
        axes.append(axis)
        return real(x, n_bits, axis)

    def plan_spy(w, n_bits, axis=-2):
        planned.append(w.shape)
        return real_plan(w, n_bits, axis)

    monkeypatch.setattr(quant, "quantize", spy)
    monkeypatch.setattr(quant, "plan_weight", plan_spy)
    reads = obs.counter(obs.STACK_PLAN_READS)
    quantizations = obs.counter(obs.STACK_QUANTIZATIONS)
    for cfg in (_cfg("pim", block), _moe_cfg(block)):
        model = build_model(cfg)
        params = jax.jit(model.init)(jax.random.key(0))
        plan = plan_weights(cfg, params)
        moe = "m" in cfg.layer_kinds()
        axes.clear()
        planned.clear()
        states = model.init_decode_state(2, 8)
        tok = jnp.zeros((2, 1), jnp.int32)
        serve_step, _ = make_serve_step(model, make_host_mesh(1))
        r0, q0 = reads.value, quantizations.value
        jax.eval_shape(serve_step, plan, states, tok, tok)
        assert axes and all(a is None for a in axes) and not planned
        assert quantizations.value == q0
        assert (reads.value > r0) == moe
        axes.clear()
        jax.eval_shape(serve_step, params, states, tok, tok)
        assert planned                  # float weights are planned per call
        assert (quantizations.value > q0) == moe


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_plan_covers_every_pim_linear_of_the_decode_step(arch, monkeypatch):
    """Every block kind (dense, local, MoE and its dense layer, RG-LRU,
    RWKV, cross-attention): on the plan, each ``pim``-mode linear of the
    decode step gets a planned weight and each MoE expert product a
    planned stack, so ``blocks.pim_weights`` and ``blocks.pim_stacks``
    list what the ``apply_*`` functions pass to ``pim_proj`` and
    ``_pim_ragged`` (``pim.stack_quantizations`` stays); a planned weight
    that reached a plain matmul would fail the trace."""
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              pim_linear_mode="pim", pim_linear_bits=8,
                              pim_block_mode="full")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    plan = jax.eval_shape(lambda p: plan_weights(cfg, p), params)
    got, stacks = [], []
    real, real_ragged = repro.pim.linear, repro.pim.ragged_linear

    def spy(x, w, n_bits):
        got.append(isinstance(w, PlannedWeight))
        return real(x, w, n_bits)

    def ragged_spy(xs, we, counts, n_bits):
        stacks.append(isinstance(we, PlannedWeight))
        return real_ragged(xs, we, counts, n_bits)

    monkeypatch.setattr(repro.pim, "linear", spy)
    monkeypatch.setattr(repro.pim, "ragged_linear", ragged_spy)
    quantizations = obs.counter(obs.STACK_QUANTIZATIONS)
    q0 = quantizations.value
    states = jax.eval_shape(lambda: model.init_decode_state(2, 8))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jax.eval_shape(model.decode_step, plan, tok, tok, states)
    assert got and all(got), got
    assert all(stacks) and bool(stacks) == ("m" in cfg.layer_kinds())
    assert quantizations.value == q0


_MOE_ARCHS = sorted(a for a in ARCHS
                    if "m" in get_config(a, smoke=True).layer_kinds())


@pytest.mark.parametrize("arch", _MOE_ARCHS)
def test_moe_decode_step_reads_planned_stacks_and_prefill_quantizes(arch):
    """Every MoE architecture in PIM ``ffn`` mode: tracing the decode
    step on the plan counts no ``pim.stack_quantizations`` and three
    ``pim.stack_plan_reads`` an MoE block; its prefill on the float
    params quantizes the stacks in the call."""
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              pim_linear_mode="pim", pim_linear_bits=8,
                              pim_block_mode="ffn")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    plan = jax.eval_shape(lambda p: plan_weights(cfg, p), params)
    reads = obs.counter(obs.STACK_PLAN_READS)
    quantizations = obs.counter(obs.STACK_QUANTIZATIONS)
    r0, q0 = reads.value, quantizations.value
    states = jax.eval_shape(lambda: model.init_decode_state(2, 8))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jax.eval_shape(model.decode_step, plan, tok, tok, states)
    assert quantizations.value == q0
    assert reads.value - r0 >= 3 and (reads.value - r0) % 3 == 0
    r0 = reads.value
    prompts = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    jax.eval_shape(model.forward, params, prompts)
    assert reads.value == r0 and quantizations.value - q0 >= 3


def _tiny_serve(seed=0):
    cfg = _cfg()
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.key(seed))


def test_serve_step_plans_once_and_matches_decode_step():
    """Over several steps the planned serve step gives the tokens and
    states of ``model.decode_step`` on the float params; it plans once,
    then reuses, and plans again for another params object."""
    model, params = _tiny_serve()
    b = 4
    states = model.init_decode_state(b, 16)
    ref_states = jax.tree.map(jnp.copy, states)
    rng = np.random.default_rng(3)
    tok = jnp.asarray(rng.integers(0, 512, (b, 1)), jnp.int32)
    _, jit_for = make_serve_step(model, make_host_mesh(1))
    step = jit_for(params, states, {"token": tok, "position": tok})
    plans, reuses = (obs.counter(obs.WEIGHT_PLANS),
                     obs.counter(obs.PLAN_REUSES))
    p0, r0 = plans.value, reuses.value
    ref = jax.jit(model.decode_step)
    served = []
    for t in range(6):
        pos = jnp.full((b, 1), t, jnp.int32)
        out, states = step(params, states, tok, pos)
        logits, ref_states = ref(params, tok, pos, ref_states)
        want = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        np.testing.assert_array_equal(out, want)
        served.append(np.asarray(out))
        tok = jnp.asarray(rng.integers(0, 512, (b, 1)), jnp.int32)
    assert len({tuple(s.ravel()) for s in served}) > 1
    assert jax.tree.all(jax.tree.map(
        lambda a, c: bool(jnp.array_equal(a, c)), states, ref_states))
    assert (plans.value - p0, reuses.value - r0) == (1, 5)

    _, other = _tiny_serve(seed=1)
    pos = jnp.full((b, 1), 6, jnp.int32)
    out, states = step(other, states, tok, pos)
    logits, _ = ref(other, tok, pos, ref_states)
    np.testing.assert_array_equal(
        out, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
    assert (plans.value - p0, reuses.value - r0) == (2, 5)


def test_serve_step_with_pim_off_runs_on_the_params():
    cfg = _cfg("off", "none")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    states = model.init_decode_state(2, 8)
    tok = jnp.zeros((2, 1), jnp.int32)
    _, jit_for = make_serve_step(model, make_host_mesh(1))
    step = jit_for(params, states, {"token": tok, "position": tok})
    assert all(a is b for a, b in zip(jax.tree.leaves(step.plan(params)),
                                      jax.tree.leaves(params)))
    out, _ = step(params, states, tok, tok)
    logits, _ = model.decode_step(params, tok, tok,
                                  model.init_decode_state(2, 8))
    np.testing.assert_array_equal(
        out, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))


def test_serve_step_lowers_on_shapes():
    """``jit_for(...).lower`` takes ShapeDtypeStruct params, as the dry
    run gives them, and lowers the step on int8 weights."""
    model, _ = _tiny_serve()
    params_like = jax.eval_shape(model.init, jax.random.key(0))
    states_like = jax.eval_shape(lambda: model.init_decode_state(2, 8))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    _, jit_for = make_serve_step(model, make_host_mesh(1))
    step = jit_for(params_like, states_like, {"token": tok, "position": tok})
    text = step.lower(params_like, states_like, tok, tok).as_text()
    assert "jit_serve_step" in text and "xi8" in text
