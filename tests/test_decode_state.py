"""The decode step writes its state in place.

A state with a time axis (K/V and latent caches) has only the token's
entries written into the layer stack, at (layer, slot); a recurrent
state is replaced whole. Attention reads its layer of the stack: on a
TPU through the Pallas kernels of :mod:`repro.kernels.decode_attention`,
checked here in interpret mode against the jnp form.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.kernels.decode_attention import (kv_decode_attention,
                                            latent_decode_attention,
                                            time_block)
from repro.models import build_model
from repro.models.attention import (KVCache, LatentCache, NEG_INF,
                                    decode_attend, latent_decode_attend)
from repro.models.blocks import apply_block, pim_proj
from repro.models.layers import rms_norm, softcap
from repro.models.transformer import stack_plan

WINDOW = 8
CACHE = 32


def _cfg(kind: str):
    """Smoke widths, a few stacked layers, by the layer kind exercised."""
    if kind == "g":
        return get_config("deepseek-7b", smoke=True).scaled(n_layers=3)
    if kind == "l":          # ring-windowed 'l' beside global 'g', stacked
        return get_config("gemma2-9b", smoke=True).scaled(n_layers=4,
                                                           window=WINDOW)
    if kind == "mla":        # a 'd' prefix, stacked 'm', held experts
        cfg = get_config("deepseek-v2-lite", smoke=True)
        moe = dataclasses.replace(cfg.moe, experts_held=2, expert_offset=1)
        return cfg.scaled(n_layers=4, layer_pattern="dmmm", moe=moe)
    if kind == "rglru":      # recurrent 'r' and a windowed 'l', stacked
        return get_config("recurrentgemma-9b", smoke=True).scaled(
            n_layers=6, window=WINDOW)
    if kind == "rwkv":
        return get_config("rwkv6-7b", smoke=True).scaled(n_layers=3)
    raise ValueError(kind)


def _parent_decode_step(cfg, params, token, position, states):
    """The decode step as the layer scan used to run it: each layer's
    state read out of the stack whole, updated, and written back whole."""
    x = params["embed"][token] * (cfg.d_model ** 0.5 if cfg.family != "rwkv"
                                  else 1.0)
    prefix, unit, n_units, suffix = stack_plan(cfg)
    new = dict(states, prefix=[], suffix=[])
    for i, kind in enumerate(prefix):
        x, ns = apply_block(cfg, kind, params["prefix"][i], x, pos=position,
                            state=states["prefix"][i], mode="decode")
        new["prefix"].append(ns)
    if n_units:
        def step(carry, xs):
            h, stacks = carry
            blks, li = xs
            outs = []
            for j, kind in enumerate(unit):
                st = jax.tree.map(lambda s: jax.lax.dynamic_index_in_dim(
                    s, li, 0, keepdims=False), stacks[j])
                h, ns = apply_block(cfg, kind, blks[j], h, pos=position,
                                    state=st, mode="decode")
                outs.append(ns)
            stacks = [jax.tree.map(
                lambda s, n: jax.lax.dynamic_update_index_in_dim(
                    s, n.astype(s.dtype), li, 0), stacks[j], outs[j])
                for j in range(len(unit))]
            return (h, stacks), None

        (x, new["scan"]), _ = jax.lax.scan(
            step, (x, states["scan"]), (params["scan"], jnp.arange(n_units)))
    for i, kind in enumerate(suffix):
        x, ns = apply_block(cfg, kind, params["suffix"][i], x, pos=position,
                            state=states["suffix"][i], mode="decode")
        new["suffix"].append(ns)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", params["embed"].T)
    return (softcap(pim_proj(cfg, x, head, scope="head"), cfg.softcap_final),
            new)


@pytest.mark.parametrize("kind", ["g", "l", "mla", "rglru", "rwkv"])
def test_decode_step_equals_the_parent_formulation(kind):
    """Over twice the window in steps (the 'l' rings wrap), the step that
    writes one position gives the same logits, tokens and states, bit for
    bit at float32, as reading each layer's state out whole and writing
    it back."""
    cfg = _cfg(kind)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(7))
    b = 2
    new_step = jax.jit(m.decode_step)
    old_step = jax.jit(lambda p, t, pos, s: _parent_decode_step(
        cfg, p, t, pos, s))
    states = old = m.init_decode_state(b, CACHE)
    tok = jnp.asarray(np.random.default_rng(8).integers(
        3, cfg.vocab_size, (b, 1)), jnp.int32)
    for t in range(2 * WINDOW + 3):
        pos = jnp.full((b, 1), t, jnp.int32)
        logits, states = new_step(params, tok, pos, states)
        want, old = old_step(params, tok, pos, old)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        assert np.array_equal(np.asarray(tok), np.asarray(
            jnp.argmax(want[:, -1:], -1)))
    for a, w in zip(jax.tree.leaves(states), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _trace(cfg, b=2):
    m = build_model(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    states = jax.eval_shape(lambda: m.init_decode_state(b, CACHE))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    return jax.make_jaxpr(m.decode_step)(params, tok, tok, states), states


@pytest.mark.parametrize("kind", ["l", "mla"])
def test_stacked_caches_take_one_position_a_step(kind):
    """Every dynamic_update_slice into a stacked K/V or latent cache
    writes one layer's entries at one position (the update differs from
    the stack in the layer and time axes only, with size 1 there); none
    writes a whole layer. Dense with a ring-windowed layer, and MLA with
    the rope keys kept positions-last."""
    cfg = _cfg(kind)
    jaxpr, states = _trace(cfg)
    stacks = [tuple(a.shape) for a in jax.tree.leaves(states["scan"])
              if a.ndim >= 3]
    assert stacks
    writes = [(tuple(e.invars[0].aval.shape), tuple(e.invars[1].aval.shape))
              for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "dynamic_update_slice"]
    into = [(s, u) for s, u in writes if s in stacks]
    assert len(into) == len(stacks)          # one write per cache buffer
    for s, u in into:
        assert u != (1,) + s[1:], (s, u)      # not a whole layer
        differ = [i for i, (a, c) in enumerate(zip(s, u)) if a != c]
        assert differ[0] == 0 and len(differ) == 2, (s, u)
        assert all(u[i] == 1 for i in differ), (s, u)


@pytest.mark.parametrize("kind,position,whole", [
    ("g", 3, 0), ("l", 4, 0), ("mla", 4, 0), ("rglru", 2, 4), ("rwkv", 0, 3),
])
def test_write_counters(kind, position, whole):
    """Tracing the decode step counts the layer states written by
    position (K/V and latent caches) and whole (recurrent states)."""
    pos = obs.counter(obs.POSITION_WRITES)
    whl = obs.counter(obs.WHOLE_WRITES)
    p0, w0 = pos.value, whl.value
    _trace(_cfg(kind))
    assert (pos.value - p0, whl.value - w0) == (position, whole)


def test_the_benchmark_configs_write_no_state_whole():
    """The decode cells' configurations at their cut depths: every layer
    state is written by position (deepseek-7b 6 layers, deepseek-v2-lite
    9), none whole."""
    pos = obs.counter(obs.POSITION_WRITES)
    whl = obs.counter(obs.WHOLE_WRITES)
    for arch, layers in (("deepseek-7b", 6), ("deepseek-v2-lite", 9)):
        cfg = get_config(arch, smoke=True)
        cfg = cfg.scaled(n_layers=layers,
                         layer_pattern=get_config(arch).layer_pattern)
        p0, w0 = pos.value, whl.value
        _trace(cfg)
        assert (pos.value - p0, whl.value - w0) == (layers, 0), arch


# ---------------------------------------------------------------- kernels --
def _kv_case(seed, layers, b, t, hkv, groups, d):
    ks = jax.random.split(jax.random.key(seed), 5)
    k = jax.random.normal(ks[0], (layers, b, t, hkv, d))
    v = jax.random.normal(ks[1], (layers, b, t, hkv, d))
    q = jax.random.normal(ks[2], (b, 1, hkv * groups, d))
    kn = jax.random.normal(ks[3], (b, 1, hkv, d))
    vn = jax.random.normal(ks[4], (b, 1, hkv, d))
    return k, v, q, kn, vn


@pytest.mark.parametrize("groups,t,length,window,cap", [
    (1, 2048, 700, None, None),         # filling, 4 blocks
    (2, 1024, 3000, None, None),        # ring wrapped, GQA, 2 blocks
    (2, 1024, 2500, 600, None),         # windowed ring across blocks
    (1, 32, 5, None, 50.0),             # one block, softcap
    (1, 1031, 1500, None, None),        # prime ring: a ragged last block
    (2, 1031, 1020, 700, None),         # ragged, the window across it
])
def test_kv_kernel_matches_the_jnp_form(groups, t, length, window, cap):
    """The K/V kernel (interpret mode) over layer 1 of a 3-layer stack,
    after the token is written, equals ``decode_attend``'s jnp form to
    float32 rounding (1e-5 at outputs of about 1). Heads of 128, so
    ``time_block`` splits the longer rings into blocks of 512; a prime
    ring's last block runs past its end, where the interpreter reads
    NaN."""
    layers, b, hkv, d = 3, 2, 4, 128
    assert time_block(t, 2 * hkv * d * 4) == min(t, 512)
    k, v, q, kn, vn = _kv_case(1, layers, b, t, hkv, groups, d)
    lens = jnp.full((layers,), length, jnp.int32)
    want, cache = decode_attend(q, KVCache(k, v, lens), kn, vn,
                                layer=jnp.int32(1), window=window, cap=cap)
    n_valid = min(length + 1, t, window or t)
    qg = (q[:, 0] * d ** -0.5).reshape(b, hkv, groups, d).transpose(0, 2, 1, 3)
    got = kv_decode_attention(qg, cache.k, cache.v, 1, length % t, n_valid,
                              cap=cap, interpret=True)
    got = got.transpose(0, 2, 1, 3).reshape(b, 1, hkv * groups, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,length,block", [
    (2048, 300, 512),      # filling, 4 blocks of 512
    (2048, 5000, 512),     # ring wrapped
    (64, 10, 64),          # one block of the whole ring
    (1031, 1100, 896),     # prime ring: the last block runs past its end
])
def test_latent_kernel_matches_the_jnp_form(t, length, block):
    """The latent kernel (interpret mode) over layer 1 of a 2-layer
    stack at deepseek-v2-lite's latent widths (rank 512, rope keys of 64
    kept positions-last) equals ``latent_decode_attend``'s jnp form
    before ``W_UV`` to float32 rounding (1e-5)."""
    layers, b, h, r, dr, dn = 2, 2, 4, 512, 64, 16
    assert time_block(t, (r + dr) * 4, align=128) == block
    ks = jax.random.split(jax.random.key(2), 7)
    c = jax.random.normal(ks[0], (layers, b, t, r))
    kpe = jax.random.normal(ks[1], (layers, b, dr, t))
    q_nope = jax.random.normal(ks[2], (b, 1, h, dn))
    q_pe = jax.random.normal(ks[3], (b, 1, h, dr))
    w_uk = jax.random.normal(ks[4], (r, h, dn)) * r ** -0.5
    c_new = jax.random.normal(ks[5], (b, 1, r))
    kpe_new = jax.random.normal(ks[6], (b, 1, dr))
    eye = jnp.broadcast_to(jnp.eye(r)[:, None], (r, h, r))  # W_UV = 1
    lens = jnp.full((layers,), length, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, cache = latent_decode_attend(
            q_nope, q_pe, LatentCache(c, kpe, lens), c_new, kpe_new, w_uk,
            eye, scale=0.05, layer=jnp.int32(1))
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
        got = latent_decode_attention(
            q_lat, q_pe[:, 0], cache.c, cache.kpe, 1, length % t,
            min(length + 1, t), scale=0.05,
            precision=jax.lax.Precision.HIGHEST, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               rtol=1e-5, atol=1e-5)


def test_kernels_leave_masked_slots_out():
    """Slots the ring has not written yet carry no weight: filling them
    with huge values changes nothing, in a block of their own too."""
    layers, b, t, hkv, d = 2, 1, 1024, 4, 128
    k, v, q, kn, vn = _kv_case(3, layers, b, t, hkv, 1, d)
    qg = (q[:, 0] * d ** -0.5).reshape(b, hkv, 1, d).transpose(0, 2, 1, 3)
    run = lambda k, v: kv_decode_attention(qg, k, v, 0, 4, 5,
                                           interpret=True)
    far = k.at[:, :, 5:].set(1e4), v.at[:, :, 5:].set(-NEG_INF / 4)
    np.testing.assert_array_equal(np.asarray(run(k, v)),
                                  np.asarray(run(*far)))
