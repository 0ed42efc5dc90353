"""DeepSeek-V2-Lite's mechanisms at a small size: the program against the
benchmark's plain reference (``benchmarks/chip/reference/
mla_moe_decoder.py``, loaded by path) on seeded random weights; the
absorbed latent decode against the decompressed one; YaRN; the two
gatings; and the expert layer's shares against the uncut layer."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.attention import LatentCache, latent_decode_attend
from repro.models.blocks import init_moe_block, mla_rope, moe_ffn, route
from repro.models.layers import Initializer

pytestmark = pytest.mark.models

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.append(str(CHIP))

import harness  # noqa: E402

ref = harness.load_module(CHIP / "reference" / "mla_moe_decoder.py",
                          "mla_moe_reference")


def small(held=2, offset=2, **over):
    """The smoke config with a scan of two MoE layers and a share of its
    experts: experts [offset, offset + held) of 4."""
    cfg = get_config("deepseek-v2-lite", smoke=True).scaled(
        n_layers=3, layer_pattern="dmm", **over)
    moe = dataclasses.replace(cfg.moe, experts_held=held,
                              expert_offset=offset)
    return dataclasses.replace(cfg, moe=moe)


def ref_config(cfg):
    """The reference's configuration (published key names) of ``cfg``."""
    a, e, rs = cfg.mla, cfg.moe, cfg.rope_scaling
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": a.kv_lora_rank,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim, "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "factor": rs.factor, "beta_fast": rs.beta_fast,
                "beta_slow": rs.beta_slow, "mscale": rs.mscale,
                "mscale_all_dim": rs.mscale_all_dim,
                "original_max_position_embeddings":
                    rs.original_max_position},
            "rms_norm_eps": cfg.norm_eps, "first_k_dense_replace": 1,
            "num_experts_per_tok": e.top_k, "router_outputs": e.n_experts,
            "n_routed_experts": e.held, "expert_offset": e.expert_offset}


def random_params(model, seed):
    """Normal weights scaled by fan_in^-1/2, and norm weights away from
    zero, so every RMSNorm's weight is exercised."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for k, (path, s) in zip(keys, leaves):
        norm = str(path[-1].key) in ("ln1", "ln2", "kv_norm", "final_norm")
        scale = 0.1 if norm else s.shape[-2] ** -0.5
        out.append(jax.random.normal(k, s.shape) * scale)
    return jax.tree_util.tree_unflatten(tree, out)


def program_logits(model, params, prompts, steps):
    """Prefill with states, then ``steps`` greedy decode steps through
    the latent cache: the logits at the prompt's last position and at
    each step (B, steps + 1, V), and the tokens served (B, steps + 1)."""
    b, plen = prompts.shape
    states = model.init_decode_state(b, plen + steps + 2)
    logits, states = model.forward(params, prompts, states=states)
    out = [logits[:, -1]]
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    served = [tok]
    for i in range(steps):
        pos = jnp.full((b, 1), plen + i, jnp.int32)
        logits, states = model.decode_step(params, tok, pos, states)
        out.append(logits[:, -1])
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        served.append(tok)
    return np.stack(out, 1), np.concatenate(served, 1)


@pytest.mark.parametrize("pim", [False, True], ids=["float", "pim8_ffn"])
def test_program_matches_reference_logits(pim):
    """The prefill's logits at the prompt's last position and each decode
    step's through the latent cache equal the plain reference's, which
    decompresses the latents per head, at the same held share, in float
    and with the FFNs (dense, shared, held experts) and the head under
    MultPIM 8-bit semantics. Tolerance 5e-5: float32 rounding of the two
    orders of summation at logits of magnitude about 1 (the absorbed
    form sums over the latent where the reference sums over heads' keys);
    a rounding that moved an 8-bit activation code would move a logit
    by about 1e-2 and fail."""
    cfg = small()
    if pim:
        cfg = dataclasses.replace(cfg, pim_linear_mode="pim",
                                  pim_linear_bits=8, pim_block_mode="ffn")
    model = build_model(cfg)
    params = random_params(model, 3)
    prompts = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (3, 7)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, served = program_logits(model, params, prompts, 5)
    want = ref.logits(params, ref_config(cfg), prompts, served,
                      pim_scopes=cfg.pim_scopes())
    assert got.shape == want.shape == (3, 6, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-5)
    # the cached decode state is the latent, with no per-head K/V
    states = model.init_decode_state(3, 16)
    assert set(states["prefix"][0]["self"]) == {"c", "kpe", "length"}


def test_pim_reference_at_4_bits_is_far_from_the_program():
    """The comparison above would see a step down in precision: the
    reference with its PIM linears at 4 bits is off by far more than its
    tolerance."""
    cfg = dataclasses.replace(small(), pim_linear_mode="pim",
                              pim_linear_bits=8, pim_block_mode="ffn")
    model = build_model(cfg)
    params = random_params(model, 3)
    prompts = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (3, 7)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, served = program_logits(model, params, prompts, 2)
    low = ref.logits(params, ref_config(cfg), prompts, served,
                     pim_scopes=cfg.pim_scopes(), bits=4)
    assert np.abs(got - low).max() > 1e-2


def test_absorbed_decode_equals_decompressed():
    """One decode step over a filled latent cache: the absorbed form's
    output equals attention over keys and values decompressed per head
    from the same latents (``[W_UK c; k_pe]`` and ``W_UV c``), over the
    valid positions. Tolerance 1e-5: float32 at outputs of about 1."""
    cfg = get_config("deepseek-v2-lite", smoke=True)
    a, h = cfg.mla, cfg.n_heads
    dn, dr, dv, r = (a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim,
                     a.kv_lora_rank)
    b, t, filled = 2, 12, 7
    ks = jax.random.split(jax.random.key(0), 6)
    c = jax.random.normal(ks[0], (b, t, r))
    kpe = jax.random.normal(ks[1], (b, t, dr))
    q_nope = jax.random.normal(ks[2], (b, 1, h, dn))
    q_pe = jax.random.normal(ks[3], (b, 1, h, dr))
    wkv_b = jax.random.normal(ks[4], (r, h, dn + dv)) * r ** -0.5
    c_new, kpe_new = c[:, filled:filled + 1], kpe[:, filled:filled + 1]
    cache = LatentCache(c.at[:, filled:].set(0),
                        kpe.at[:, filled:].set(0).swapaxes(1, 2),
                        jnp.asarray(filled, jnp.int32))
    _, _, scale = mla_rope(cfg)
    with jax.default_matmul_precision("highest"):
        got, new = latent_decode_attend(
            q_nope, q_pe, cache, c_new, kpe_new, wkv_b[..., :dn],
            wkv_b[..., dn:], scale=scale)
        n = filled + 1
        kv = jnp.einsum("btr,rhd->bthd", c[:, :n], wkv_b)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            kpe[:, :n, None], (b, n, h, dr))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)
        p = jax.nn.softmax(jnp.einsum("bshd,bthd->bhst", q, k) * scale, -1)
        want = jnp.einsum("bhst,bthd->bshd", p, kv[..., dn:])
    assert int(new.length) == n
    np.testing.assert_allclose(np.asarray(new.c[:, :n]),
                               np.asarray(c[:, :n]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("i", [0, 10, 23, 31])
def test_yarn_frequencies(i):
    """DeepSeek-V2-Lite's rope frequencies at dims 0, 10, 23 and 31 of
    its 32 pairs: the original below the ramp (low = 10), the
    interpolated (/ 40) above it (high = 23), and the ramp's mix in
    between; the reference computes the same."""
    cfg = get_config("deepseek-v2-lite")
    inv, m, _ = mla_rope(cfg)
    extra = 10000.0 ** (-2 * i / 64)
    low = math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    mask = 1 - min(max((i - low) / (high - low), 0), 1)
    want = extra / 40 * (1 - mask) + extra * mask
    assert float(inv[i]) == pytest.approx(want, rel=1e-6)
    assert m == 1.0
    rcfg = {"qk_rope_head_dim": 64, "rope_theta": 10000,
            "rope_scaling": {"factor": 40, "beta_fast": 32, "beta_slow": 1,
                             "original_max_position_embeddings": 4096}}
    assert float(ref.yarn_inv_freq(rcfg)[i]) == pytest.approx(want,
                                                              rel=1e-6)


def test_yarn_softmax_scale():
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla_rope(cfg)[2] == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)


def _router_input(cfg, seed=0):
    p = init_moe_block(cfg, Initializer(jax.random.key(seed)))
    x = jax.random.normal(jax.random.key(seed + 1), (9, cfg.d_model))
    return p, x


def test_softmax_gating_keeps_unrenormalized_probabilities():
    """DeepSeek's gating (``scoring="softmax"``): the gates are the top-k
    of the softmax over every routed expert, as they are, so they sum to
    less than 1; deepseek-moe-16b and deepseek-v2-lite use it."""
    cfg = get_config("deepseek-v2-lite", smoke=True)
    assert cfg.moe.scoring == "softmax"
    assert get_config("deepseek-moe-16b").moe.scoring == "softmax"
    p, x = _router_input(cfg)
    gate, idx = route(cfg, p, x)
    probs = jax.nn.softmax(x @ p["router"], -1)
    want = jnp.take_along_axis(probs, idx, -1)
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jax.lax.top_k(probs, cfg.moe.top_k)[1]))
    assert float(jnp.max(jnp.sum(gate, -1))) < 1 - 1e-3


def test_topk_softmax_gating_renormalizes():
    """The default (``"topk_softmax"``, phi3.5-moe's): the softmax over
    the top-k logits, so each token's gates sum to 1."""
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    assert cfg.moe.scoring == "topk_softmax"
    p, x = _router_input(cfg)
    gate, idx = route(cfg, p, x)
    top, top_idx = jax.lax.top_k(x @ p["router"], cfg.moe.top_k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(top_idx))
    np.testing.assert_allclose(np.asarray(gate),
                               np.asarray(jax.nn.softmax(top, -1)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(gate, -1)), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """The MoE layer told its share, at offsets 0, E/n, ..., computes the
    held experts' part of the routed result; the shares' parts plus the
    shared experts counted once equal the uncut layer (the reference's
    held part at the whole share agrees too). Tolerance 1e-5: float32
    sums in another order."""
    cfg = get_config("deepseek-v2-lite", smoke=True)
    e = cfg.moe
    per = e.n_experts // shares
    p, _ = _router_input(cfg, 5)
    x = jax.random.normal(jax.random.key(7), (2, 5, cfg.d_model))
    no_shared = dict(p, shared=jax.tree.map(jnp.zeros_like, p["shared"]))
    with jax.default_matmul_precision("highest"):
        whole = moe_ffn(cfg, p, x)
        shared = moe_ffn(cfg, dict(p, we2=jnp.zeros_like(p["we2"])), x)
        parts = []
        for off in range(0, e.n_experts, per):
            part_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                e, experts_held=per, expert_offset=off))
            sl = {k: p[k][off:off + per] for k in ("we1", "we2", "we3")}
            parts.append(moe_ffn(part_cfg, dict(no_shared, **sl), x))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=0, atol=1e-5)
    rcfg = dict(ref_config(cfg), n_routed_experts=e.n_experts,
                expert_offset=0)
    ids, n = ref.call_ids(2, 5, 5, 2)
    want = ref.experts(x, p, rcfg, ids, n, pim=False, bits=8,
                       precision="highest", renormalize=False)
    np.testing.assert_allclose(np.asarray(whole - shared),
                               np.asarray(want), rtol=0, atol=1e-5)
