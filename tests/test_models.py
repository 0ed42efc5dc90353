"""Per-arch smoke tests + decode/prefill consistency (reduced configs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build_model, stack_plan
from repro.models.transformer import encode

pytestmark = pytest.mark.models


def _batch(cfg, b=2, s=16):
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (b, s))),
             "labels": jnp.asarray(rng.integers(3, cfg.vocab_size, (b, s)))}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.enc_frames, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_loss(arch):
    """Reduced config: one forward + loss, shape and finiteness checks."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["extra_embed"] = batch["patches"]
    if cfg.family == "encdec":
        kwargs["enc_frames"] = batch["frames"]
    logits, _ = m.forward(params, batch["tokens"], **kwargs)
    exp_s = batch["tokens"].shape[1] + (cfg.n_patches
                                        if cfg.family == "vlm" else 0)
    assert logits.shape == (2, exp_s, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    loss = m.loss(params, batch)
    assert jnp.isfinite(loss) and float(loss) > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_one_train_step(arch):
    """One gradient step on CPU: grads finite, params move."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    batch = _batch(cfg, b=2, s=8)
    loss, grads = jax.value_and_grad(m.loss)(params, batch)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(loss) and jnp.isfinite(gn) and float(gn) > 0


@pytest.mark.parametrize("arch", ["gemma2-9b", "qwen3-8b",
                                  "recurrentgemma-9b", "rwkv6-7b",
                                  "deepseek-moe-16b"])
def test_prefill_decode_consistency(arch):
    """Token-by-token decode reproduces the full-sequence forward —
    exercises KV ring buffers, RoPE offsets, recurrent state handoff."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    b, s = 1, 12
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, (b, s)))
    full_logits, _ = m.forward(params, toks)

    states = m.init_decode_state(b, 32)
    pos = jnp.zeros((b, 1), jnp.int32)
    for t in range(s):
        logits, states = m.decode_step(params, toks[:, t:t + 1],
                                       pos + t, states)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "deepseek-v2-lite"])
def test_moe_prefill_hands_off_the_cache(arch):
    """A prefill with states leaves the prompt's cache in every layer,
    the MoE layers' too: decoding after it gives the full forward's
    logits (float32 rounding, as above)."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(5))
    b, s, p = 2, 10, 6
    toks = jnp.asarray(np.random.default_rng(6).integers(
        3, cfg.vocab_size, (b, s)))
    full_logits, _ = m.forward(params, toks)
    _, states = m.forward(params, toks[:, :p],
                          states=m.init_decode_state(b, 16))
    assert all(float(jnp.abs(a).sum()) > 0             # every layer's
               for a in jax.tree.leaves(states))
    for t in range(p, s):
        logits, states = m.decode_step(
            params, toks[:, t:t + 1], jnp.full((b, 1), t, jnp.int32), states)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-3, atol=2e-3)


def test_windowed_cache_ring_buffer():
    """Decode beyond the window: ring buffer wraps and matches a full
    forward restricted to the window."""
    cfg = get_config("gemma2-9b", smoke=True)   # window=32 in smoke
    cfg = cfg.scaled(window=8)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(4))
    b, s = 1, 20
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, (b, s)))
    full_logits, _ = m.forward(params, toks)
    states = m.init_decode_state(b, 64)   # local layers clamp to window=8
    pos = jnp.zeros((b, 1), jnp.int32)
    for t in range(s):
        logits, states = m.decode_step(params, toks[:, t:t + 1],
                                       pos + t, states)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_whisper_cross_attention_path():
    cfg = get_config("whisper-small", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(6))
    frames = jnp.asarray(np.random.default_rng(7).standard_normal(
        (1, cfg.enc_frames, cfg.d_model)), jnp.float32)
    toks = jnp.asarray([[5, 6, 7, 8]])
    with_enc, _ = m.forward(params, toks, enc_frames=frames)
    without, _ = m.forward(params, toks, enc_frames=frames * 0)
    assert float(jnp.max(jnp.abs(with_enc - without))) > 1e-6

    # decode path consumes the precomputed encoder output
    states = m.init_decode_state(1, 16)
    states["enc_out"] = encode(cfg, params, frames)
    logits, _ = m.decode_step(params, toks[:, :1],
                              jnp.zeros((1, 1), jnp.int32), states)
    assert jnp.isfinite(logits).all()


def test_stack_plan_structures():
    assert stack_plan(get_config("gemma2-9b")) == ((), ("l", "g"), 21, ())
    assert stack_plan(get_config("recurrentgemma-9b")) == \
        ((), ("r", "r", "l"), 12, ("r", "r"))
    assert stack_plan(get_config("deepseek-moe-16b")) == \
        (("d",), ("m",), 27, ())


def test_moe_routing_mass_conservation():
    """Top-k gates sum to 1 per token; capacity drops only excess."""
    from repro.models.blocks import moe_ffn, init_moe_block
    from repro.models.layers import Initializer
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    ini = Initializer(jax.random.PRNGKey(0))
    p = init_moe_block(cfg, ini)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (4, 16, cfg.d_model)), jnp.float32)
    y = moe_ffn(cfg, p, x)
    assert y.shape == x.shape and jnp.isfinite(y).all()
    assert float(jnp.linalg.norm(y)) > 0
