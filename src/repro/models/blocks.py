"""Transformer-family blocks: init + apply for each layer kind.

Every block is a pair of pure functions:

* ``init_<kind>(cfg, ini) -> params`` (dict pytree)
* ``apply_<kind>(cfg, params, x, *, pos, state, enc_out, mode)
  -> (y, new_state)``

``mode`` is ``"full"`` (training / prefill over a whole sequence) or
``"decode"`` (one token, stateful). ``state`` is kind-specific:

* attention ('g'/'l'): :class:`repro.models.attention.KVCache`
  (+ a cross-attention KV pair for enc-dec decoders)
* latent attention ('m'/'d' of a config with ``cfg.mla``):
  :class:`repro.models.attention.LatentCache`, the normed latent and
  the shared rope key per position instead of per-head K/V (the rope
  keys with positions along their last axis)
* RG-LRU ('r', hybrid): {"h": (B, D), "conv": (B, 3, D)}
* RWKV-6 ('r', rwkv): {"wkv": (B, H, dh, dh), "tshift"/"cshift": (B, D)}
* MoE ('m'/'d'): same as attention (the FFN is stateless).

MoE dispatch is dropless sort->grouped-GEMM->gather (ragged per-expert
segments via ``jax.lax.ragged_dot``; the expert weight stacks shard over
the 'model' axis as (E, D, F)). Dropless keeps the layer
token-independent, so prefill and decode agree bit-for-bit. A layer told
its share (``MoEConfig.experts_held``/``expert_offset``) routes over
every expert and computes the picks that land in the experts it holds.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs, pim
from repro.configs.base import ModelConfig

from .attention import (POSITIONS_LAST, KVCache, LatentCache, attend,
                        decode_attend, latent_decode_attend)
from .layers import Initializer, rms_norm, rope, yarn_inv_freq, yarn_mscale

__all__ = ["init_block", "apply_block", "init_state", "pim_proj",
           "pim_weights", "pim_stacks", "writes_by_position"]


# ------------------------------------------------------ PIM offload ----
def pim_proj(cfg: ModelConfig, x: jnp.ndarray, w: jnp.ndarray, *,
             scope: str) -> jnp.ndarray:
    """One linear of ``scope`` (``"head"``, ``"attn"`` for the q/k/v/o
    projections, ``"ffn"`` for the FFN's): a PIM linear at
    ``cfg.pim_linear_bits`` (:func:`repro.pim.linear`) when the scope is
    in ``cfg.pim_scopes()``, else ``x @ w``."""
    if scope not in cfg.pim_scopes():
        return x @ w
    return pim.linear(x, w, cfg.pim_linear_bits)


_MLP = ("w1", "w2", "w3")


def pim_weights(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    """The weights a block of ``kind`` passes to :func:`pim_proj`, by
    scope, as key paths into its params (a path may be absent, e.g.
    ``w3`` of a gelu MLP). :func:`repro.models.transformer.plan_weights`
    quantizes these once, per column, and the MoE expert stacks that the
    block passes to ``_pim_ragged`` (:func:`pim_stacks`) once, with one
    scale a stack. Keep both beside the ``apply_*`` functions' calls:
    ``tests/test_weight_plan.py`` traces every architecture's decode
    step on the plan and fails on a float weight or stack in ``pim``
    mode."""
    def mlp(key):
        return tuple((key, n) for n in _MLP)
    attn = ("wq", "wk", "wv", "wo", "xq", "xk", "xv", "xo")
    if cfg.mla is not None and kind in ("m", "d"):
        attn = ("wq", "wkv_a", "wo")      # wkv_b is absorbed, digital
    if kind in ("g", "l", "d"):
        return {"attn": tuple((n,) for n in attn), "ffn": mlp("mlp")}
    if kind == "m":
        return {"attn": tuple((n,) for n in attn), "ffn": mlp("shared")}
    if kind == "r" and cfg.family != "rwkv":
        return {"ffn": mlp("mlp")}
    return {}


def pim_stacks(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    """The expert stacks a block of ``kind`` passes to ``_pim_ragged``,
    by scope, as key paths into its params (:func:`pim_weights`)."""
    if kind == "m":
        return {"ffn": (("we1",), ("we3",), ("we2",))}
    return {}


def _pim_ragged(cfg: ModelConfig, xs: jnp.ndarray, we,
                counts: jnp.ndarray) -> jnp.ndarray:
    """MoE per-expert grouped GEMM: a PIM one (:func:`repro.pim.
    ragged_linear`, on a planned or a float stack) under the ``"ffn"``
    scope, else ``ragged_dot``."""
    if "ffn" not in cfg.pim_scopes():
        return jax.lax.ragged_dot(xs, we, counts)
    return pim.ragged_linear(xs, we, counts, cfg.pim_linear_bits)


# ============================================================ attention ====
def _init_attn_core(cfg: ModelConfig, ini: Initializer,
                    kind: str = "g") -> Dict[str, Any]:
    d = cfg.d_model
    if cfg.mla is not None and kind in ("m", "d"):
        return _init_mla(cfg, ini)
    p = {
        "wq": ini(d, cfg.q_dim, scale=d ** -0.5),
        "wk": ini(d, cfg.kv_dim, scale=d ** -0.5),
        "wv": ini(d, cfg.kv_dim, scale=d ** -0.5),
        "wo": ini(cfg.q_dim, d, scale=(cfg.q_dim * 2 * cfg.n_layers) ** -0.5),
    }
    if cfg.qk_norm:
        p["qn"] = ini.zeros(cfg.hd)
        p["kn"] = ini.zeros(cfg.hd)
    return p


def _init_mlp(cfg: ModelConfig, ini: Initializer, d_ff: int) -> Dict[str, Any]:
    d = cfg.d_model
    p = {"w1": ini(d, d_ff, scale=d ** -0.5),
         "w2": ini(d_ff, d, scale=(d_ff * 2 * cfg.n_layers) ** -0.5)}
    if cfg.mlp_type == "swiglu":
        p["w3"] = ini(d, d_ff, scale=d ** -0.5)
    return p


def _apply_mlp(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray):
    # Same math as layers.swiglu/gelu_mlp, with each projection routed
    # through the PIM hook (plain matmul when the scope is off).
    h1 = pim_proj(cfg, x, p["w1"], scope="ffn")
    if "w3" in p:
        gated = jax.nn.silu(h1) * pim_proj(cfg, x, p["w3"], scope="ffn")
        return pim_proj(cfg, gated, p["w2"], scope="ffn")
    return pim_proj(cfg, jax.nn.gelu(h1), p["w2"], scope="ffn")


def init_attn_block(cfg: ModelConfig, ini: Initializer, kind: str,
                    d_ff: Optional[int] = None) -> Dict[str, Any]:
    p = {"ln1": ini.zeros(cfg.d_model), "ln2": ini.zeros(cfg.d_model)}
    p.update(_init_attn_core(cfg, ini, kind))
    p["mlp"] = _init_mlp(cfg, ini, d_ff or cfg.d_ff)
    if cfg.family == "encdec":
        d = cfg.d_model
        p["lnx"] = ini.zeros(d)
        p["xq"] = ini(d, cfg.q_dim, scale=d ** -0.5)
        p["xk"] = ini(d, cfg.kv_dim, scale=d ** -0.5)
        p["xv"] = ini(d, cfg.kv_dim, scale=d ** -0.5)
        p["xo"] = ini(cfg.q_dim, d, scale=(cfg.q_dim * 2 * cfg.n_layers) ** -0.5)
    return p


def _qkv(cfg: ModelConfig, p, xn, pos):
    b, s, _ = xn.shape
    q = pim_proj(cfg, xn, p["wq"], scope="attn").reshape(
        b, s, cfg.n_heads, cfg.hd)
    k = pim_proj(cfg, xn, p["wk"], scope="attn").reshape(
        b, s, cfg.n_kv_heads, cfg.hd)
    v = pim_proj(cfg, xn, p["wv"], scope="attn").reshape(
        b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def _prefill_cache(state, **entries):
    """The block's decode state with the prompt's entries (``k``/``v``,
    or the latent ``c``/``kpe``; (B, S, ...) each) left behind, each in
    its buffer's layout (:data:`~.attention.POSITIONS_LAST`)."""
    s = next(iter(entries.values())).shape[1]
    with obs.scope(obs.KV_CACHE):
        cache = {}
        for name, a in entries.items():
            buf = state["self"][name]
            last = name in POSITIONS_LAST
            t = buf.shape[-1 if last else 1]
            if s < t:
                a = jnp.pad(a, ((0, 0), (0, t - s)) + ((0, 0),) * (a.ndim - 2))
            elif s > t:        # windowed: keep the most recent slice,
                # rotated so token j sits at ring slot j % t.
                a = jnp.roll(a[:, -t:], s % t, axis=1)
            if last:
                a = jnp.moveaxis(a, 1, -1)
            cache[name] = a.astype(buf.dtype)
        cache["length"] = jnp.asarray(s, jnp.int32)
        new_state = dict(state)
        new_state["self"] = cache
    return new_state


# ------------------------------------------------- latent attention (MLA) --
def _init_mla(cfg: ModelConfig, ini: Initializer) -> Dict[str, Any]:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    if a.q_lora_rank is not None:
        raise NotImplementedError("MLA with a compressed query "
                                  "(q_lora_rank) is not implemented")
    r = a.kv_lora_rank
    o_in = h * a.v_head_dim
    return {
        "wq": ini(d, h * a.qk_head_dim, scale=d ** -0.5),
        "wkv_a": ini(d, r + a.qk_rope_head_dim, scale=d ** -0.5),
        "kv_norm": ini.zeros(r),
        "wkv_b": ini(r, h * (a.qk_nope_head_dim + a.v_head_dim),
                     scale=r ** -0.5),
        "wo": ini(o_in, d, scale=(o_in * 2 * cfg.n_layers) ** -0.5),
    }


def mla_rope(cfg: ModelConfig):
    """-> (rope frequencies or None, rope output factor, softmax scale)
    of latent attention; with YaRN, HF's ``DeepseekV2Attention``: the
    softmax scale is ``qk_head_dim^-1/2 * m^2`` with ``m`` the magnitude
    at ``mscale_all_dim``, and cos/sin carry ``m(mscale) / m(all)``."""
    a, rs = cfg.mla, cfg.rope_scaling
    scale = a.qk_head_dim ** -0.5
    if rs is None:
        return None, 1.0, scale
    inv = yarn_inv_freq(a.qk_rope_head_dim, cfg.rope_theta, rs.factor,
                        rs.original_max_position, rs.beta_fast,
                        rs.beta_slow)
    m_all = yarn_mscale(rs.factor, rs.mscale_all_dim)
    return inv, yarn_mscale(rs.factor, rs.mscale) / m_all, scale * m_all ** 2


def _mla_attention(cfg: ModelConfig, p, xn, pos, state, mode, layer=None):
    """Latent attention of the normed ``xn`` (B, S, D) -> (o (B, S,
    H * v_head_dim), new_state). The prefill decompresses per-head keys
    and values from the latent; decode attends over the cached latents
    in the absorbed form (:func:`~.attention.latent_decode_attend`)."""
    a, h = cfg.mla, cfg.n_heads
    dn, dr, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    b, s, _ = xn.shape
    inv_freq, m, scale = mla_rope(cfg)

    def roped(x):
        y = rope(x, pos, cfg.rope_theta, inv_freq)
        return y if m == 1.0 else y * m

    q = pim_proj(cfg, xn, p["wq"], scope="attn").reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], roped(q[..., dn:])
    ckv = pim_proj(cfg, xn, p["wkv_a"], scope="attn")
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = roped(ckv[..., None, r:])                       # (B, S, 1, dr)
    wkv_b = p["wkv_b"].reshape(r, h, dn + a.v_head_dim)
    new_state = state
    if mode == "decode":
        o, cache = latent_decode_attend(
            q_nope, q_pe, LatentCache(**state["self"]), c, k_pe[:, :, 0],
            wkv_b[..., :dn], wkv_b[..., dn:], scale=scale, layer=layer)
        new_state = dict(state)
        new_state["self"] = cache._asdict()
    else:
        kv = jnp.einsum("bsr,rhd->bshd", c, wkv_b)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
        o = attend(jnp.concatenate([q_nope, q_pe], axis=-1), k,
                   kv[..., dn:], causal=True, cap=cfg.softcap_attn,
                   scale=scale)
        if state is not None:         # prefill: leave the latents behind
            new_state = _prefill_cache(state, c=c, kpe=k_pe[:, :, 0])
    return o.reshape(b, s, h * a.v_head_dim), new_state


def _self_attention(cfg: ModelConfig, p, x, *, pos, state, mode,
                    window=None, kind="g", layer=None):
    """``x`` plus the block's self-attention sublayer -> (x, new_state).
    With ``layer``, ``state`` is a layer stack's decode state and this
    block is its layer ``layer``: decode writes the token's entries into
    the stack and attends over that layer of it."""
    b, s, _ = x.shape
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None and kind in ("m", "d"):
        o, new_state = _mla_attention(cfg, p, xn, pos, state, mode, layer)
    else:
        q, k, v = _qkv(cfg, p, xn, pos)
        new_state = state
        if mode in ("full", "encode"):
            o = attend(q, k, v, causal=(mode != "encode"), window=window,
                       cap=cfg.softcap_attn)
            if state is not None:     # prefill: leave the KV behind
                new_state = _prefill_cache(state, k=k, v=v)
        else:
            o, cache = decode_attend(q, KVCache(**state["self"]), k, v,
                                     layer=layer, window=window,
                                     cap=cfg.softcap_attn)
            new_state = dict(state)
            new_state["self"] = cache._asdict()
        o = o.reshape(b, s, cfg.q_dim)
    return x + pim_proj(cfg, o, p["wo"], scope="attn"), new_state


def apply_attn_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode,
                     kind: str, layer=None):
    b, s, d = x.shape
    window = cfg.window if kind == "l" else None
    with obs.scope(obs.ATTENTION):
        x, new_state = _self_attention(cfg, p, x, pos=pos, state=state,
                                       mode=mode, window=window, kind=kind,
                                       layer=layer)

        if cfg.family == "encdec" and enc_out is not None:
            xn2 = rms_norm(x, p["lnx"], cfg.norm_eps)
            qx = pim_proj(cfg, xn2, p["xq"], scope="attn").reshape(
                b, s, cfg.n_heads, cfg.hd)
            kx = pim_proj(cfg, enc_out, p["xk"], scope="attn").reshape(
                b, enc_out.shape[1], cfg.n_kv_heads, cfg.hd)
            vx = pim_proj(cfg, enc_out, p["xv"], scope="attn").reshape(
                b, enc_out.shape[1], cfg.n_kv_heads, cfg.hd)
            ox = attend(qx, kx, vx, causal=False)
            x = x + pim_proj(cfg, ox.reshape(b, s, cfg.q_dim), p["xo"],
                             scope="attn")

    xn3 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _apply_mlp(cfg, p["mlp"], xn3)
    return x, new_state


# ================================================================= MoE ====
def init_moe_block(cfg: ModelConfig, ini: Initializer) -> Dict[str, Any]:
    e = cfg.moe
    d, f = cfg.d_model, cfg.d_ff
    p = {"ln1": ini.zeros(d), "ln2": ini.zeros(d)}
    p.update(_init_attn_core(cfg, ini, "m"))
    p["router"] = ini(d, e.n_experts, scale=d ** -0.5)   # every expert
    p["we1"] = ini(e.held, d, f, scale=d ** -0.5)         # the held ones
    p["we3"] = ini(e.held, d, f, scale=d ** -0.5)
    p["we2"] = ini(e.held, f, d, scale=(f * 2 * cfg.n_layers) ** -0.5)
    if e.n_shared:
        p["shared"] = _init_mlp(cfg, ini, f * e.n_shared)
    return p


MOE_CHUNK = 32768   # PERF(H3): cap tokens per dispatch so the (E, C, D)
# capacity buffers stay bounded for 1M-token prefills.


def moe_ffn(cfg: ModelConfig, p, x3: jnp.ndarray) -> jnp.ndarray:
    """Dropless top-k expert FFN over (B, S, D); long sequences are
    dispatched in chunks *along S* — the batch axis keeps its data
    sharding in every chunk, so all devices stay active and the sorted
    (T*k, D) dispatch activations stay O(chunk)
    (PERF(H3): 1M-token MoE prefills)."""
    b, s, d = x3.shape
    sc = max(1, MOE_CHUNK // max(1, b))
    if s > sc and s % sc == 0:
        xs = x3.reshape(b, s // sc, sc, d).swapaxes(0, 1)   # (nc,B,sc,D)
        ys = jax.lax.map(
            lambda xc: _moe_ffn_chunk(cfg, p, xc.reshape(b * sc, d)
                                      ).reshape(b, sc, d), xs)
        return ys.swapaxes(0, 1).reshape(b, s, d)
    return _moe_ffn_chunk(cfg, p, x3.reshape(b * s, d)).reshape(b, s, d)


def route(cfg: ModelConfig, p, x2: jnp.ndarray):
    """(T, D) -> gates (T, k) and expert ids (T, k) over every routed
    expert, by ``cfg.moe.scoring``: ``"softmax"`` takes the top-k of the
    softmax over all experts as the gates (DeepSeek's, unrenormalized);
    ``"topk_softmax"`` softmaxes the top-k logits (gates sum to 1)."""
    e = cfg.moe
    logits = x2 @ p["router"]
    if e.scoring == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate, idx = jax.lax.top_k(probs, e.top_k)
    elif e.scoring == "topk_softmax":
        gate, idx = jax.lax.top_k(logits, e.top_k)
        gate = jax.nn.softmax(gate.astype(jnp.float32), axis=-1)
    else:
        raise ValueError(f"unknown MoE scoring {e.scoring!r}")
    return gate.astype(x2.dtype), idx


def _moe_ffn_chunk(cfg: ModelConfig, p, x2: jnp.ndarray) -> jnp.ndarray:
    """Dropless dispatch: sort token-expert pairs by expert, then grouped
    GEMMs over the ragged per-expert segments (``jax.lax.ragged_dot``).

    Dropless matters for correctness, not just quality: a capacity
    bound makes a token's output depend on the *other* tokens in the
    dispatch (whoever overflows the expert loses its contribution), so
    prefill and token-by-token decode disagree. Here every routed pair
    is computed, so the layer is token-independent and prefill ==
    decode exactly. Memory stays O(T*k) activations — same order as the
    old (E, C, D) capacity buffers at capacity factor 1.25.

    The layer holds experts ``[expert_offset, expert_offset + held)``:
    picks of other experts sort last, outside every segment, and are
    zero in the grouped GEMMs' inputs and outputs, so what the absent
    experts would add is left out (and a PIM call's activation scale is
    over the held experts' rows alone).
    """
    e = cfg.moe
    t, d = x2.shape
    held = e.held
    with obs.scope(obs.MOE_ROUTE):
        gate, idx = route(cfg, p, x2)                      # (T, k)
        local = idx.reshape(-1) - e.expert_offset          # (T*k,)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        flat_t = jnp.repeat(jnp.arange(t), e.top_k)
        order = jnp.argsort(key)                           # stable
        st, sg, sm = flat_t[order], gate.reshape(-1)[order], mine[order]
        counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        sm = sm[:, None]
        xs = jnp.where(sm, x2[st], 0)                      # (T*k, d)
    with obs.scope(obs.MOE_EXPERTS):
        h = _pim_ragged(cfg, xs, p["we1"], counts)
        h3 = _pim_ragged(cfg, xs, p["we3"], counts)
        g = jnp.where(sm, jax.nn.silu(h) * h3, 0)
        y = jnp.where(sm, _pim_ragged(cfg, g, p["we2"], counts), 0)
    with obs.scope(obs.MOE_ROUTE):
        out = jnp.zeros_like(x2).at[st].add(y * sg[:, None])
    if e.n_shared:
        out = out + _apply_mlp(cfg, p["shared"], x2)
    return out


def apply_moe_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode,
                    layer=None):
    with obs.scope(obs.ATTENTION):
        x, new_state = _self_attention(cfg, p, x, pos=pos, state=state,
                                       mode=mode, kind="m", layer=layer)
    xn2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + moe_ffn(cfg, p, xn2), new_state


# ============================================================== RG-LRU ====
def init_rglru_block(cfg: ModelConfig, ini: Initializer) -> Dict[str, Any]:
    d = cfg.d_model
    p = {
        "ln1": ini.zeros(d), "ln2": ini.zeros(d),
        "wx": ini(d, d, scale=d ** -0.5),     # recurrence branch in-proj
        "wg": ini(d, d, scale=d ** -0.5),     # gelu gate branch
        "wo": ini(d, d, scale=(d * 2 * cfg.n_layers) ** -0.5),
        "conv": ini(4, d, scale=0.1),         # causal depthwise conv
        "wa": ini(d, d, scale=d ** -0.5),     # recurrence gate r_t
        "wi": ini(d, d, scale=d ** -0.5),     # input gate i_t
        "lam": ini.zeros(d) + 2.0,            # sigmoid(lam)^c decay base
    }
    p["mlp"] = _init_mlp(cfg, ini, cfg.d_ff)
    return p


def _rglru_scan(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray):
    """h_t = a_t * h_{t-1} + b_t over axis 1, associative (parallel)."""
    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2
    a_s, b_s = jax.lax.associative_scan(combine, (a, b), axis=1)
    return a_s * h0[:, None, :] + b_s


def apply_rglru_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode):
    b, s, d = x.shape
    c_exp = 8.0
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    u = xn @ p["wx"]
    g = jax.nn.gelu(xn @ p["wg"])
    if mode == "full":
        conv_in = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
        uc = sum(conv_in[:, i:i + s] * p["conv"][i] for i in range(4))
    else:
        hist = jnp.concatenate([state["conv"], u], axis=1)   # (B, 4, D)
        uc = jnp.sum(hist * p["conv"], axis=1, keepdims=True)
    r = jax.nn.sigmoid(xn @ p["wa"])
    i = jax.nn.sigmoid(xn @ p["wi"])
    log_a = c_exp * r * jax.nn.log_sigmoid(p["lam"])         # < 0
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2 * log_a), 1e-6)) * (i * uc)
    h0 = state["h"] if state is not None else jnp.zeros((b, d), x.dtype)
    new_state = state
    if mode == "full":
        h = _rglru_scan(a, gated, h0)
        if state is not None:
            new_state = {"h": h[:, -1], "conv": conv_in[:, s:s + 3]
                         if s >= 3 else jnp.pad(u, ((0, 0), (3 - s, 0), (0, 0)))}
    else:
        h = (a * h0[:, None] + gated)
        new_state = {"h": h[:, -1],
                     "conv": jnp.concatenate([state["conv"][:, 1:], u], axis=1)}
    y = (h * g) @ p["wo"]
    x = x + y
    xn2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + _apply_mlp(cfg, p["mlp"], xn2), new_state


# ============================================================== RWKV-6 ====
def init_rwkv_block(cfg: ModelConfig, ini: Initializer) -> Dict[str, Any]:
    d = cfg.d_model
    lora = max(32, d // 64)
    p = {
        "ln1": ini.zeros(d), "ln2": ini.zeros(d),
        "mix": ini(5, d, scale=0.5),          # base lerp for r,k,v,w,g
        "wr": ini(d, d, scale=d ** -0.5),
        "wk": ini(d, d, scale=d ** -0.5),
        "wv": ini(d, d, scale=d ** -0.5),
        "wg": ini(d, d, scale=d ** -0.5),
        "wo": ini(d, d, scale=(d * 2 * cfg.n_layers) ** -0.5),
        "w0": ini.zeros(d) - 6.0,             # decay bias (slow decay)
        "wa": ini(d, lora, scale=d ** -0.5),  # data-dependent decay LoRA
        "wb": ini(lora, d, scale=lora ** -0.5),
        "u": ini(d, scale=0.5),               # bonus
        "gn": ini.zeros(d),                   # group-norm scale
        # channel mix
        "cmix": ini(2, d, scale=0.5),
        "ck": ini(d, cfg.d_ff, scale=d ** -0.5),
        "cv": ini(cfg.d_ff, d, scale=cfg.d_ff ** -0.5),
        "cr": ini(d, d, scale=d ** -0.5),
    }
    return p


def _rwkv_time_mix(cfg, p, xn, xprev, state_wkv):
    """xn (B,S,D); xprev (B,S,D) = token-shifted xn; returns (y, last wkv)."""
    b, s, d = xn.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd
    mix = jax.nn.sigmoid(p["mix"])
    def lerp(i):
        return xn * mix[i] + xprev * (1 - mix[i])
    r = (lerp(0) @ p["wr"]).reshape(b, s, nh, hd)
    k = (lerp(1) @ p["wk"]).reshape(b, s, nh, hd)
    v = (lerp(2) @ p["wv"]).reshape(b, s, nh, hd)
    wdd = p["w0"] + jnp.tanh(lerp(3) @ p["wa"]) @ p["wb"]
    w = jnp.exp(-jnp.exp(wdd)).reshape(b, s, nh, hd)      # in (0,1)
    g = jax.nn.silu(lerp(4) @ p["wg"])
    u = p["u"].reshape(nh, hd)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                          # (B, nh, hd)
        kv = jnp.einsum("bhi,bhj->bhij", k_t, v_t)
        y = jnp.einsum("bhi,bhij->bhj", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    S0 = state_wkv
    xs = (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
          v.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3))
    S_last, ys = jax.lax.scan(step, S0, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    y = rms_norm(y, p["gn"], cfg.norm_eps)                # group-norm proxy
    return (y * g) @ p["wo"], S_last


def apply_rwkv_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode):
    b, s, d = x.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd
    if state is None:
        state = init_state(cfg, "r", b, 0, x.dtype)
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    if mode == "full":
        xprev = jnp.concatenate([state["tshift"][:, None], xn[:, :-1]], axis=1)
    else:
        xprev = state["tshift"][:, None]
    y, S_last = _rwkv_time_mix(cfg, p, xn, xprev, state["wkv"])
    x = x + y
    xn2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if mode == "full":
        xprev2 = jnp.concatenate([state["cshift"][:, None], xn2[:, :-1]],
                                 axis=1)
    else:
        xprev2 = state["cshift"][:, None]
    cmix = jax.nn.sigmoid(p["cmix"])
    xk = xn2 * cmix[0] + xprev2 * (1 - cmix[0])
    xr = xn2 * cmix[1] + xprev2 * (1 - cmix[1])
    kk = jnp.square(jax.nn.relu(xk @ p["ck"]))
    y2 = jax.nn.sigmoid(xr @ p["cr"]) * (kk @ p["cv"])
    new_state = {"wkv": S_last, "tshift": xn[:, -1], "cshift": xn2[:, -1]}
    return x + y2, new_state


# ========================================================== dispatch =======
def init_block(cfg: ModelConfig, ini: Initializer, kind: str):
    if kind in ("g", "l"):
        return init_attn_block(cfg, ini, kind)
    if kind == "m":
        return init_moe_block(cfg, ini)
    if kind == "d":
        return init_attn_block(cfg, ini, "d",
                               d_ff=cfg.moe.d_ff_dense or cfg.d_ff)
    if kind == "r":
        return (init_rwkv_block(cfg, ini) if cfg.family == "rwkv"
                else init_rglru_block(cfg, ini))
    raise ValueError(kind)


def apply_block(cfg: ModelConfig, kind: str, p, x, *, pos, state=None,
                enc_out=None, mode="full", layer=None):
    """One block -> (y, new_state). ``layer``: ``state`` is a layer
    stack's decode state (:func:`writes_by_position`), of which this
    block is layer ``layer``; the new state is the stack with this
    layer's token written."""
    if kind in ("g", "l", "d"):
        return apply_attn_block(cfg, p, x, pos=pos, state=state,
                                enc_out=enc_out, mode=mode, kind=kind,
                                layer=layer)
    if kind == "m":
        return apply_moe_block(cfg, p, x, pos=pos, state=state,
                               enc_out=enc_out, mode=mode, layer=layer)
    if kind == "r":
        fn = (apply_rwkv_block if cfg.family == "rwkv"
              else apply_rglru_block)
        return fn(cfg, p, x, pos=pos, state=state, enc_out=enc_out, mode=mode)
    raise ValueError(kind)


def writes_by_position(state) -> bool:
    """Whether a block's decode state has a time axis: a KV or latent
    cache (``{"self": ...}``), which a decode step writes one position
    of. Recurrent states (RG-LRU, RWKV-6) have none and are replaced
    whole."""
    return isinstance(state, dict) and "self" in state


def init_state(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
               dtype=jnp.float32, enc_len: int = 0):
    """Zero decode-state for one block."""
    if cfg.mla is not None and kind in ("m", "d"):
        a = cfg.mla
        return {"self": {
            "c": jnp.zeros((batch, max(cache_len, 1), a.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, a.qk_rope_head_dim, max(cache_len, 1)),
                             dtype),
            "length": jnp.zeros((), jnp.int32)}}
    if kind in ("g", "l", "m", "d"):
        t = cache_len if kind != "l" else min(cfg.window, cache_len)
        t = max(t, 1)
        return {"self": {
            "k": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.hd), dtype),
            "length": jnp.zeros((), jnp.int32)}}
    if cfg.family == "rwkv":
        d = cfg.d_model
        nh = d // cfg.rwkv_head_dim
        return {"wkv": jnp.zeros((batch, nh, cfg.rwkv_head_dim,
                                  cfg.rwkv_head_dim), dtype),
                "tshift": jnp.zeros((batch, d), dtype),
                "cshift": jnp.zeros((batch, d), dtype)}
    return {"h": jnp.zeros((batch, cfg.d_model), dtype),
            "conv": jnp.zeros((batch, 3, cfg.d_model), dtype)}
