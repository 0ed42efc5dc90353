"""Transformer-family blocks: init + apply for each layer kind.

Every block is a pair of pure functions:

* ``init_<kind>(cfg, ini) -> params`` (dict pytree)
* ``apply_<kind>(cfg, params, x, *, pos, state, enc_out, mode)
  -> (y, new_state)``

``mode`` is ``"full"`` (training / prefill over a whole sequence) or
``"decode"`` (one token, stateful). ``state`` is kind-specific:

* attention ('g'/'l'): :class:`repro.models.attention.KVCache`
  (+ a cross-attention KV pair for enc-dec decoders)
* RG-LRU ('r', hybrid): {"h": (B, D), "conv": (B, 3, D)}
* RWKV-6 ('r', rwkv): {"wkv": (B, H, dh, dh), "tshift"/"cshift": (B, D)}
* MoE ('m'/'d'): same as attention (the FFN is stateless).

MoE dispatch is dropless sort->grouped-GEMM->gather (ragged per-expert
segments via ``jax.lax.ragged_dot``; the expert weight stacks shard over
the 'model' axis as (E, D, F)). Dropless keeps the layer
token-independent, so prefill and decode agree bit-for-bit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig

from .attention import KVCache, attend, decode_attend
from .layers import Initializer, rms_norm, rope

__all__ = ["init_block", "apply_block", "init_state", "pim_proj",
           "pim_weights"]


# ------------------------------------------------------ PIM offload ----
def pim_proj(cfg: ModelConfig, x: jnp.ndarray, w: jnp.ndarray, *,
             scope: str) -> jnp.ndarray:
    """One block linear, optionally offloaded to the PIM engine.

    ``scope`` is ``"attn"`` (q/k/v/o projections) or ``"ffn"`` (both
    FFN projections); whether it routes through the engine is governed
    by ``cfg.pim_block_mode`` (:meth:`ModelConfig.pim_scopes`). The
    engine path quantizes to ``cfg.pim_linear_bits``, runs the integer
    matmul bit-identical to the in-memory MultPIM-MAC, and compiles the
    co-scheduled MAC group into the process-shared program cache at
    trace time — every projection of every layer reuses the one
    verified schedule (weight-stationary: decode steps never recompile).
    """
    if scope not in cfg.pim_scopes():
        return x @ w
    from repro.engine import get_engine   # lazy: models stay engine-free
    mode = "pim" if cfg.pim_linear_mode == "off" else cfg.pim_linear_mode
    return get_engine().linear(x, w, n_bits=cfg.pim_linear_bits, mode=mode)


_MLP = ("w1", "w2", "w3")


def pim_weights(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    """The weights a block of ``kind`` passes to :func:`pim_proj`, by
    scope, as key paths into its params (a path may be absent, e.g.
    ``w3`` of a gelu MLP). :func:`repro.models.transformer.plan_weights`
    quantizes these once; the MoE expert stacks (``_pim_ragged``) are
    not here. Keep it beside the ``apply_*`` functions' ``pim_proj``
    calls: ``tests/test_weight_plan.py`` traces every architecture's
    decode step on the plan and fails on a float weight in ``pim`` mode."""
    def mlp(key):
        return tuple((key, n) for n in _MLP)
    if kind in ("g", "l", "d"):
        attn = ("wq", "wk", "wv", "wo", "xq", "xk", "xv", "xo")
        return {"attn": tuple((n,) for n in attn), "ffn": mlp("mlp")}
    if kind == "m":                       # its "wo" is a plain matmul
        return {"attn": (("wq",), ("wk",), ("wv",)), "ffn": mlp("shared")}
    if kind == "r" and cfg.family != "rwkv":
        return {"ffn": mlp("mlp")}
    return {}


def _pim_ragged(cfg: ModelConfig, xs: jnp.ndarray, we: jnp.ndarray,
                counts: jnp.ndarray) -> jnp.ndarray:
    """MoE per-expert grouped GEMM, PIM-offloaded under the ``"ffn"``
    scope (the expert FFNs are the block's FFN projections)."""
    if "ffn" not in cfg.pim_scopes():
        return jax.lax.ragged_dot(xs, we, counts)
    from repro.engine import get_engine
    mode = "pim" if cfg.pim_linear_mode == "off" else cfg.pim_linear_mode
    return get_engine().ragged_linear(xs, we, counts,
                                      n_bits=cfg.pim_linear_bits, mode=mode)


# ============================================================ attention ====
def _init_attn_core(cfg: ModelConfig, ini: Initializer) -> Dict[str, Any]:
    d = cfg.d_model
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
    p.update(_init_attn_core(cfg, ini))
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


def _prefill_cache(state, k, v):
    """The block's decode state with the prompt's K/V left behind."""
    s = k.shape[1]
    t = state["self"]["k"].shape[1]
    with obs.scope(obs.KV_CACHE):
        kc, vc = k, v
        if s < t:
            kc = jnp.pad(k, ((0, 0), (0, t - s), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, t - s), (0, 0), (0, 0)))
        elif s > t:            # windowed: keep the most recent slice,
            # rotated so token j sits at ring slot j % t.
            kc = jnp.roll(k[:, -t:], s % t, axis=1)
            vc = jnp.roll(v[:, -t:], s % t, axis=1)
        new_state = dict(state)
        new_state["self"] = {
            "k": kc.astype(state["self"]["k"].dtype),
            "v": vc.astype(state["self"]["v"].dtype),
            "length": jnp.asarray(s, jnp.int32)}
    return new_state


def apply_attn_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode,
                     kind: str):
    b, s, d = x.shape
    window = cfg.window if kind == "l" else None
    with obs.scope(obs.ATTENTION):
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, xn, pos)
        new_state = state
        if mode in ("full", "encode"):
            o = attend(q, k, v, causal=(mode != "encode"), window=window,
                       cap=cfg.softcap_attn)
            if state is not None:     # prefill: leave the KV behind
                new_state = _prefill_cache(state, k, v)
        else:
            o, cache = decode_attend(q, KVCache(**state["self"]), k, v,
                                     window=window, cap=cfg.softcap_attn)
            new_state = dict(state)
            new_state["self"] = cache._asdict()
        x = x + pim_proj(cfg, o.reshape(b, s, cfg.q_dim), p["wo"],
                         scope="attn")

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
    p.update(_init_attn_core(cfg, ini))
    p["router"] = ini(d, e.n_experts, scale=d ** -0.5)
    p["we1"] = ini(e.n_experts, d, f, scale=d ** -0.5)
    p["we3"] = ini(e.n_experts, d, f, scale=d ** -0.5)
    p["we2"] = ini(e.n_experts, f, d, scale=(f * 2 * cfg.n_layers) ** -0.5)
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
    """
    e = cfg.moe
    t, d = x2.shape
    logits = x2 @ p["router"]
    gate, idx = jax.lax.top_k(logits, e.top_k)            # (T, k)
    gate = jax.nn.softmax(gate.astype(jnp.float32), axis=-1).astype(x2.dtype)

    flat_e = idx.reshape(-1)                               # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), e.top_k)
    order = jnp.argsort(flat_e)                            # stable
    st, sg = flat_t[order], gate.reshape(-1)[order]
    counts = jnp.bincount(flat_e, length=e.n_experts).astype(jnp.int32)

    xs = x2[st]                                            # (T*k, d)
    h = _pim_ragged(cfg, xs, p["we1"], counts)
    h3 = _pim_ragged(cfg, xs, p["we3"], counts)
    y = _pim_ragged(cfg, jax.nn.silu(h) * h3, p["we2"], counts)
    out = jnp.zeros_like(x2).at[st].add(y * sg[:, None])
    if e.n_shared:
        out = out + _apply_mlp(cfg, p["shared"], x2)
    return out


def apply_moe_block(cfg: ModelConfig, p, x, *, pos, state, enc_out, mode):
    b, s, d = x.shape
    with obs.scope(obs.ATTENTION):
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, xn, pos)
        new_state = state
        if mode == "full":
            o = attend(q, k, v, causal=True, cap=cfg.softcap_attn)
        else:
            o, cache = decode_attend(q, KVCache(**state["self"]), k, v,
                                     cap=cfg.softcap_attn)
            new_state = dict(state)
            new_state["self"] = cache._asdict()
        x = x + (o.reshape(b, s, cfg.q_dim) @ p["wo"])
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
        return init_attn_block(cfg, ini, "g",
                               d_ff=cfg.moe.d_ff_dense or cfg.d_ff)
    if kind == "r":
        return (init_rwkv_block(cfg, ini) if cfg.family == "rwkv"
                else init_rglru_block(cfg, ini))
    raise ValueError(kind)


def apply_block(cfg: ModelConfig, kind: str, p, x, *, pos, state=None,
                enc_out=None, mode="full"):
    if kind in ("g", "l"):
        return apply_attn_block(cfg, p, x, pos=pos, state=state,
                                enc_out=enc_out, mode=mode, kind=kind)
    if kind == "d":
        return apply_attn_block(cfg, p, x, pos=pos, state=state,
                                enc_out=enc_out, mode=mode, kind="g")
    if kind == "m":
        return apply_moe_block(cfg, p, x, pos=pos, state=state,
                               enc_out=enc_out, mode=mode)
    if kind == "r":
        fn = (apply_rwkv_block if cfg.family == "rwkv"
              else apply_rglru_block)
        return fn(cfg, p, x, pos=pos, state=state, enc_out=enc_out, mode=mode)
    raise ValueError(kind)


def init_state(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
               dtype=jnp.float32, enc_len: int = 0):
    """Zero decode-state for one block."""
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
