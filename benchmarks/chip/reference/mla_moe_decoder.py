"""Plain reference of the MLA + MoE decoder the benchmark's
``deepseek-v2-lite`` cells run (DeepSeek-V2-Lite, arXiv:2405.04434).

Straight ``jax.numpy`` in float32 with every float matrix product at an
explicit precision (``highest`` for the reference, lower ones for the
controls), no cache, no batching across steps: the whole sequence
``prompt + served tokens`` goes through each layer at once, layer by
layer, attention in blocks of sequences and of queries.

The model, written out here (not imported from the program):

* token embedding scaled by ``sqrt(hidden_size)`` (the program's);
  pre-norm blocks, RMSNorm ``x / rms(x) * (1 + w)`` (the program's);
* multi-head latent attention in its published, decompressed form:
  ``q = W_q x`` split per head into ``q_nope`` (``qk_nope_head_dim``)
  and ``q_pe`` (``qk_rope_head_dim``); ``[c; k_pe] = W_kv_a x``, ``c``
  through its own RMSNorm; per-head keys ``[W_UK c; k_pe]`` (``k_pe``
  shared by every head) and values ``W_UV c`` from ``W_kv_b``; rope on
  ``q_pe`` and ``k_pe``; causal softmax at YaRN's scale
  ``qk_head_dim^-1/2 * m^2``; ``W_o``. Rope rotates the two halves of
  the rope dims (the program's; HF rotates interleaved pairs, a fixed
  permutation of the rope columns of ``W_q`` and ``W_kv_a``) at YaRN's
  frequencies, as HF's ``DeepseekV2YarnRotaryEmbedding`` defines them;
* layers before ``first_k_dense_replace``: SwiGLU FFN
  ``w2(silu(w1 x) * w3 x)``; the others: softmax over every routed
  expert's logit (``router_outputs`` of them), the top
  ``num_experts_per_tok`` probabilities as gates, not renormalized
  (``norm_topk_prob: false``); of those picks only the ones that land in
  the experts this chip holds, ``[expert_offset, expert_offset +
  n_routed_experts)``, are computed, each gate times its expert's SwiGLU
  (what the absent experts would add is left out); plus the shared
  experts, one SwiGLU of ``n_shared_experts * moe_intermediate_size``;
* final RMSNorm; LM head.

Linears in ``pim_scopes`` follow the MultPIM fixed-point semantics
(``ffn``: dense FFN, shared experts, held experts; ``head``): operands
quantized to ``bits``-bit unsigned values with offset ``2^(bits-1)``,
the integer product exact in int32, scaled back. Weights: per output
column for the dense linears; one scale per held stack of experts (each
of ``we1``, ``we3``, ``we2``). Activations: one scale per call of the
served system, over what that call multiplies: the prefill is one call
per slice of ``prefill_batch`` sequences over its prompt positions, each
decode step one call over the batch at one position; a held expert
stack's call takes the rows routed to the held experts only.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference.decoder import _Frozen, _layers, _qint, dot, quantize_weight
from reference.decoder import rms_norm

QUERY_BLOCK = 256
SEQ_BLOCK = 4
VOCAB_BLOCK = 16384


def yarn_inv_freq(cfg) -> jnp.ndarray:
    """Rotary frequencies over the rope dims: YaRN's ramp between the
    interpolated (``/ factor``) and the original frequencies."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return jnp.asarray(extra)
    inter = extra / rs["factor"]

    def d(n):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (n * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(d(rs["beta_fast"])), 0)
    high = min(math.ceil(d(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1 - np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                       / (high - low), 0, 1)
    return jnp.asarray(inter * (1 - mask) + extra * mask, jnp.float32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope_factor(cfg) -> float:
    """cos/sin magnitude: ``m(mscale) / m(mscale_all_dim)``."""
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0
    return (_mscale(rs["factor"], rs.get("mscale", 1.0))
            / _mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))


def rope(x, pos, inv_freq, factor):
    """x (B, S, H, D) roped on its two halves at positions pos (S,)."""
    half = x.shape[-1] // 2
    ang = pos[:, None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------- calls ----
def call_ids(b: int, s: int, prompt_len: int, prefill_batch: int):
    """(B, S) id of the served system's call that holds each position:
    prefill slices of ``prefill_batch`` sequences, then one decode call
    per position."""
    slices = -(-b // prefill_batch)
    seq = np.arange(b)[:, None] // prefill_batch
    pos = np.arange(s)[None, :]
    ids = np.where(pos < prompt_len, seq, slices + pos - prompt_len)
    return jnp.asarray(ids, jnp.int32), int(slices + max(s - prompt_len, 0))


def call_scale(amax, ids, n_calls: int, bits: int):
    """Per-position activation scale (B, S) from each position's largest
    magnitude ``amax`` (B, S): the largest over its call."""
    m = jax.ops.segment_max(amax.ravel(), ids.ravel(),
                            num_segments=n_calls)
    return jnp.maximum(m, 1e-8)[ids] / (2 ** (bits - 1) - 1)


def _amax(x):
    return jnp.max(jnp.abs(x), axis=-1)


def fixed(x, sx, qw, sw, bits: int):
    """x (..., K) at per-row scale sx (...) times codes qw (K, N) at
    scale sw, exact in int32."""
    qx = _qint(x / sx[..., None], bits)
    acc = jax.lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx[..., None] * sw


def _by_seq(fn, *xs):
    """``fn`` over blocks of SEQ_BLOCK sequences (leading axis)."""
    b = xs[0].shape[0]
    blk = SEQ_BLOCK if b % SEQ_BLOCK == 0 else 1
    split = [x.reshape(b // blk, blk, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: fn(*a), split)
    return jax.tree.map(lambda y: y.reshape(b, *y.shape[2:]), out)


# ---------------------------------------------------------- attention ----
def attention(q, k, v, scale, precision):
    """Causal attention, q/k (B, S, H, Dq), v (B, S, H, Dv), in blocks
    of queries over the keys up to the block's last."""
    s = q.shape[1]
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        sc = dot("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, :q1],
                 precision) * scale
        mask = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(dot("bhqk,bkhd->bqhd", p, v[:, :q1], precision))
    return jnp.concatenate(outs, axis=1)


def mla(x, p, cfg, precision):
    """x plus the latent attention sublayer, decompressed per head."""
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    inv, fac, scale = yarn_inv_freq(cfg), rope_factor(cfg), softmax_scale(cfg)
    pos = jnp.arange(s)

    def block(xb):
        n = xb.shape[0]
        xn = rms_norm(xb, p["ln1"], eps)
        q = dot("bsk,kn->bsn", xn, p["wq"], precision).reshape(
            n, s, h, dn + dr)
        ckv = dot("bsk,kn->bsn", xn, p["wkv_a"], precision)
        c = rms_norm(ckv[..., :r], p["kv_norm"], eps)
        k_pe = rope(ckv[..., None, r:], pos, inv, fac)
        kv = dot("bsr,rn->bsn", c, p["wkv_b"], precision).reshape(
            n, s, h, dn + dv)
        qf = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, inv,
                                                fac)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (n, s, h, dr))], -1)
        o = attention(qf, k, kv[..., dn:], scale, precision)
        return dot("bsk,kn->bsn", o.reshape(n, s, h * dv), p["wo"],
                   precision)

    return x + _by_seq(block, x)


# ----------------------------------------------------------------- FFN ----
def _glu(xn, sx, m, *, pim, bits, precision):
    if pim:
        (q1, s1), (q3, s3) = (quantize_weight(m["w1"], bits),
                              quantize_weight(m["w3"], bits))
        h1, h3 = fixed(xn, sx, q1, s1, bits), fixed(xn, sx, q3, s3, bits)
    else:
        h1 = dot("bsk,kn->bsn", xn, m["w1"], precision)
        h3 = dot("bsk,kn->bsn", xn, m["w3"], precision)
    return jax.nn.silu(h1) * h3


def swiglu(xn, m, ids, n_calls, *, pim, bits, precision):
    """The SwiGLU FFN of xn (B, S, D), in blocks of sequences; under PIM
    each of its two products at its calls' activation scales (the
    second's found in a first pass)."""
    if not pim:
        return _by_seq(lambda a: dot(
            "bsk,kn->bsn", _glu(a, None, m, pim=False, bits=bits,
                                precision=precision), m["w2"], precision),
            xn)
    sx = call_scale(_amax(xn), ids, n_calls, bits)
    glu = partial(_glu, m=m, pim=True, bits=bits, precision=precision)
    s2 = call_scale(_by_seq(lambda a, sa: _amax(glu(a, sa)), xn, sx),
                    ids, n_calls, bits)
    q2, w2s = quantize_weight(m["w2"], bits)
    return _by_seq(lambda a, sa, sb: fixed(glu(a, sa), sb, q2, w2s, bits),
                   xn, sx, s2)


def gates(xn, router, cfg, *, renormalize, precision):
    """(B, S, E) gate of each routed expert: its softmax probability
    where it is among the token's top-k, else 0 (``renormalize``: the
    top-k probabilities summed to 1, the control)."""
    probs = jax.nn.softmax(dot("bsk,ke->bse", xn, router, precision), -1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if renormalize:
        top = top / jnp.sum(top, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
    return jnp.einsum("bsk,bske->bse", top, onehot), jnp.sum(onehot, -2)


def experts(xn, p, cfg, ids, n_calls, *, pim, bits, precision,
            renormalize):
    """The held experts' part of the MoE FFN over xn (B, S, D), in
    blocks of sequences, one held expert after another."""
    off, held = cfg.get("expert_offset", 0), cfg["n_routed_experts"]
    g_all, picked = gates(xn, p["router"], cfg, renormalize=renormalize,
                          precision=precision)
    g = g_all[..., off:off + held]                            # (B, S, E)
    routed = picked[..., off:off + held] > 0

    def stack(w):                       # one scale for the held stack
        sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / (2 ** (bits - 1) - 1)
        return _qint(w / sw, bits), sw
    ws = (p["we1"], p["we3"], p["we2"])
    if pim:
        (w1s, s1), (w3s, s3), (w2s, s2) = map(stack, ws)
    else:
        w1s, w3s, w2s = ws

    def glu(a, sa, w1, w3):
        if pim:
            return (jax.nn.silu(fixed(a, sa, w1, s1, bits))
                    * fixed(a, sa, w3, s3, bits))
        return (jax.nn.silu(dot("bsk,kn->bsn", a, w1, precision))
                * dot("bsk,kn->bsn", a, w3, precision))

    def experts_of(a, fn, init, per_expert):
        return jax.lax.scan(fn, init, (w1s, w3s, w2s,
                                       jnp.moveaxis(per_expert, -1, 0)))[0]

    sx = sh = jnp.zeros(xn.shape[:2], jnp.float32)
    if pim:
        sx = call_scale(jnp.where(jnp.any(routed, -1), _amax(xn), 0), ids,
                        n_calls, bits)

        def peak(a, sa, r):
            def one(m, e):
                w1, w3, _, re = e
                return jnp.maximum(m, jnp.where(
                    re, _amax(glu(a, sa, w1, w3)), 0)), None
            return experts_of(a, one, jnp.zeros(a.shape[:2]), r)
        sh = call_scale(_by_seq(peak, xn, sx, routed), ids, n_calls, bits)

    def block(a, sa, sb, gb):
        def one(y, e):
            w1, w3, w2, ge = e
            h = glu(a, sa, w1, w3)
            down = (fixed(h, sb, w2, s2, bits) if pim
                    else dot("bsn,nk->bsk", h, w2, precision))
            return y + ge[..., None] * down, None
        return experts_of(a, one, jnp.zeros_like(a), gb)
    return _by_seq(block, xn, sx, sh, g)


@partial(jax.jit, static_argnames=("cfg", "dense", "pim", "bits",
                                   "n_calls", "precision", "renormalize"))
def _layer(x, p, ids, *, cfg, dense, pim, bits, n_calls, precision,
           renormalize):
    x = mla(x, p, cfg, precision)
    xn = rms_norm(x, p["ln2"], cfg["rms_norm_eps"])
    kw = dict(pim=pim, bits=bits, precision=precision)
    if dense:
        return x + swiglu(xn, p["mlp"], ids, n_calls, **kw)
    y = experts(xn, p, cfg, ids, n_calls, renormalize=renormalize, **kw)
    return x + y + swiglu(xn, p["shared"], ids, n_calls, **kw)


def _final(params, cfg, prompts, served, *, pim_scopes, bits, precision,
           prefill_batch, renormalize):
    """Final-normed hidden states at the served positions (B, n + 1, D)
    and the head's activation scales there (B, n + 1)."""
    prompts = jnp.asarray(prompts, jnp.int32)
    served = jnp.asarray(served, jnp.int32)
    b, plen = prompts.shape
    tokens = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    ids, n_calls = call_ids(b, tokens.shape[1], plen, prefill_batch or b)
    frozen = _Frozen(cfg)
    x = params["embed"][tokens] * cfg["hidden_size"] ** 0.5
    for i, p in enumerate(_layers(params)):
        x = _layer(x, p, ids, cfg=frozen,
                   dense=i < cfg["first_k_dense_replace"],
                   pim="ffn" in pim_scopes, bits=bits, n_calls=n_calls,
                   precision=precision, renormalize=renormalize)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    sx = call_scale(_amax(x), ids, n_calls, bits)
    return x[:, plen - 1:], sx[:, plen - 1:]


def _head(xs, sx, w, *, pim, bits, precision):
    if pim:
        qw, sw = quantize_weight(w, bits)
        return fixed(xs, sx, qw, sw, bits)
    return dot("bsk,kn->bsn", xs, w, precision)


def _check_scopes(pim_scopes):
    if "attn" in pim_scopes:
        raise NotImplementedError("the reference runs attention in float")


def logits(params, cfg: Dict, prompts, served, *, pim_scopes: Sequence = (),
           bits: int = 8, precision: str = "highest",
           prefill_batch: Optional[int] = None,
           renormalize: bool = False) -> np.ndarray:
    """Reference logits (B, n + 1, V) where each token of ``served`` (B,
    n + 1) was chosen, at position ``P - 1 + j``; the whole vocabulary
    at once, for small sizes."""
    _check_scopes(pim_scopes)
    xs, sx = _final(params, cfg, prompts, served, pim_scopes=pim_scopes,
                    bits=bits, precision=precision,
                    prefill_batch=prefill_batch, renormalize=renormalize)
    with jax.default_matmul_precision(precision):
        return np.asarray(jax.jit(partial(
            _head, pim="head" in pim_scopes, bits=bits,
            precision=precision))(xs, sx, params["lm_head"]))


@partial(jax.jit, static_argnames=("pim", "bits", "precision"))
def _head_block(xs, sx, w, served, extra, *, pim, bits, precision):
    lg = _head(xs, sx, w, pim=pim, bits=bits, precision=precision)
    n = w.shape[1]

    def pick(tok):
        inside = (tok >= 0) & (tok < n)
        got = jnp.take_along_axis(
            lg, jnp.clip(tok, 0, n - 1)[..., None], -1)[..., 0]
        return jnp.where(inside, got, -jnp.inf)

    return (jnp.max(lg, -1), jnp.argmax(lg, -1), pick(served),
            jax.vmap(pick)(extra))


def readings(params, cfg: Dict, prompts, served, *, pim_scopes: Sequence
             = (), bits: int = 8, precision: str = "highest",
             extra: Sequence = (), prefill_batch: Optional[int] = None,
             renormalize: bool = False) -> Dict[str, np.ndarray]:
    """As :func:`reference.decoder.readings`: per (sequence, j), ``best``
    (the largest logit), ``argmax``, ``served`` (the served token's
    logit) and ``extra`` (the logits of each further (B, n + 1) array of
    tokens), over the vocabulary in blocks."""
    _check_scopes(pim_scopes)
    served = jnp.asarray(served, jnp.int32)
    extra = jnp.asarray(np.stack([served, *extra]), jnp.int32)
    xs, sx = _final(params, cfg, prompts, served, pim_scopes=pim_scopes,
                    bits=bits, precision=precision,
                    prefill_batch=prefill_batch, renormalize=renormalize)
    head = params["lm_head"]
    best = arg = got = got_extra = None
    for c0 in range(0, head.shape[1], VOCAB_BLOCK):
        bb, ab, sb, eb = _head_block(
            xs, sx, head[:, c0:c0 + VOCAB_BLOCK], served - c0, extra - c0,
            pim="head" in pim_scopes, bits=bits, precision=precision)
        if best is None:
            best, arg, got, got_extra = bb, ab + c0, sb, eb
        else:
            arg = jnp.where(bb > best, ab + c0, arg)
            best = jnp.maximum(best, bb)
            got = jnp.maximum(got, sb)
            got_extra = jnp.maximum(got_extra, eb)
    return {"best": np.asarray(best), "argmax": np.asarray(arg),
            "served": np.asarray(got), "extra": np.asarray(got_extra)[1:]}
