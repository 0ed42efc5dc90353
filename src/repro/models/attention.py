"""Attention: GQA/MQA, causal + sliding-window masks, KV-cache decode.

All functions take/return (B, S, H, D) tensors. GQA repeats KV heads up
to the query head count with a reshape-free einsum grouping so the TP
sharding of the query-head axis is preserved.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs

from .layers import softcap as _softcap

__all__ = ["attend", "decode_attend", "KVCache", "LatentCache",
           "latent_decode_attend", "projection_shapes"]


def projection_shapes(cfg) -> "list[Tuple[str, int, int]]":
    """The attention block's linear inventory: (name, in_dim, out_dim)
    for the q/k/v/o projections — plus the cross-attention xq/xk/xv/xo
    pair carried by enc-dec decoder blocks — the shapes the PIM block
    planner (:mod:`repro.pim.planner`) lowers onto co-scheduled crossbar
    groups under ``cfg.pim_block_mode == "full"``. Kept next to the
    attention math so the planner can never drift from what the block
    computes. Latent attention (``cfg.mla``) projects the query, the
    latent with the shared rope key (``attn.kv_a``) and the output; its
    ``wkv_b`` is absorbed into the attention core and stays digital.
    """
    d = cfg.d_model
    if cfg.mla is not None:
        a = cfg.mla
        return [("attn.q", d, cfg.n_heads * a.qk_head_dim),
                ("attn.kv_a", d, a.kv_lora_rank + a.qk_rope_head_dim),
                ("attn.o", cfg.n_heads * a.v_head_dim, d)]
    shapes = [("attn.q", d, cfg.q_dim),
              ("attn.k", d, cfg.kv_dim),
              ("attn.v", d, cfg.kv_dim),
              ("attn.o", cfg.q_dim, d)]
    if cfg.family == "encdec":
        shapes += [("attn.xq", d, cfg.q_dim),
                   ("attn.xk", d, cfg.kv_dim),
                   ("attn.xv", d, cfg.kv_dim),
                   ("attn.xo", cfg.q_dim, d)]
    return shapes

NEG_INF = -2.3819763e38


class KVCache(NamedTuple):
    """Ring-buffered KV cache. ``k``/``v``: (B, T, Hkv, D); ``length``:
    running token count (scalar int32). For windowed layers T = window
    and writes wrap modulo T."""
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray


class LatentCache(NamedTuple):
    """Latent-attention cache: ``c`` (B, T, kv_lora_rank), the normed
    latent; ``kpe`` (B, T, rope dims), the roped key every head shares;
    ``length`` as :class:`KVCache`'s."""
    c: jnp.ndarray
    kpe: jnp.ndarray
    length: jnp.ndarray


def _grouped_scores(q, k):
    """(B,S,Hq,D) x (B,T,Hkv,D) -> (B, Hq, S, T) with GQA grouping."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k)
    return scores.reshape(b, hkv * g, s, k.shape[1])


def _grouped_out(probs, v):
    b, h, s, t = probs.shape
    hkv = v.shape[2]
    g = h // hkv
    pg = probs.reshape(b, hkv, g, s, t)
    out = jnp.einsum("bkgst,btkd->bskgd", pg, v)
    return out.reshape(b, s, h, v.shape[-1])


FLASH_THRESHOLD = 4096          # switch to blockwise above this S*T size
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 512


def _mask(qpos, kpos, causal, window):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _dense_attend(q, k, v, *, causal, window, cap, q_offset, scale=None):
    d = q.shape[-1]
    scores = _grouped_scores(q, k) * (d ** -0.5 if scale is None else scale)
    scores = _softcap(scores, cap)
    s_len, t_len = scores.shape[-2], scores.shape[-1]
    m = _mask(jnp.arange(s_len) + q_offset, jnp.arange(t_len), causal, window)
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return _grouped_out(probs, v)


def _flash_attend(q, k, v, *, causal, window, cap, q_offset, scale=None):
    """Blockwise online-softmax attention (memory O(bq*bk), pure JAX).

    The peak live buffer is one (B, H, bq, bk) score tile instead of the
    full (B, H, S, T) matrix — required for the 32k prefill and 4k x 256
    train shapes. Lowered as two nested lax.scans that XLA unrolls onto
    the MXU; on real TPUs the same call sites can swap in a Pallas
    flash kernel without touching callers.
    """
    b, s, hq, d = q.shape
    t = k.shape[1]
    bq = min(FLASH_BLOCK_Q, s)
    bk = min(FLASH_BLOCK_K, t)
    s_pad = (-s) % bq
    t_pad = (-t) % bk
    qp = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk
    scale = d ** -0.5 if scale is None else scale
    dv = v.shape[-1]

    kb = kp.reshape(b, nk, bk, *kp.shape[2:])
    vb = vp.reshape(b, nk, bk, *vp.shape[2:])

    def q_block(qi, q_tile):
        # q_tile: (B, bq, Hq, D)
        qpos = qi * bq + jnp.arange(bq) + q_offset

        def kv_block(carry, inp):
            acc, m_run, l_run = carry
            ki, k_tile, v_tile = inp
            kpos = ki * bk + jnp.arange(bk)
            sc = _grouped_scores(q_tile, k_tile) * scale     # (B,H,bq,bk)
            sc = _softcap(sc, cap)
            valid = (kpos < t)[None, :]
            msk = _mask(qpos, kpos, causal, window) & valid
            sc = jnp.where(msk[None, None], sc.astype(jnp.float32), NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + _grouped_out(
                p.astype(q.dtype), v_tile).swapaxes(1, 2).astype(jnp.float32)
            return (acc, m_new, l_new), None

        hq_ = q_tile.shape[2]
        acc0 = jnp.zeros((b, hq_, bq, dv), jnp.float32)
        m0 = jnp.full((b, hq_, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq_, bq), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_block, (acc0, m0, l0),
            (jnp.arange(nk), kb.swapaxes(0, 1), vb.swapaxes(0, 1)))
        out = acc / jnp.maximum(l_run[..., None], 1e-30)
        return out.swapaxes(1, 2).astype(q.dtype)     # (B, bq, Hq, D)

    qb = qp.reshape(b, nq, bq, hq, d).swapaxes(0, 1)
    outs = jax.lax.map(lambda args: q_block(args[0], args[1]),
                       (jnp.arange(nq), qb))
    out = outs.swapaxes(0, 1).reshape(b, nq * bq, hq, dv)
    return out[:, :s]


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           causal: bool = True, window: Optional[int] = None,
           cap: Optional[float] = None,
           q_offset: int = 0, scale: Optional[float] = None) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).

    ``window``: sliding-window width (None = global). ``q_offset``:
    absolute position of q[0] relative to k[0] (cross/self alignment).
    ``scale``: the scores' factor (None: ``D^-1/2`` of q's head size);
    values may be narrower than queries and keys. Dispatches to the
    blockwise (flash) path for long sequences.
    """
    s, t = q.shape[1], k.shape[1]
    if s * t > FLASH_THRESHOLD * FLASH_THRESHOLD // 4 and s > 1:
        return _flash_attend(q, k, v, causal=causal, window=window, cap=cap,
                             q_offset=q_offset, scale=scale)
    return _dense_attend(q, k, v, causal=causal, window=window, cap=cap,
                         q_offset=q_offset, scale=scale)


def decode_attend(q: jnp.ndarray, cache: KVCache, k_new: jnp.ndarray,
                  v_new: jnp.ndarray, *, window: Optional[int] = None,
                  cap: Optional[float] = None
                  ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: append (k_new, v_new) then attend over the cache.

    q/k_new/v_new: (B, 1, H*, D). Ring-buffer write keeps the windowed
    layers' cache O(window) for the 500k-context shapes.
    """
    t = cache.k.shape[1]
    slot = jnp.mod(cache.length, t)
    with obs.scope(obs.KV_CACHE):
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (0, slot, 0, 0))
    new_len = cache.length + 1

    d = q.shape[-1]
    scores = _grouped_scores(q, k) * (d ** -0.5)       # (B,H,1,T)
    scores = _softcap(scores, cap)
    kpos_slot = jnp.arange(t)
    # valid slots: those written within the last min(new_len, window or T)
    age = jnp.mod(slot - kpos_slot, t)                  # 0 = newest
    valid = age < jnp.minimum(new_len, t)
    if window is not None:
        valid &= age < window
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = _grouped_out(probs, v)
    return out, KVCache(k, v, new_len)


def latent_decode_attend(q_nope: jnp.ndarray, q_pe: jnp.ndarray,
                         cache: LatentCache, c_new: jnp.ndarray,
                         kpe_new: jnp.ndarray, w_uk: jnp.ndarray,
                         w_uv: jnp.ndarray, *, scale: float
                         ) -> Tuple[jnp.ndarray, LatentCache]:
    """One-token latent attention in the absorbed form: append the
    token's latent and rope key, then attend over the latents.

    q_nope (B, 1, H, dn), q_pe (B, 1, H, dr), c_new (B, 1, r), kpe_new
    (B, 1, dr); ``w_uk`` (r, H, dn) and ``w_uv`` (r, H, dv) are the key
    and value halves of ``wkv_b``. Per head the query is taken into the
    latent (``q_lat = W_UK^T q_nope``), the scores are ``q_lat . c +
    q_pe . k_pe``, and the latents' weighted sum leaves through ``W_UV``:
    the same scores and output as decompressing every cached latent into
    per-head keys and values, without doing so. -> (B, 1, H, dv).
    """
    t = cache.c.shape[1]
    slot = jnp.mod(cache.length, t)
    with obs.scope(obs.KV_CACHE):
        c = jax.lax.dynamic_update_slice(
            cache.c, c_new.astype(cache.c.dtype), (0, slot, 0))
        kpe = jax.lax.dynamic_update_slice(
            cache.kpe, kpe_new.astype(cache.kpe.dtype), (0, slot, 0))
    new_len = cache.length + 1

    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c)
              + jnp.einsum("bshd,btd->bhst", q_pe, kpe)) * scale
    age = jnp.mod(slot - jnp.arange(t), t)              # 0 = newest
    valid = age < jnp.minimum(new_len, t)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q_nope.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
    return out, LatentCache(c, kpe, new_len)
