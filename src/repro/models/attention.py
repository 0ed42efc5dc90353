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
           "latent_decode_attend", "projection_shapes", "write_position",
           "POSITIONS_LAST"]


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

# Cache buffers kept with positions along their last axis: the rope keys
# are 64 wide, so positions-last fills a TPU tile's 128 lanes, where a
# position-major array would be laid out time-minor by the compiler and
# copied to row-major for every kernel that reads it.
POSITIONS_LAST = ("kpe",)


class KVCache(NamedTuple):
    """Ring-buffered KV cache. ``k``/``v``: (B, T, Hkv, D); ``length``:
    running token count (scalar int32). For windowed layers T = window
    and writes wrap modulo T."""
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray


class LatentCache(NamedTuple):
    """Latent-attention cache: ``c`` (B, T, kv_lora_rank), the normed
    latent; ``kpe`` (B, rope dims, T), the roped key every head shares,
    positions along the last axis (:data:`POSITIONS_LAST`); ``length`` as
    :class:`KVCache`'s."""
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


def write_position(buf: jnp.ndarray, new: jnp.ndarray, slot,
                   layer=None, axis: int = 1) -> jnp.ndarray:
    """``buf`` with the token's entries ``new`` written at ring slot
    ``slot`` of its time axis ``axis``, where ``new`` has size 1: of a
    layer's cache (B, T, ... with ``axis`` 1), or of layer ``layer`` of
    a layer stack (L, B, T, ...). Nothing else of ``buf`` is written, so
    a donated stack is updated in place."""
    new = new.astype(buf.dtype)
    start = tuple(slot if i == axis else 0 for i in range(new.ndim))
    if layer is not None:
        new, start = new[None], (layer,) + start
    return jax.lax.dynamic_update_slice(buf, new, start)


def _lengths(length: jnp.ndarray, layer):
    """-> (the layer's token count before this step, ``length`` counted
    up by one token): a scalar, or entry ``layer`` of a layer stack's
    counts."""
    if layer is None:
        return length, length + 1
    n = jax.lax.dynamic_index_in_dim(length, layer, 0, keepdims=False)
    return n, jax.lax.dynamic_update_slice(length, (n + 1)[None], (layer,))


def _layer(buf: jnp.ndarray, layer) -> jnp.ndarray:
    return (buf if layer is None
            else jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False))


def _n_valid(length, t: int, window: Optional[int] = None):
    """The ring slots to attend over once the token is written: the
    tokens written, capped at the ring and the window."""
    n = jnp.minimum(length + 1, t)
    return n if window is None else jnp.minimum(n, window)


def _ring_valid(slot, n_valid, t: int):
    """(T,) bool: the ring slots written within the last ``n_valid``
    tokens, ``slot`` the newest."""
    return jnp.mod(slot - jnp.arange(t), t) < n_valid     # age 0 = newest


def decode_attend(q: jnp.ndarray, cache: KVCache, k_new: jnp.ndarray,
                  v_new: jnp.ndarray, *, layer=None,
                  window: Optional[int] = None,
                  cap: Optional[float] = None
                  ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: write (k_new, v_new) at the ring slot, then
    attend over the layer's cache.

    q/k_new/v_new: (B, 1, H*, D). ``cache`` holds one layer's (B, T,
    Hkv, D) buffers and scalar count, or, with ``layer``, a layer
    stack's (L, B, T, Hkv, D) buffers and (L,) counts, of which layer
    ``layer`` is this one. Only the token's entries and the count are
    written (:func:`write_position`); the returned cache is ``cache``
    with them. Ring-buffer writes keep the windowed layers' cache
    O(window) for the 500k-context shapes.
    """
    t = cache.k.shape[-3]
    length, new_length = _lengths(cache.length, layer)
    slot = jnp.mod(length, t)
    with obs.scope(obs.KV_CACHE):
        k = write_position(cache.k, k_new, slot, layer)
        v = write_position(cache.v, v_new, slot, layer)
    d = q.shape[-1]

    def dense():
        with obs.scope(obs.KV_CACHE):
            k_l, v_l = _layer(k, layer), _layer(v, layer)
        scores = _grouped_scores(q, k_l) * (d ** -0.5)     # (B,H,1,T)
        scores = _softcap(scores, cap)
        valid = _ring_valid(slot, _n_valid(length, t, window), t)
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        return _grouped_out(probs, v_l)

    def kernel():
        from repro.kernels.decode_attention import kv_decode_attention
        b, _, hq, _ = q.shape
        hkv = k.shape[-2]
        qg = (q[:, 0] * d ** -0.5).reshape(b, hkv, hq // hkv, d)
        o = kv_decode_attention(
            qg.transpose(0, 2, 1, 3), *_stacks(k, v, layer=layer), slot,
            _n_valid(length, t, window), cap=cap, interpret=False)
        return o.transpose(0, 2, 1, 3).reshape(b, 1, hq, d)

    return _read_in_place(kernel, dense), KVCache(k, v, new_length)


def latent_decode_attend(q_nope: jnp.ndarray, q_pe: jnp.ndarray,
                         cache: LatentCache, c_new: jnp.ndarray,
                         kpe_new: jnp.ndarray, w_uk: jnp.ndarray,
                         w_uv: jnp.ndarray, *, scale: float, layer=None
                         ) -> Tuple[jnp.ndarray, LatentCache]:
    """One-token latent attention in the absorbed form: write the
    token's latent and rope key at the ring slot, then attend over the
    layer's latents.

    q_nope (B, 1, H, dn), q_pe (B, 1, H, dr), c_new (B, 1, r), kpe_new
    (B, 1, dr), cached as (B, dr, T) (:class:`LatentCache`); ``w_uk``
    (r, H, dn) and ``w_uv`` (r, H, dv) are the key and value halves of
    ``wkv_b``. ``cache`` and ``layer`` as
    :func:`decode_attend`'s: one layer's (B, T, ...) latents, or a
    layer stack's with ``layer`` this one. Per head the query is taken
    into the latent (``q_lat = W_UK^T q_nope``), the scores are ``q_lat
    . c + q_pe . k_pe``, and the latents' weighted sum leaves through
    ``W_UV``: the same scores and output as decompressing every cached
    latent into per-head keys and values, without doing so. -> (B, 1,
    H, dv).
    """
    t = cache.c.shape[-2]
    length, new_length = _lengths(cache.length, layer)
    slot = jnp.mod(length, t)
    with obs.scope(obs.KV_CACHE):
        c = write_position(cache.c, c_new, slot, layer)
        kpe = write_position(cache.kpe, kpe_new.swapaxes(1, 2), slot,
                             layer, axis=2)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)

    def dense():
        with obs.scope(obs.KV_CACHE):
            c_l, kpe_l = _layer(c, layer), _layer(kpe, layer)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_l)
                  + jnp.einsum("bshd,bdt->bhst", q_pe, kpe_l)) * scale
        valid = _ring_valid(slot, _n_valid(length, t), t)
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(q_nope.dtype)
        return jnp.einsum("bhst,btr->bshr", probs, c_l)

    def kernel():
        from repro.kernels.decode_attention import latent_decode_attention
        o = latent_decode_attention(
            q_lat[:, 0], q_pe[:, 0], *_stacks(c, kpe, layer=layer), slot,
            _n_valid(length, t), scale=scale, precision=_precision(),
            interpret=False)
        return o[:, None]

    o_lat = _read_in_place(kernel, dense)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
    return out, LatentCache(c, kpe, new_length)


def _stacks(*bufs, layer):
    """-> (*layer stacks, the layer's index): a layer's own cache as a
    stack of one."""
    if layer is None:
        return tuple(b[None] for b in bufs) + (0,)
    return bufs + (layer,)


def _precision():
    """The float products' precision that ``jax_default_matmul_precision``
    sets (None: the platform's default)."""
    p = jax.config.jax_default_matmul_precision
    try:
        return None if p is None else jax.lax.Precision(p)
    except ValueError:              # a dot algorithm, not a precision
        return None


def _read_in_place(kernel, dense):
    """Attention over a layer of the written cache: on a TPU where the
    process sees one device, the Pallas kernel, which reads the layer
    where it lies in the stack (:mod:`repro.kernels.decode_attention`);
    elsewhere ``dense``, the jnp form. The platform is the lowering's;
    with several devices the cache may be sharded, and the jnp form is
    what the partitioner can split along it."""
    if jax.device_count() > 1:
        return dense()
    return jax.lax.platform_dependent(tpu=kernel, default=dense)
