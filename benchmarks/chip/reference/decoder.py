"""Plain reference of the dense decoder the benchmark's decoder cells run.

Straight ``jax.numpy`` in float32 with every matrix product at an
explicit precision (``highest`` for the reference, lower ones for the
controls), no cache, no batching across steps: the whole sequence
``prompt + served tokens`` goes through each layer at once, layer by
layer, with causal attention computed in blocks of queries.

The model is the one the program defines for its dense decoders
(written out here, not imported): token embedding scaled by
``sqrt(d_model)``; pre-norm blocks with RMSNorm ``x / rms(x) * (1 + w)``;
rotary embedding on the two halves of each head; causal softmax
attention; SwiGLU FFN ``w2(silu(w1 x) * w3 x)``; final RMSNorm; LM head.

Linears in ``pim_scopes`` follow the MultPIM fixed-point semantics:
activations quantized per tensor, weights per output column, both to
``bits``-bit unsigned values with offset ``2^(bits-1)``
(``q = clip(round(x / s) + z, 0, 2^bits - 1)``, ``s = max|x| / (2^(bits-1)
- 1)``), the integer product of ``(qx - z)`` and ``(qw - z)`` exact in
int32, then scaled by ``sx * sw``. "Per tensor" is per call of the
served system: the prefill is one call over every prompt position of
the batch, and each decode step one call over the batch at one
position. So positions ``< prompt_len`` share one activation scale and
each later position has its own.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
VOCAB_BLOCK = 16384


def _layers(params) -> list:
    """Per-layer weight dicts in model order from the stacked layout
    (``prefix`` blocks, then ``scan`` units stacked on a leading axis,
    then ``suffix`` blocks)."""
    out = list(params["prefix"])
    scan = params["scan"]
    if scan:
        n_units = jax.tree.leaves(scan[0])[0].shape[0]
        for u in range(n_units):
            for slot in scan:
                out.append(jax.tree.map(lambda a, u=u: a[u], slot))
    return out + list(params["suffix"])


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act_scale(x, prompt_len: int, bits: int):
    """Per-call activation scale of x (B, S, K), one per position: the
    prompt positions share the prefill call's, later ones their own."""
    amax = jnp.max(jnp.abs(x), axis=(0, 2))
    s = x.shape[1]
    if prompt_len > 0:
        amax = jnp.where(jnp.arange(s) < prompt_len,
                         jnp.max(amax[:prompt_len]), amax)
    return jnp.maximum(amax, 1e-8) / (2 ** (bits - 1) - 1)


def quantize_weight(w, bits: int):
    """Per-column scale and offset-free integers of w (K, N)."""
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                     1e-8) / (2 ** (bits - 1) - 1)
    return _qint(w / sw, bits), sw


def _qint(r, bits: int):
    z = 2 ** (bits - 1)
    q = jnp.clip(jnp.round(r) + z, 0, 2 ** bits - 1) - z
    return q.astype(jnp.int8 if bits <= 8 else jnp.int32)


def fixed_point(x, sx, w, bits: int):
    """x (B, S, K) with per-position scale sx (S,) times w (K, N)."""
    qx = _qint(x / sx[None, :, None], bits)
    qw, sw = quantize_weight(w, bits)
    acc = jax.lax.dot_general(qx, qw, (((2,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx[None, :, None] * sw


def dot(subscripts: str, a, b, precision: str):
    """``einsum`` of float32 operands at ``precision``. ``high`` and
    ``default`` are written out as the TPU does them, so that a control
    reads the same on every platform: three bfloat16 products (high
    parts, and each high part with the other's low part) summed in
    float32, and one bfloat16 product accumulated in float32."""
    if precision not in ("high", "default"):
        return jnp.einsum(subscripts, a, b, precision=precision)
    bf = jnp.bfloat16

    def split(x):
        hi = x.astype(bf)
        return hi, (x - hi.astype(jnp.float32)).astype(bf)

    (ah, al), (bh, bl) = split(a), split(b)

    def f(x, y):
        return jnp.einsum(subscripts, x, y,
                          preferred_element_type=jnp.float32)

    if precision == "default":
        return f(ah, bh)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def linear(x, w, *, pim: bool, bits: int, prompt_len: int, precision):
    if pim:
        return fixed_point(x, act_scale(x, prompt_len, bits), w, bits)
    return dot("bsk,kn->bsn", x, w, precision)


def attention(q, k, v, precision):
    """Causal attention, (B, S, H, D) each, in blocks of queries."""
    s, d = q.shape[1], q.shape[-1]
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        sc = dot("bqhd,bkhd->bhqk", qb, k, precision) * d ** -0.5
        qpos = q0 + jnp.arange(qb.shape[1])
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(dot("bhqk,bkhd->bqhd", p, v, precision))
    return jnp.concatenate(outs, axis=1)


@partial(jax.jit, static_argnames=("cfg", "scopes", "bits", "prompt_len",
                                   "precision"))
def _block(x, p, *, cfg, scopes, bits, prompt_len, precision):
    b, s, d = x.shape
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    eps = cfg["rms_norm_eps"]
    lin = partial(linear, bits=bits, prompt_len=prompt_len,
                  precision=precision)
    pos = jnp.arange(s)
    xn = rms_norm(x, p["ln1"], eps)
    q = lin(xn, p["wq"], pim="attn" in scopes).reshape(b, s, h, hd)
    k = lin(xn, p["wk"], pim="attn" in scopes).reshape(b, s, kvh, hd)
    v = lin(xn, p["wv"], pim="attn" in scopes).reshape(b, s, kvh, hd)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    o = attention(q, k, v, precision).reshape(b, s, h * hd)
    x = x + lin(o, p["wo"], pim="attn" in scopes)
    xn = rms_norm(x, p["ln2"], eps)
    m = p["mlp"]
    ffn = "ffn" in scopes
    g = jax.nn.silu(lin(xn, m["w1"], pim=ffn)) * lin(xn, m["w3"], pim=ffn)
    return x + lin(g, m["w2"], pim=ffn)


@partial(jax.jit, static_argnames=("pim", "bits", "precision"))
def _head_block(xs, sx, w, served, extra, *, pim, bits, precision):
    if pim:
        logits = fixed_point(xs, sx, w, bits)
    else:
        logits = dot("bsk,kn->bsn", xs, w, precision)
    n = w.shape[1]

    def pick(tok):
        inside = (tok >= 0) & (tok < n)
        got = jnp.take_along_axis(
            logits, jnp.clip(tok, 0, n - 1)[..., None], -1)[..., 0]
        return jnp.where(inside, got, -jnp.inf)

    return (jnp.max(logits, -1), jnp.argmax(logits, -1), pick(served),
            jax.vmap(pick)(extra))


def readings(params, cfg: Dict, prompts, served, *, pim_scopes: Sequence
             = (), bits: int = 8, precision: str = "highest",
             extra: Sequence = ()) -> Dict[str, np.ndarray]:
    """Reference logits at every position where a token was served.

    ``prompts`` (B, P) and ``served`` (B, n + 1): token ``served[:, j]``
    was chosen at position ``P - 1 + j``. Returns, per (sequence, j),
    ``best`` (the largest logit), ``argmax``, ``served`` (the logit of
    the served token) and ``extra``, the logits of each array of
    further tokens (B, n + 1) in ``extra``.
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    served = jnp.asarray(served, jnp.int32)
    extra = jnp.asarray(np.stack([served, *extra]), jnp.int32)
    plen = prompts.shape[1]
    tokens = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    scopes = tuple(sorted(pim_scopes))
    frozen = _Frozen(cfg)
    x = params["embed"][tokens] * cfg["hidden_size"] ** 0.5
    for p in _layers(params):
        x = _block(x, p, cfg=frozen, scopes=scopes, bits=bits,
                   prompt_len=plen, precision=precision)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    head_pim = "head" in scopes
    sx = act_scale(x, plen, bits)[plen - 1:]
    xs = x[:, plen - 1:]
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    vocab = head.shape[1]
    best = arg = got = got_extra = None
    for c0 in range(0, vocab, VOCAB_BLOCK):
        w = head[:, c0:c0 + VOCAB_BLOCK]
        bb, ab, sb, eb = _head_block(xs, sx, w, served - c0, extra - c0,
                                     pim=head_pim, bits=bits,
                                     precision=precision)
        if best is None:
            best, arg, got, got_extra = bb, ab + c0, sb, eb
        else:
            arg = jnp.where(bb > best, ab + c0, arg)
            best = jnp.maximum(best, bb)
            got = jnp.maximum(got, sb)
            got_extra = jnp.maximum(got_extra, eb)
    return {"best": np.asarray(best), "argmax": np.asarray(arg),
            "served": np.asarray(got), "extra": np.asarray(got_extra)[1:]}


class _Frozen(dict):
    """A hashable view of the configuration (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
