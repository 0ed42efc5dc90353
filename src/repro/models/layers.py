"""Shared neural primitives (pure functions over explicit param pytrees)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "layer_norm", "softcap", "rope", "yarn_inv_freq",
           "yarn_mscale", "swiglu", "gelu_mlp", "dense_init", "Initializer"]


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w)


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0,
         inv_freq: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Rotary embedding on the two halves of the last axis. x: (..., S,
    H, D) with D even; positions (..., S). ``inv_freq`` (D/2,) replaces
    the frequencies ``theta^(-2i/D)`` (YaRN: :func:`yarn_inv_freq`)."""
    d = x.shape[-1]
    half = d // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
            if inv_freq is None else inv_freq)
    # positions (..., S) -> (..., S, 1, 1) broadcast over heads and dims
    ang = positions[..., :, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's rotary frequencies over ``dim`` rope dims, as
    ``DeepseekV2YarnRotaryEmbedding`` defines them: dims that turn fewer
    than ``beta_slow`` times over the original context are interpolated
    (divided by ``factor``), those that turn more than ``beta_fast``
    times keep their frequency, and a linear ramp joins the two."""
    import math

    def turns(n):          # the dim index that turns n times
        return dim * math.log(original / (n * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention magnitude ``0.1 * mscale * ln(factor) + 1``."""
    import math
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def gelu_mlp(x, w1, w2):
    return jax.nn.gelu(x @ w1) @ w2


class Initializer:
    """Deterministic, cheap param init (split-by-path fold-in)."""

    def __init__(self, key: jax.Array, scale: float = 0.02):
        self.key = key
        self.scale = scale
        self._n = 0

    def __call__(self, *shape, scale: Optional[float] = None,
                 dtype=jnp.float32) -> jnp.ndarray:
        self._n += 1
        k = jax.random.fold_in(self.key, self._n)
        s = self.scale if scale is None else scale
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    def zeros(self, *shape, dtype=jnp.float32) -> jnp.ndarray:
        self._n += 1
        return jnp.zeros(shape, dtype)


def dense_init(key, in_dim, out_dim, dtype=jnp.float32):
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * (in_dim ** -0.5)).astype(dtype)
