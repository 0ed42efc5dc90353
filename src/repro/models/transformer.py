"""Model assembly: layer-pattern segmentation + scan-over-layers.

The layer pattern is decomposed into (prefix, repeating unit x n, suffix)
by :func:`stack_plan`. Unit slots are stacked along a leading axis and
executed with ``lax.scan`` so the lowered HLO is O(pattern) rather than
O(depth) — essential for compiling 30-52-layer models against a
512-device mesh on a 1-core CPU host, and exactly how production JAX LMs
(MaxText et al.) keep compile times flat.

Decode states are stacked with the same structure, so one pytree carries
the whole model's KV caches / recurrent states through ``lax.scan``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.pim.quant import STACK_AXES, plan_weight

from .blocks import (apply_block, init_block, init_state, pim_proj,
                     pim_stacks, pim_weights, writes_by_position)
from .layers import Initializer, rms_norm, softcap

__all__ = ["stack_plan", "init_params", "forward", "decode_step",
           "init_decode_state", "encode", "plan_weights"]


# ------------------------------------------------------------ planning ----
def stack_plan(cfg: ModelConfig) -> Tuple[Tuple[str, ...], Tuple[str, ...],
                                          int, Tuple[str, ...]]:
    """-> (prefix_kinds, unit_kinds, n_units, suffix_kinds)."""
    kinds = list(cfg.layer_kinds())
    best = (tuple(kinds), (), 0, ())      # fallback: all prefix
    best_cost = len(kinds)
    for p in range(0, min(4, len(kinds)) + 1):
        for u in range(1, 5):
            rest = kinds[p:]
            if len(rest) < u:
                continue
            unit = rest[:u]
            n = 0
            while (n + 1) * u <= len(rest) and rest[n * u:(n + 1) * u] == unit:
                n += 1
            suffix = rest[n * u:]
            cost = p + len(suffix) + (u if n > 1 else len(kinds))
            if n > 1 and cost < best_cost:
                best = (tuple(kinds[:p]), tuple(unit), n, tuple(suffix))
                best_cost = cost
    return best


def _stack(trees: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------- init ----
def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.float32) -> Dict[str, Any]:
    ini = Initializer(key)
    prefix, unit, n_units, suffix = stack_plan(cfg)
    params: Dict[str, Any] = {
        "embed": ini(cfg.vocab_size, cfg.d_model,
                     scale=cfg.d_model ** -0.5, dtype=dtype),
        "final_norm": ini.zeros(cfg.d_model, dtype=dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = ini(cfg.d_model, cfg.vocab_size,
                                scale=cfg.d_model ** -0.5, dtype=dtype)
    params["prefix"] = [init_block(cfg, ini, k) for k in prefix]
    params["scan"] = [
        _stack([init_block(cfg, ini, k) for _ in range(n_units)])
        for k in unit
    ]
    params["suffix"] = [init_block(cfg, ini, k) for k in suffix]

    if cfg.family == "encdec":
        enc_cfg = cfg.scaled(family="decoder")  # no cross-attn weights
        params["encoder"] = {
            "blocks": _stack([init_block(enc_cfg, ini, "g")
                              for _ in range(cfg.enc_layers)]),
            "norm": ini.zeros(cfg.d_model, dtype=dtype),
            "pos": ini(cfg.enc_frames, cfg.d_model, scale=0.02, dtype=dtype),
        }
    if cfg.family == "vlm":
        params["patch_proj"] = ini(cfg.d_model, cfg.d_model,
                                   scale=cfg.d_model ** -0.5, dtype=dtype)
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


# ---------------------------------------------------------- weight plan ----
def plan_weights(cfg: ModelConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with every weight that a ``pim``-mode linear of
    :func:`decode_step` would quantize replaced by its
    :class:`~repro.pim.quant.PlannedWeight` at ``cfg.pim_linear_bits``,
    stacked like the float weight; the other leaves are the same objects.

    The weights are those each block kind passes to
    :func:`~repro.models.blocks.pim_proj` (:func:`~.blocks.pim_weights`)
    under ``cfg.pim_scopes()``, planned per column, the MoE expert
    stacks it passes to ``repro.pim.ragged_linear``
    (:func:`~.blocks.pim_stacks`), planned with one scale a stack and
    layer, and the LM head; a tied head is planned as ``lm_head``, which
    :func:`decode_step` reads before ``embed.T``. The identity when no
    linear runs in ``pim`` mode. Traceable, so ``jax.eval_shape`` gives
    the plan's shapes.
    """
    scopes = cfg.pim_scopes()
    if not scopes:
        return params
    bits = cfg.pim_linear_bits
    # One program a weight: op by op, each temporary is a weight's size.
    plan_one = jax.jit(plan_weight, static_argnums=(1, 2))

    def planned(p, path, axis):
        key, *rest = path
        if key not in p:                  # e.g. w3 of a gelu MLP
            return p
        p = dict(p)
        p[key] = (planned(p[key], rest, axis) if rest
                  else plan_one(p[key], bits, axis))
        return p

    def block(p, kind):
        for weights, axis in ((pim_weights, -2), (pim_stacks, STACK_AXES)):
            for scope, paths in weights(cfg, kind).items():
                if scope in scopes:
                    for path in paths:
                        p = planned(p, path, axis)
        return p

    prefix, unit, _, suffix = stack_plan(cfg)
    out = dict(params)
    for group, kinds in (("prefix", prefix), ("scan", unit),
                         ("suffix", suffix)):
        out[group] = [block(p, k) for p, k in zip(params[group], kinds)]
    if "head" in scopes:
        head = (params["lm_head"] if "lm_head" in params
                else params["embed"].T)
        out["lm_head"] = plan_one(head, bits, -2)
    return out


# ------------------------------------------------------------- encoder ----
def encode(cfg: ModelConfig, params, frames: jnp.ndarray) -> jnp.ndarray:
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): non-causal self-attention blocks."""
    enc = params["encoder"]
    x = frames + enc["pos"][None, : frames.shape[1]]
    s = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (x.shape[0], s))

    def step(carry, blk):
        h = carry
        h, _ = apply_block(cfg.scaled(family="decoder"), "g", blk, h,
                           pos=pos, mode="encode")  # non-causal
        return h, None

    x, _ = jax.lax.scan(step, x, enc["blocks"])
    return rms_norm(x, enc["norm"], cfg.norm_eps)


# ------------------------------------------------------------- forward ----
def forward(cfg: ModelConfig, params, tokens: jnp.ndarray, *,
            extra_embed: Optional[jnp.ndarray] = None,
            enc_frames: Optional[jnp.ndarray] = None,
            states=None, mode: str = "full",
            positions: Optional[jnp.ndarray] = None,
            remat: bool = False):
    """Full-sequence forward. ``tokens`` (B, S) int32.

    ``extra_embed``: (B, P, D) patch/frame embeddings prepended to the
    token stream (VLM stub frontend). Returns (logits, new_states).
    """
    b, s = tokens.shape
    x = params["embed"][tokens] * (cfg.d_model ** 0.5 if cfg.family != "rwkv"
                                   else 1.0)
    if extra_embed is not None:
        x = jnp.concatenate(
            [extra_embed @ params["patch_proj"], x], axis=1)
        s = x.shape[1]
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        pos = positions
    enc_out = None
    if cfg.family == "encdec" and enc_frames is not None:
        enc_out = encode(cfg, params, enc_frames)

    prefix, unit, n_units, suffix = stack_plan(cfg)
    st = states if states is not None else {}
    new_states: Dict[str, Any] = {"prefix": [], "scan": None, "suffix": []}

    for i, kind in enumerate(prefix):
        x, ns = apply_block(cfg, kind, params["prefix"][i], x, pos=pos,
                            state=(st.get("prefix") or [None] * len(prefix))[i],
                            enc_out=enc_out, mode=mode)
        new_states["prefix"].append(ns)

    if n_units:
        scan_states = st.get("scan")

        def step(carry, xs):
            h = carry
            blks, states_u = xs
            out_states = []
            for j, kind in enumerate(unit):
                h, ns = apply_block(cfg, kind, blks[j], h, pos=pos,
                                    state=None if states_u is None
                                    else states_u[j],
                                    enc_out=enc_out, mode=mode)
                out_states.append(ns)
            return h, (out_states if states_u is not None else 0)

        if remat:
            step = jax.checkpoint(step)
        if scan_states is None:
            x, _ = jax.lax.scan(step, x, (params["scan"], None))
        else:
            x, out = jax.lax.scan(step, x, (params["scan"], scan_states))
            new_states["scan"] = out

    for i, kind in enumerate(suffix):
        x, ns = apply_block(cfg, kind, params["suffix"][i], x, pos=pos,
                            state=(st.get("suffix") or [None] * len(suffix))[i],
                            enc_out=enc_out, mode=mode)
        new_states["suffix"].append(ns)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", params["embed"].T)
    logits = pim_proj(cfg, x, head, scope="head")
    logits = softcap(logits, cfg.softcap_final)
    return logits, (new_states if states is not None else None)


# -------------------------------------------------------------- decode ----
def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=jnp.float32) -> Dict[str, Any]:
    prefix, unit, n_units, suffix = stack_plan(cfg)

    def one(kind):
        return init_state(cfg, kind, batch, cache_len, dtype)

    return {
        "prefix": [one(k) for k in prefix],
        "scan": [_stack([one(k) for _ in range(n_units)]) for k in unit]
        if n_units else None,
        "suffix": [one(k) for k in suffix],
        "enc_out": (jnp.zeros((batch, cfg.enc_frames, cfg.d_model), dtype)
                    if cfg.family == "encdec" else None),
    }


def decode_step(cfg: ModelConfig, params, token: jnp.ndarray,
                position: jnp.ndarray, states: Dict[str, Any]):
    """One-token serve step. token (B,1); position (B,1) absolute.

    A state with a time axis (:func:`~.blocks.writes_by_position`: KV
    and latent caches) has only the token's entries written, in place;
    a recurrent state is replaced whole. Tracing the step counts the
    layer states of each kind in ``kv_cache.position_writes`` and
    ``kv_cache.whole_writes`` (:data:`repro.obs.POSITION_WRITES`,
    :data:`repro.obs.WHOLE_WRITES`).
    """
    x = params["embed"][token] * (cfg.d_model ** 0.5 if cfg.family != "rwkv"
                                  else 1.0)
    enc_out = states.get("enc_out")
    prefix, unit, n_units, suffix = stack_plan(cfg)
    new_states = dict(states)
    new_states["prefix"] = []
    new_states["suffix"] = []

    def count(state, n):
        """Count ``n`` layer states like ``state``; whether they are
        written by position."""
        by_position = writes_by_position(state)
        obs.counter(obs.POSITION_WRITES if by_position
                    else obs.WHOLE_WRITES).inc(n)
        return by_position

    for i, kind in enumerate(prefix):
        count(states["prefix"][i], 1)
        x, ns = apply_block(cfg, kind, params["prefix"][i], x, pos=position,
                            state=states["prefix"][i], enc_out=enc_out,
                            mode="decode")
        new_states["prefix"].append(ns)

    if n_units:
        # The stacked states ride the scan CARRY, donated. A cache is
        # handed to its block whole, with the layer index: the block
        # writes the token's entries at (li, slot) and attends over layer
        # li of the stack, so no layer's cache is copied out or back. A
        # recurrent state is read at li and written back whole.
        by_position = [count(st, n_units) for st in states["scan"]]

        def step(carry, xs):
            h, scan_states = carry
            blks, li = xs
            out_states = []
            for j, kind in enumerate(unit):
                st = scan_states[j]
                if by_position[j]:
                    h, st = apply_block(cfg, kind, blks[j], h, pos=position,
                                        state=st, enc_out=enc_out,
                                        mode="decode", layer=li)
                    out_states.append(st)
                    continue
                with obs.scope(obs.KV_CACHE):
                    st_j = jax.tree.map(
                        lambda s: jax.lax.dynamic_index_in_dim(
                            s, li, 0, keepdims=False), st)
                h, ns = apply_block(cfg, kind, blks[j], h, pos=position,
                                    state=st_j, enc_out=enc_out,
                                    mode="decode")
                with obs.scope(obs.KV_CACHE):
                    out_states.append(jax.tree.map(
                        lambda s, n: jax.lax.dynamic_update_index_in_dim(
                            s, n.astype(s.dtype), li, 0), st, ns))
            return (h, out_states), None

        (x, out), _ = jax.lax.scan(
            step, (x, states["scan"]),
            (params["scan"], jnp.arange(n_units)))
        new_states["scan"] = out

    for i, kind in enumerate(suffix):
        count(states["suffix"][i], 1)
        x, ns = apply_block(cfg, kind, params["suffix"][i], x, pos=position,
                            state=states["suffix"][i], enc_out=enc_out,
                            mode="decode")
        new_states["suffix"].append(ns)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", params["embed"].T)
    logits = softcap(pim_proj(cfg, x, head, scope="head"),
                     cfg.softcap_final)
    return logits, new_states
