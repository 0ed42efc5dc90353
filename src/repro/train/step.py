"""Jitted train_step / serve_step factories with explicit shardings.

``make_train_step``: microbatched (gradient-accumulation) AdamW step.
Batch shards over (pod, data); params/optimizer state shard per the
partition rules; buffers are donated. ``lax.scan`` over microbatches
keeps the peak activation footprint to one microbatch — combined with
the per-layer remat scan this is what lets seq=4096 x batch=256 fit the
16 GB/chip budget.

``make_serve_step``: one-token decode against a sharded KV cache
(batch -> data, kv-heads -> model), cache buffers donated in place; the
PIM linears' weights are quantized once per weight set
(:class:`ServeStep`), not on every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.models.model import Model
from repro.models.transformer import plan_weights
from repro.optim.adamw import (AdamWConfig, OptState, adamw_init,
                               adamw_update)
from repro.optim.compress import ef_compress_tree
from repro.pim import PlannedWeight

from .sharding import (batch_shardings, param_shardings, state_shardings,
                       zero1_shardings, zero1_spec)

__all__ = ["make_train_step", "make_serve_step", "make_prefill",
           "ServeStep"]


def make_train_step(model: Model, opt_cfg: AdamWConfig, mesh, *,
                    microbatches: int = 1,
                    compress_grads: bool = False):
    """Returns (train_step, init_fn) — both jitted with explicit
    shardings against ``mesh``."""

    def init_fn(key, dtype=jnp.float32):
        params = model.init(key, dtype)
        opt = adamw_init(params)
        resid = (jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                              params) if compress_grads else None)
        return params, opt, resid

    def grads_microbatched(params, batch):
        """Gradient accumulation: value_and_grad runs *inside* the
        microbatch scan so only one microbatch's residuals are ever
        live (differentiating through the scan would store all of
        them)."""
        if microbatches == 1:
            return jax.value_and_grad(model.loss)(params, batch)
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

        def to_mb(x):
            y = x.reshape((microbatches, x.shape[0] // microbatches)
                          + x.shape[1:])
            # keep the per-microbatch rows sharded over (pod, data) —
            # without the constraint GSPMD re-lays the split batch out
            # 8x fatter per device.
            spec = P(None, dp, *([None] * (y.ndim - 2)))
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, spec))
        mb = jax.tree.map(to_mb, batch)

        def _z1(path, x):
            from .sharding import _leaf_name
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, zero1_spec(mesh, _leaf_name(path),
                                                  x.shape)))

        # ZeRO-1: the f32 grad accumulator shards over 'data' too — each
        # microbatch's gradient is reduce-scattered into it, so the
        # accumulator costs 1/dp of the full-precision gradient.
        g0 = jax.tree_util.tree_map_with_path(
            lambda p, x: _z1(p, jnp.zeros(x.shape, jnp.float32)), params)

        def body(acc, one):
            tot, gacc = acc
            l, g = jax.value_and_grad(model.loss)(params, one)
            gacc = jax.tree_util.tree_map_with_path(
                lambda p, a, b: _z1(p, a + b.astype(jnp.float32)), gacc, g)
            return (tot + l, gacc), None

        (total, gsum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), g0), mb)
        inv = 1.0 / microbatches
        return total * inv, jax.tree.map(lambda g: g * inv, gsum)

    def train_step(params, opt_state, residual, batch):
        loss, grads = grads_microbatched(params, batch)
        if compress_grads:
            grads, residual = ef_compress_tree(grads, residual)
        params, opt_state, metrics = adamw_update(opt_cfg, grads, opt_state,
                                                  params)
        metrics["loss"] = loss
        return params, opt_state, residual, metrics

    def jit_for(params_like, batch_like):
        ps = param_shardings(mesh, params_like)
        zs = zero1_shardings(mesh, params_like)     # ZeRO-1 m/v
        os_ = OptState(m=zs, v=zs, count=NamedSharding(mesh, P()))
        rs = zs if compress_grads else None
        bs = batch_shardings(mesh, batch_like)
        ms = {"loss": NamedSharding(mesh, P()),
              "grad_norm": NamedSharding(mesh, P()),
              "lr": NamedSharding(mesh, P())}
        return jax.jit(
            train_step,
            in_shardings=(ps, os_, rs, bs),
            out_shardings=(ps, os_, rs, ms),
            donate_argnums=(0, 1, 2),
        )
    return train_step, init_fn, jit_for


class ServeStep:
    """A jitted serve step behind a weight-stationary plan.

    ``step(params, states, token, position)`` plans ``params``
    (:func:`repro.models.transformer.plan_weights`) when their leaves are
    not the objects it last planned from, else reuses the stored plan:
    JAX arrays are immutable, so the same objects hold the same weights.
    It then runs the jitted ``serve_step(plan, states, token, position)``.
    It holds the params it planned from, and their plan. Counters:
    ``pim.weight_plans`` (plans made) and ``pim.plan_reuses`` (calls
    served from a stored plan).
    """

    def __init__(self, cfg, jitted, plan_shardings):
        self._jitted = jitted
        self._cfg = cfg
        self._shardings = plan_shardings
        self._source = None      # the leaves of the params last planned
        self._plan = None
        self._plans = obs.counter(obs.WEIGHT_PLANS)
        self._reuses = obs.counter(obs.PLAN_REUSES)

    def plan(self, params):
        """The stored plan of ``params``, made anew if they changed."""
        leaves = jax.tree_util.tree_leaves(params)
        if (self._source is not None and len(leaves) == len(self._source)
                and all(a is b for a, b in zip(leaves, self._source))):
            self._reuses.inc()
            return self._plan
        self._plan = None                 # free the old plan first
        self._plan = jax.tree.map(
            lambda w, sh: (jax.device_put(w, sh)
                           if isinstance(w, PlannedWeight) else w),
            plan_weights(self._cfg, params), self._shardings,
            is_leaf=lambda w: isinstance(w, PlannedWeight))
        self._source = leaves
        self._plans.inc()
        return self._plan

    def __call__(self, params, states, token, position):
        return self._jitted(self.plan(params), states, token, position)

    def lower(self, params_like, states_like, token, position):
        """Lower the jitted step for ``params_like`` (arrays or
        ``ShapeDtypeStruct`` leaves), planned in shapes only."""
        return self._jitted.lower(_plan_like(self._cfg, params_like),
                                 states_like, token, position)


def _plan_like(cfg, params_like):
    return jax.eval_shape(functools.partial(plan_weights, cfg), params_like)


def make_serve_step(model: Model, mesh):
    """Returns (serve_step, jit_for(params, states, batch)).

    ``serve_step(params, states, token, position)`` takes float or
    planned params; ``jit_for`` returns a :class:`ServeStep` that plans
    the weights once per weight set and jits ``serve_step`` on the plan.
    """

    def serve_step(params, states, token, position):
        logits, states = model.decode_step(params, token, position, states)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, states

    def jit_for(params_like, states_like, batch_like):
        plan_like = _plan_like(model.cfg, params_like)
        ps = param_shardings(mesh, plan_like)
        ss = state_shardings(mesh, states_like)
        bs = batch_shardings(mesh, batch_like)
        step = jax.jit(
            serve_step,
            in_shardings=(ps, ss, bs["token"], bs["position"]),
            out_shardings=(bs["token"], ss),
            donate_argnums=(1,),
        )
        # Shapes only, for obs.device_scopes() after a measured window.
        obs.register_program(step, plan_like, states_like,
                             batch_like["token"], batch_like["position"])
        return ServeStep(model.cfg, step, ps)
    return serve_step, jit_for


def make_prefill(model: Model, mesh):
    def prefill(params, batch):
        kwargs = {}
        if model.cfg.family == "vlm":
            kwargs["extra_embed"] = batch.get("patches")
        if model.cfg.family == "encdec":
            kwargs["enc_frames"] = batch.get("frames")
        logits, _ = model.forward(params, batch["tokens"], **kwargs)
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    def jit_for(params_like, batch_like):
        ps = param_shardings(mesh, params_like)
        bs = batch_shardings(mesh, batch_like)
        dp = bs["tokens"]
        return jax.jit(prefill, in_shardings=(ps, bs), out_shardings=dp)
    return prefill, jit_for
