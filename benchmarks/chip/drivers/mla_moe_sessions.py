"""Greedy decode sessions of an MLA + MoE decoder (DeepSeek-V2-Lite)
through the program's serve step: :mod:`decode_sessions`' cell, with

* the configuration's keys mapped to the program's: the published
  ``intermediate_size`` is the dense layer's width and
  ``moe_intermediate_size`` the experts' (the program's ``d_ff``);
  latent attention, YaRN, the gating (``scoring_func``,
  ``norm_topk_prob``) and the chip's share of the routed experts
  (``n_routed_experts`` held here of ``router_outputs``, from
  ``expert_offset``);
* the latent's RMSNorm (``kv_norm``) among the zero-initialized norms;
* a prefill in slices of ``prefill_batch`` sequences (each a call of the
  program's forward pass), each slice's decode state written into the
  batch's in place, so the prefill's transient stays a slice's;
* the counts of :mod:`counting_mla_moe`, and ``expert_bytes``, the held
  experts' least bytes over the window;
* the check against :mod:`reference.mla_moe_decoder`, given the same
  prefill slices; a control may also renormalize the gates
  (``"renormalize": true``, the program's former gating).
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import counting_mla_moe as counting  # noqa: E402
from harness import Check, load_module  # noqa: E402
from reference import mla_moe_decoder as ref  # noqa: E402

base = load_module(HERE / "decode_sessions.py", "mla_moe_decode_sessions")
base.NORMS = (*base.NORMS, "kv_norm")

FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "moe_intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}
MOE_FIELDS = {"router_outputs": "n_experts", "num_experts_per_tok": "top_k",
              "n_shared_experts": "n_shared",
              "intermediate_size": "d_ff_dense",
              "n_routed_experts": "experts_held",
              "expert_offset": "expert_offset"}
MLA_FIELDS = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim")


def program_config(c: dict):
    """The program's ModelConfig of configuration ``c``."""
    import dataclasses

    from repro.configs import MLAConfig, RopeScaling, get_config
    cfg = get_config(c["program_config"])
    if c["scoring_func"] != "softmax":
        raise ValueError(f"unknown scoring_func {c['scoring_func']!r}")
    moe = dataclasses.replace(
        cfg.moe, scoring="topk_softmax" if c["norm_topk_prob"]
        else "softmax", **{f: c[k] for k, f in MOE_FIELDS.items()})
    rs = c["rope_scaling"]
    dense = c["first_k_dense_replace"]
    return cfg.scaled(
        **{f: c[k] for k, f in FIELDS.items()}, moe=moe,
        mla=MLAConfig(**{k: c[k] for k in MLA_FIELDS}),
        rope_scaling=RopeScaling(
            factor=rs["factor"],
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        layer_pattern="d" * dense + "m" * (c["num_hidden_layers"] - dense))


class Cell(base.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.prefill_batch = traffic["prefill_batch"]
        if self.batch % self.prefill_batch:
            raise ValueError("batch is not a multiple of prefill_batch")

    def model_config(self):
        import dataclasses
        cfg = program_config(self.config)
        if self.pim:
            cfg = dataclasses.replace(
                cfg, pim_linear_mode="pim", pim_linear_bits=self.pim["bits"],
                pim_block_mode=self.pim["block_mode"])
        return cfg

    def _prefill(self, model):
        """(first tokens (B, 1), prefilled decode state), slice by slice."""
        import jax
        import jax.numpy as jnp

        pb, clen = self.prefill_batch, self.cache_len

        def put(full, new, start):
            axes = [i for i, (a, b) in enumerate(zip(full.shape, new.shape))
                    if a != b]
            if not axes:                     # the whole batch, or a length
                return new.astype(full.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                full, new.astype(full.dtype), start, axes[0])

        def one(params, prompts, states, start):
            fresh = model.init_decode_state(pb, clen)
            logits, part = model.forward(params, prompts, states=fresh)
            states = jax.tree.map(lambda f, n: put(f, n, start), states, part)
            return (jnp.argmax(logits[:, -1:], -1).astype(jnp.int32),
                    states)

        def empty():          # the structure that the forward pass returns
            states = model.init_decode_state(self.batch, clen)
            return {k: v for k, v in states.items() if k != "enc_out"}

        step = jax.jit(one, donate_argnums=(2,))
        states = jax.jit(empty)()
        toks = []
        for start in range(0, self.batch, pb):
            tok, states = step(self.params, self.prompts[start:start + pb],
                               states, jnp.int32(start))
            toks.append(tok)
        return jnp.concatenate(toks, axis=0), states

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train import make_serve_step

        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        cfg = self.model_config()
        self.model = model = build_model(cfg)
        b, plen = self.batch, self.prompt_len
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.params = base.make_params(shapes, self.seed, cfg.d_model)
        rng = np.random.default_rng(self.seed)
        self.prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, plen)), jnp.int32)
        self.tok0, self.prefilled = self._prefill(model)
        self.copy = jax.jit(lambda s: jax.tree.map(jnp.copy, s))
        self.positions = [jnp.full((b, 1), plen + i, jnp.int32)
                          for i in range(self.decode_len)]
        _, jit_for = make_serve_step(model, make_host_mesh(1))
        self.step = jit_for(self.params, self.prefilled,
                            {"token": self.tok0,
                             "position": self.positions[0]})
        states = self.copy(self.prefilled)
        tok = self.tok0
        for pos in self.positions[:2]:      # compile, then a warm call
            tok, states = self.step(self.params, states, tok, pos)
            np.asarray(tok)
        del states
        self.served = [np.asarray(self.tok0)]

    def window(self, seconds: float):
        e2e, counts = super().window(seconds)
        c, bits = self.config, self.pim["bits"] if self.pim else 0
        scopes = self.pim_scopes()
        ctx = [self.prompt_len + k % self.decode_len + 1
               for k in range(counts["steps"])]
        counts["flops"] = float(sum(counting.token_flops(c, x)
                                    for x in ctx) * self.batch)
        counts["bytes"] = float(sum(counting.step_bytes(
            c, self.batch, x, bits, scopes) for x in ctx))
        counts["expert_bytes"] = float(
            counting.expert_bytes(c, bits) * counts["steps"])
        return e2e, counts

    def check(self, control: Optional[str] = None) -> Check:
        """As :meth:`decode_sessions.Cell.check`, against
        :mod:`reference.mla_moe_decoder` with the prefill's slices."""
        sessions = self.sessions()
        first = sessions[0]
        disagree = sum(not np.array_equal(s, first[:, :s.shape[1]])
                       for s in sessions[1:])
        kw = dict(pim_scopes=self.pim_scopes(),
                  bits=self.pim["bits"] if self.pim else 8,
                  precision=self.config["matmul_precision"],
                  prefill_batch=self.prefill_batch)
        picks = []
        if control is not None:
            c = {c["name"]: c for c in self.traffic["controls"]}[control]
            picks = [ref.readings(
                self.params, self.config, self.prompts, first,
                **dict(kw, bits=c.get("bits", kw["bits"]),
                       precision=c.get("precision", kw["precision"])),
                renormalize=c.get("renormalize", False))["argmax"]]
        r = ref.readings(self.params, self.config, self.prompts, first,
                         extra=picks, **kw)
        gaps = r["best"] - (r["extra"][0] if picks else r["served"])
        lim = self.limits
        chk = Check(attempted=self.tokens,
                    failed=int(np.sum(gaps > lim["max_logit_gap"])))
        chk.numbers["max_logit_gap"] = [float(gaps.max()),
                                        lim["max_logit_gap"]]
        chk.numbers["sessions_disagreeing"] = [
            int(disagree), lim["sessions_disagreeing"]]
        chk.readings["tokens_checked"] = int(gaps.size)
        chk.readings["tokens_off_argmax"] = int(np.sum(gaps > 0))
        chk.readings["mean_logit_gap"] = float(gaps.mean())
        return chk
