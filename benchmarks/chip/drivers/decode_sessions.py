"""Greedy decode sessions of one batch through the program's serve step.

Set-up makes the weights on the device in one jitted call from the seed
(the program's parameter layout, its own values: normal, scaled by
``fan_in^-1/2``; ``d_model^-1/2`` for the embedding; norm weights zero),
draws ``batch`` prompts of ``prompt_len`` tokens uniformly over the
vocabulary from the seed, prefills them with the program's forward pass
(jitted here) into a cache of ``cache_len`` positions, and compiles the
program's serve step (``repro.train.make_serve_step``) and a copy of the
prefilled state.

The window runs the serve step back to back, each step ending with the
batch's tokens on the host. A session decodes ``decode_len`` tokens
after the prompt, then restarts from a fresh copy of the prefilled
state, so every window decodes the same positions in the same order.
The window ends at the first step boundary after ``seconds``.

End-to-end: ``decode_tokens_per_s`` (tokens of every step over the
window's time) and ``itl_p95_ms`` (95th percentile of the time from one
step's tokens on the host to the next's, over every step). Counts:
``steps``, ``tokens``, ``flops`` and ``bytes`` (:mod:`counting`, at each
step's context), ``window_s``.

The check runs after the program's state is freed: the plain reference
(:mod:`reference.decoder`) over the prompts and the first session's
served tokens. Compared: ``max_logit_gap``, the widest gap by which a
served token's reference logit lies below the reference's best at its
position; and
``sessions_disagreeing``, the sessions of the window whose tokens
differ from the first session's. A control (the traffic file's
``controls``: the reference at a lower precision of the float products
or of the PIM linears) puts its own first choices in the served tokens'
place and is compared the same way.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counting  # noqa: E402
from harness import Check  # noqa: E402
from reference import decoder as ref  # noqa: E402

NORMS = ("ln1", "ln2", "lnx", "qn", "kn", "final_norm", "norm")
# Configuration keys (the published config.json's names) -> the
# program's ModelConfig fields.
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}


def seed_key(seed: int):
    """A JAX key from every bit of a non-negative seed of up to 64 bits."""
    import jax
    seed %= 1 << 64
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def make_params(shapes, seed: int, d_model: int):
    """Weights in the layout of ``shapes`` (a pytree of ShapeDtypeStruct),
    made on the device by one jitted call."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if name in NORMS:
                out.append(jnp.zeros(sd.shape, sd.dtype))
                continue
            scale = (d_model if name == "embed" else sd.shape[-2]) ** -0.5
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, sd.shape, jnp.float32)
                        * scale).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(seed_key(seed))


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.batch = traffic["batch"]
        self.prompt_len = traffic["prompt_len"]
        self.decode_len = traffic["decode_len"]
        self.cache_len = traffic["cache_len"]
        if self.prompt_len + self.decode_len > self.cache_len:
            raise ValueError("prompt_len + decode_len exceeds cache_len")
        self.pim = traffic.get("pim")
        self.limits = traffic["limits"]

    def model_config(self):
        import dataclasses

        from repro.configs import get_config
        c = self.config
        cfg = get_config(c["program_config"]).scaled(
            **{FIELDS[k]: c[k] for k in FIELDS})
        if self.pim:
            cfg = dataclasses.replace(
                cfg, pim_linear_mode="pim", pim_linear_bits=self.pim["bits"],
                pim_block_mode=self.pim["block_mode"])
        return cfg

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
        b, plen, clen = self.batch, self.prompt_len, self.cache_len
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.params = make_params(shapes, self.seed, cfg.d_model)
        rng = np.random.default_rng(self.seed)
        self.prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, plen)), jnp.int32)

        def prefill(params, prompts):
            states = model.init_decode_state(b, clen)
            logits, states = model.forward(params, prompts, states=states)
            return (jnp.argmax(logits[:, -1:], -1).astype(jnp.int32),
                    states)

        self.tok0, self.prefilled = jax.jit(prefill)(self.params,
                                                     self.prompts)
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
        steps, itl = [], []
        states, tok, i = self.copy(self.prefilled), self.tok0, 0
        t0 = t_prev = time.perf_counter()
        while True:
            tok, states = self.step(self.params, states, tok,
                                    self.positions[i])
            host = np.asarray(tok)
            t = time.perf_counter()
            itl.append(t - t_prev)
            t_prev = t
            steps.append(i)
            self.served.append(host)
            i += 1
            if i == self.decode_len:
                states, tok, i = self.copy(self.prefilled), self.tok0, 0
                self.served.append(np.asarray(self.tok0))
            if t - t0 >= seconds:
                break
        self.states = states
        self.tokens = len(steps) * self.batch
        elapsed = t_prev - t0
        c = self.config
        ctx = [self.prompt_len + i + 1 for i in steps]
        scopes = self.pim_scopes()
        counts = {
            "steps": len(steps), "tokens": self.tokens,
            "window_s": elapsed,
            "flops": float(sum(counting.decoder_token_flops(c, x)
                               for x in ctx) * self.batch),
            "bytes": float(sum(counting.decoder_step_bytes(
                c, self.batch, x, self.pim["bits"] if self.pim else 0,
                scopes) for x in ctx)),
        }
        e2e = {"decode_tokens_per_s": counts["tokens"] / elapsed,
               "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3}
        return e2e, counts

    def pim_scopes(self):
        return self.model_config().pim_scopes() if self.pim else ()

    def release(self) -> None:
        self.states = self.prefilled = self.step = self.copy = None
        self.positions = None

    def sessions(self):
        """The window's sessions as (B, tokens) arrays, prefill token
        first; the last one may be cut short."""
        per = self.decode_len + 1
        out = self.served
        return [np.concatenate(out[i:i + per], axis=1)
                for i in range(0, len(out), per)]

    @property
    def controls(self):
        return [c["name"] for c in self.traffic.get("controls", [])]

    def check(self, control: Optional[str] = None) -> Check:
        """The first session's served tokens against the reference at the
        stated precisions. With ``control``, the tokens that the
        reference at that control's lower precision puts first, at each
        of the same positions, take the served tokens' place."""
        sessions = self.sessions()
        first = sessions[0]
        disagree = sum(not np.array_equal(s, first[:, :s.shape[1]])
                       for s in sessions[1:])
        scopes = self.pim_scopes()
        bits = self.pim["bits"] if self.pim else 8
        precision = self.config["matmul_precision"]
        picks = []
        if control is not None:
            c = {c["name"]: c for c in self.traffic["controls"]}[control]
            picks = [ref.readings(self.params, self.config, self.prompts,
                                  first, pim_scopes=scopes,
                                  bits=c.get("bits", bits),
                                  precision=c.get("precision", precision)
                                  )["argmax"]]
        r = ref.readings(self.params, self.config, self.prompts, first,
                         pim_scopes=scopes, bits=bits, precision=precision,
                         extra=picks)
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
