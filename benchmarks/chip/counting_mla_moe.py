"""The work an MLA + MoE decode step's semantics require, from shapes.

As :mod:`counting`, for DeepSeek-V2-Lite's decoder as the cell runs it:
latent attention decoded in the absorbed form (the least work per
token: no per-head keys or values are made for the cached positions),
a dense first layer, then MoE layers that hold ``n_routed_experts`` of
the router's ``router_outputs`` experts.

Every held expert is counted as read on every step. In the deployment
this chip stands for, each MoE layer's experts lie over
``router_outputs / n_routed_experts`` chips (8), and every chip routes
its batch to all of them: 8 chips x 32 sequences x 6 picks / 64 experts,
about 24 rows reach each held expert a step, so each one's weights are
read. The FLOPs count the rows this chip's own batch sends to its
experts, ``batch * k * held / router_outputs`` in expectation.
"""
from __future__ import annotations

from typing import Dict

FLOAT_BYTES = {"float32": 4, "bfloat16": 2}


def _moe_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def linears(cfg: Dict) -> Dict[str, tuple]:
    """(weights, output columns) of each kind of linear, over the layers:
    ``attn`` (q, kv_a, kv_b, o), ``router``, ``dense`` (the dense FFNs),
    ``shared`` (the shared experts), ``experts`` (the held ones; every
    held expert), ``head``. The embedding is a gather."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    layers, moe = cfg["num_hidden_layers"], _moe_layers(cfg)
    dense = layers - moe
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held, e = cfg["n_routed_experts"], cfg["router_outputs"]
    attn_w = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    attn_c = h * (dn + dr) + (r + dr) + h * (dn + dv) + d
    return {"attn": (layers * attn_w, layers * attn_c),
            "router": (moe * d * e, moe * e),
            "dense": (dense * 3 * d * f, dense * (2 * f + d)),
            "shared": (moe * 3 * d * fs, moe * (2 * fs + d)),
            "experts": (moe * held * 3 * d * fe, moe * held * (2 * fe + d)),
            "head": (d * cfg["vocab_size"], cfg["vocab_size"])}


def token_flops(cfg: Dict, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` positions:
    two per weight of every linear it runs (of the held experts, the
    ``k * held / router_outputs`` it picks in expectation), and per
    layer and head ``2 * context * (2 * kv_lora_rank +
    qk_rope_head_dim)`` for the latent scores and weighted sum."""
    lin = linears(cfg)
    picks = cfg["num_experts_per_tok"] / cfg["router_outputs"]
    w = sum(n for k, (n, _) in lin.items() if k != "experts")
    w += lin["experts"][0] * picks
    attn = (2 * context * cfg["num_attention_heads"]
            * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * cfg["num_hidden_layers"])
    return 2 * w + attn


def expert_bytes(cfg: Dict, pim_bits: int = 0) -> float:
    """Least bytes a step reads of the held expert stacks: every held
    expert once, at ``pim_bits`` a weight and one float scale per stack
    (three stacks a layer) under PIM, else at ``torch_dtype``."""
    w, _ = linears(cfg)["experts"]
    if pim_bits:
        return w * pim_bits / 8 + 3 * 4 * _moe_layers(cfg)
    return w * FLOAT_BYTES[cfg["torch_dtype"]]


def step_bytes(cfg: Dict, batch: int, context: int, pim_bits: int = 0,
               pim_scopes=()) -> float:
    """Least HBM bytes of one decode step of ``batch`` sequences that
    attend to ``context`` positions, the new one included: every weight
    once at the stated precision (the ``ffn`` linears, which are the
    dense FFNs, the shared and the held experts, and the ``head`` at
    ``pim_bits`` under PIM, with one float scale a column, or a stack
    for the held experts), the latent and rope key of the ``context -
    1`` cached positions read and of the new one written, and the token
    embeddings read."""
    fb = FLOAT_BYTES[cfg["torch_dtype"]]
    pim = {"dense": "ffn", "shared": "ffn", "head": "head"}
    total = 0.0
    for kind, (w, cols) in linears(cfg).items():
        if kind == "experts":
            total += expert_bytes(cfg, pim_bits if "ffn" in pim_scopes
                                  else 0)
        elif pim.get(kind) in pim_scopes:
            total += w * pim_bits / 8 + cols * 4
        else:
            total += w * fb
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    cache = cfg["num_hidden_layers"] * batch * latent * fb * context
    return total + cache + batch * cfg["hidden_size"] * fb
