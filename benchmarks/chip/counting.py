"""The work a cell's semantics require, from its shapes alone.

These count what any implementation has to do, not what the program
does: a later change that stores 8-bit weights raises the share of a
roofline computed from them, and leaves the count as it is.
"""
from __future__ import annotations

from typing import Dict


def decoder_linears(cfg: Dict) -> Dict[str, tuple]:
    """(weights, output columns) of each kind of linear in a dense
    decoder: attention projections, FFN projections, LM head. The
    embedding is a gather and is not counted."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = hd * cfg["num_attention_heads"]
    kv = hd * cfg["num_key_value_heads"]
    layers = cfg["num_hidden_layers"]
    return {"attn": (layers * (d * q + 2 * d * kv + q * d),
                     layers * (q + 2 * kv + d)),
            "ffn": (layers * 3 * d * f, layers * (2 * f + d)),
            "head": (d * cfg["vocab_size"], cfg["vocab_size"])}


def decoder_token_flops(cfg: Dict, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` positions:
    two per weight of every linear, and 4 x q_dim x context per layer
    for scores and values."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    q = hd * cfg["num_attention_heads"]
    attn = 4 * q * context * cfg["num_hidden_layers"]
    return 2 * sum(w for w, _ in decoder_linears(cfg).values()) + attn


def decoder_step_bytes(cfg: Dict, batch: int, context: int,
                       pim_bits: int = 0, pim_scopes=()) -> float:
    """Least HBM bytes of one decode step of ``batch`` sequences that
    attend to ``context`` positions, the new one included: every weight
    once at the precision the configuration states (``torch_dtype``; for the
    linears of ``pim_scopes``, ``pim_bits`` a weight and one float
    scale a column), the keys and values of the ``context - 1`` cached
    positions read and of the new one written, and the token embeddings
    read."""
    fb = {"float32": 4, "bfloat16": 2}[cfg["torch_dtype"]]
    weights = 0.0
    for kind, (w, cols) in decoder_linears(cfg).items():
        if kind in pim_scopes:
            weights += w * pim_bits / 8 + cols * 4
        else:
            weights += w * fb
    d = cfg["hidden_size"]
    kv = d // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    cache = 2 * cfg["num_hidden_layers"] * batch * kv * fb * context
    return weights + cache + batch * d * fb
