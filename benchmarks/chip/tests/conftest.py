"""Put the benchmark and the program on the path; tiny cells for the CPU."""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

TINY_DECODER = {
    "name": "tiny-decoder", "kind": "decoder",
    "program_config": "deepseek-7b",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "matmul_precision": "highest",
}
TINY_SESSIONS = {
    "driver": "decode_sessions", "batch": 4, "prompt_len": 4,
    "decode_len": 24, "cache_len": 32, "pim": {"bits": 8, "block_mode": "ffn"},
    "limits": {"max_logit_gap": 0.005, "sessions_disagreeing": 0},
    "controls": [{"name": "pim_bits_4", "bits": 4},
                 {"name": "matmul_high", "precision": "high"},
                 {"name": "matmul_default", "precision": "default"}],
}


@pytest.fixture
def decode_cell():
    wl = {"name": "deepseek-7b.decode-pim", "chips": 1}
    return wl, dict(TINY_DECODER), dict(TINY_SESSIONS)
