"""The operation and byte counters against shapes worked by hand."""
import json

import pytest

import counting
import harness

DEEPSEEK_6 = {"hidden_size": 4096, "intermediate_size": 11008,
              "num_attention_heads": 32, "num_key_value_heads": 32,
              "num_hidden_layers": 6, "vocab_size": 102400,
              "torch_dtype": "float32"}


def test_decoder_weights_by_kind():
    lin = counting.decoder_linears(DEEPSEEK_6)
    assert lin["attn"] == (6 * 4 * 4096 * 4096, 6 * 4 * 4096)
    assert lin["ffn"] == (6 * 3 * 4096 * 11008, 6 * (2 * 11008 + 4096))
    assert lin["head"] == (4096 * 102400, 102400)


def test_decoder_token_flops():
    # 2 x (402,653,184 + 811,597,824 + 419,430,400) weights, plus
    # 4 x 4096 x 769 x 6 for attention at a context of 769 positions.
    want = 2 * (402_653_184 + 811_597_824 + 419_430_400) + 75_595_776
    assert counting.decoder_token_flops(DEEPSEEK_6, 769) == want


def test_decoder_step_bytes():
    # float32 attention weights, 8-bit FFN and head plus a float32 scale
    # per output column, 769 positions of float32 keys and values for
    # 8 sequences over 6 layers, 8 embedding rows.
    attn = 402_653_184 * 4
    pim = (811_597_824 + 419_430_400) + 4 * (6 * (2 * 11008 + 4096)
                                             + 102400)
    cache = 2 * 6 * 8 * 4096 * 4 * 769
    got = counting.decoder_step_bytes(DEEPSEEK_6, 8, 769, 8,
                                      ("ffn", "head"))
    assert got == attn + pim + cache + 8 * 4096 * 4
    plain = counting.decoder_step_bytes(DEEPSEEK_6, 8, 769)
    assert plain == (402_653_184 + 811_597_824 + 419_430_400) * 4 \
        + cache + 8 * 4096 * 4


def test_peaks_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99")


def test_every_peak_has_a_source():
    table = json.loads((harness.HERE / "peaks.json").read_text())
    for kind, row in table["devices"].items():
        assert row["source"], kind
