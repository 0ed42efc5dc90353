"""The deepseek-v2-lite cell's pieces on the CPU: its counts against
shapes worked by hand, its configuration file against the registered
model, the held experts' roofline reader, and its controls at a tiny
size (the plain reference one step below what the configuration states
comes out not correct where the program's own run comes out correct)."""
import json
import time

import pytest

import harness
from conftest import CHIP

SEED = 2**31 + 1

V2_LITE_9 = json.loads((harness.HERE / "configs" / "deepseek-v2-lite.json")
                       .read_text())


def test_mla_moe_weights_by_kind():
    import counting_mla_moe as c
    lin = c.linears(V2_LITE_9)
    # q 2048x3072, kv_a 2048x576, kv_b 512x4096, o 2048x2048, 9 layers
    assert lin["attn"][0] == 9 * (6_291_456 + 1_179_648 + 2_097_152
                                  + 4_194_304)
    assert lin["dense"] == (3 * 2048 * 10944, 2 * 10944 + 2048)
    assert lin["shared"] == (8 * 3 * 2048 * 2816, 8 * (2 * 2816 + 2048))
    assert lin["experts"] == (8 * 8 * 3 * 2048 * 1408,
                              8 * 8 * (2 * 1408 + 2048))
    assert lin["router"] == (8 * 2048 * 64, 8 * 64)
    assert lin["head"] == (2048 * 102400, 102400)


def test_mla_moe_step_bytes_and_flops():
    """int8 FFNs, held experts (every one, one scale a stack) and head,
    float32 attention and router, the latent cache (576 floats a
    position and layer) up to the step's position, the embedding rows;
    a token's FLOPs take 6 of 64 experts' share of the 8 held."""
    import counting_mla_moe as c
    lin = c.linears(V2_LITE_9)
    experts = lin["experts"][0] + 3 * 4 * 8
    assert c.expert_bytes(V2_LITE_9, 8) == experts
    pim = sum(lin[k][0] + 4 * lin[k][1] for k in ("dense", "shared", "head"))
    floats = 4 * (lin["attn"][0] + lin["router"][0])
    cache = 9 * 32 * 576 * 4 * 3841
    got = c.step_bytes(V2_LITE_9, 32, 3841, 8, ("ffn", "head"))
    assert got == floats + pim + experts + cache + 32 * 2048 * 4
    w = sum(n for k, (n, _) in lin.items() if k != "experts")
    want = 2 * (w + lin["experts"][0] * 6 / 64) \
        + 2 * 3841 * 16 * (2 * 512 + 64) * 9
    assert c.token_flops(V2_LITE_9, 3841) == pytest.approx(want)


def test_mla_moe_config_maps_to_the_registered_model():
    """The configuration file, as the driver maps it, is the registered
    deepseek-v2-lite cut to 9 layers holding experts 0-7."""
    import dataclasses

    from repro.configs import get_config
    drv = harness.load_module(harness.HERE / "drivers"
                              / "mla_moe_sessions.py")
    want = get_config("deepseek-v2-lite")
    want = dataclasses.replace(
        want, n_layers=9, moe=dataclasses.replace(want.moe, experts_held=8))
    got = drv.program_config(V2_LITE_9)
    assert got.layer_kinds() == want.layer_kinds() == ("d",) + ("m",) * 8
    assert dataclasses.replace(got, layer_pattern=want.layer_pattern) == want


def test_moe_experts_roofline_share():
    reader = harness.reader("moe_experts_hbm_roofline")
    # 0.819 GB in 2 ms at 819 GB/s is half the roofline
    assert reader.share(0.819e9, 2.0, 819e9) == pytest.approx(50.0)
    assert reader.share(1.0, None, 819e9) is None
    assert reader.read(harness.Run({}, {"steps": 4}, [], None, {})) is None


def _tiny_mla_moe():
    config = json.loads((CHIP / "configs" / "deepseek-v2-lite.json")
                        .read_text())
    config.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_attention_heads=4, num_key_value_heads=4,
        num_hidden_layers=3, vocab_size=512, n_routed_experts=2,
        router_outputs=8, num_experts_per_tok=3, n_shared_experts=1,
        expert_offset=2)
    traffic = json.loads((CHIP / "traffic" / "decode-pim-4k.json")
                         .read_text())
    traffic.update(batch=4, prompt_len=6, decode_len=10, cache_len=16,
                   prefill_batch=2,
                   limits={"max_logit_gap": 0.005, "sessions_disagreeing": 0})
    return config, traffic


def run(control=None):
    wl = {"name": "deepseek-v2-lite.decode-pim-4k", "chips": 1}
    config, traffic = _tiny_mla_moe()
    return harness.run_cell(wl["name"], SEED, 0.5, False,
                            t_start=time.perf_counter(),
                            cell_override=(wl, config, traffic),
                            require_chip=False, control=control)


def test_mla_moe_controls_fail():
    """The MLA + MoE cell at a tiny size, prefilled in slices of 2: the
    program's run is correct; the reference with its PIM linears at 4
    bits, and with the top-k gates renormalized, are not."""
    assert run()["correct"]
    for control in ("pim_bits_4", "gates_renormalized"):
        r = run(control)
        assert r["correct"] is False, (control, r["compared"])
