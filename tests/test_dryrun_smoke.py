"""Dry-run path guard: one real cell lowers + compiles against the
production 16x16 mesh in a subprocess (512 simulated devices)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.infra


def test_dryrun_single_cell(tmp_path):
    out_json = tmp_path / "cell.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "rwkv6-7b", "--shape", "long_500k",
         "--out", str(out_json)],
        capture_output=True, text=True, cwd=os.getcwd(), env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out_json))[0]
    assert rec["status"] == "ok"
    assert rec["per_device"]["peak_bytes"] < 16 * 2 ** 30
    assert rec["flops"] > 0


def test_dryrun_one_chip_share_with_latent_state(tmp_path):
    """One chip's share of DeepSeek-V2-Lite served with PIM FFNs: three
    layers holding 8 of 64 experts, batch 32 over a 4096-position cache;
    the decode state is the latent (576 floats a position and layer) and
    the float weights kept beside the plan are counted."""
    out_json = tmp_path / "cell.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "deepseek-v2-lite", "--shape", "decode_32k",
         "--one-chip", "--batch", "32", "--seq-len", "4096",
         "--dtype", "float32", "--override", json.dumps(
             {"n_layers": 3, "moe": {"experts_held": 8},
              "pim_linear_mode": "pim", "pim_block_mode": "ffn"}),
         "--out", str(out_json)],
        capture_output=True, text=True, cwd=os.getcwd(), env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out_json))[0]
    assert rec["status"] == "ok" and rec["mesh"] == "1x1"
    pd = rec["per_device"]
    assert pd["state_bytes"] == 3 * (32 * 4096 * 576 * 4 + 4)  # + lengths
    assert pd["kept_float_bytes"] > 0
    assert pd["peak_bytes"] >= pd["argument_bytes"] + pd["kept_float_bytes"]
