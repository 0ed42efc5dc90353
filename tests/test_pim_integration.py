"""Closes the loop: the PIM linear's int matmul == cycle-accurate simulator.

The chain: float layer -> quantized ints -> (a) qmatmul_exact /
(b) the in-memory MultPIM-MAC simulator — both must agree bit-for-bit
on the integer accumulation.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matvec import matvec as pim_matvec
from repro import pim
from repro.pim import gemms_from_config, plan_model

pytestmark = pytest.mark.pim


def test_pim_linear_matches_simulator():
    """8-bit PIM linear integer accumulation == the crossbar simulator's
    full-precision fixed-point mat-vec, element for element."""
    n_bits = 8
    rng = np.random.default_rng(0)
    rows, k = 4, 5
    # unsigned operand tiles bounded so the 2N-bit carry-save accumulator
    # cannot overflow (k * 63^2 < 2^16), matching deployment scaling
    xi = rng.integers(0, 64, (rows, k))
    wi = rng.integers(0, 64, (k, 3))
    # simulator: one output column at a time (Fig. 5 layout)
    sim = np.zeros((rows, wi.shape[1]), dtype=object)
    for j in range(wi.shape[1]):
        col, _ = pim_matvec(xi.astype(object),
                            wi[:, j].astype(object), n_bits)
        sim[:, j] = col
    direct = xi.astype(np.int64) @ wi.astype(np.int64)
    assert (sim.astype(np.int64) == direct).all()


def test_pim_linear_quant_error_small():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 96)), jnp.float32)
    yf = x @ w
    yp = pim.linear(x, w, 8)
    rel = float(jnp.linalg.norm(yp - yf) / jnp.linalg.norm(yf))
    assert rel < 0.02


def test_planner_on_real_arch():
    from repro.configs import get_config
    cfg = get_config("deepseek-7b")
    plan = plan_model(gemms_from_config(cfg, batch_tokens=1), n_bits=8)
    assert plan.total_cycles > 0
    assert plan.speedup_vs_floatpim > 5.0      # Table III scaled up
    assert "TOTAL" in plan.summary()


def test_planner_moe_counts_active_experts():
    from repro.configs import get_config
    cfg = get_config("deepseek-moe-16b")
    plan = plan_model(gemms_from_config(cfg), n_bits=8)
    names = [g.name for g in plan.gemms]
    assert "moe.ffn" in names and "moe.router" in names


def test_planner_mla_and_held_experts():
    """DeepSeek-V2-Lite's step inventory: latent attention's four
    products (wkv_b's absorbed ones take its weights once a token) and,
    with 8 of 64 experts held, six picks and two shared a MoE layer."""
    import dataclasses

    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=8))
    g = {x.name: x for x in gemms_from_config(cfg)}
    assert {n for n in g if n.startswith("attn.")} == {
        "attn.q", "attn.kv_a", "attn.kv_b", "attn.o"}
    assert (g["attn.kv_b"].k, g["attn.kv_b"].n) == (512, 16 * 256)
    assert g["moe.ffn"].count == 26 * 8
    assert g["moe.router"].n == 64
