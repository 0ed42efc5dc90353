"""The PIM linear in ``repro.pim``: bit for bit the exact quantized
product, the only PIM linear the decode step reaches (it traces without
importing the crossbar engine), and the two modes a config accepts."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pim
from repro.configs import get_config
from repro.pim import quant

pytestmark = pytest.mark.pim

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("weight", ["float", "planned"])
@pytest.mark.parametrize("n_bits", [2, 4, 8, 12])
def test_linear_is_qmatmul_exact(n_bits, weight):
    """3-D activations through a float weight (planned in the call) and a
    weight planned beforehand: :func:`qmatmul_exact` of the quantized
    rows and the per-column quantized weight, bit for bit."""
    rng = np.random.default_rng(n_bits)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 40)) * 0.05, jnp.float32)
    want = jax.jit(lambda x, w: quant.qmatmul_exact(
        quant.quantize(x.reshape(-1, 64), n_bits),
        quant.quantize(w, n_bits, axis=0)).reshape(2, 3, 40))(x, w)
    if weight == "planned":
        w = jax.jit(quant.plan_weight, static_argnums=1)(w, n_bits)
    got = jax.jit(lambda x, w: pim.linear(x, w, n_bits))(x, w)
    assert got.shape == want.shape == (2, 3, 40)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# Traces the planned serve step of a PIM config; prints whether the
# crossbar engine was imported along the way.
_TRACE = textwrap.dedent("""
    import dataclasses, sys
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, plan_weights
    from repro.train import make_serve_step
    arch, block = sys.argv[1], sys.argv[2]
    cfg = get_config(arch, smoke=True).scaled(n_layers=2)
    cfg = dataclasses.replace(cfg, pim_linear_mode="pim", pim_linear_bits=8,
                              pim_block_mode=block)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    plan = jax.eval_shape(lambda p: plan_weights(cfg, p), params)
    states = jax.eval_shape(lambda: model.init_decode_state(2, 8))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    serve_step, _ = make_serve_step(model, make_host_mesh(1))
    text = jax.jit(serve_step).lower(plan, states, tok, tok).as_text()
    assert "xi8" in text
    print("engine imported:", "repro.engine" in sys.modules)
""")


@pytest.mark.parametrize("arch,block", [
    ("deepseek-7b", "none"), ("deepseek-7b", "ffn"), ("deepseek-7b", "full"),
    ("deepseek-v2-lite", "ffn")], ids=["head", "ffn", "full", "mla-moe"])
def test_decode_step_traces_without_the_engine(arch, block):
    """The head, FFN and attention scopes, and DeepSeek-V2-Lite's held
    experts under the FFN scope: a fresh process traces the planned
    decode step on int8 weights and never imports ``repro.engine``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [SRC] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run([sys.executable, "-c", _TRACE, arch, block],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "engine imported: False" in proc.stdout, proc.stdout


def test_fake_mode_is_refused():
    cfg = get_config("deepseek-7b", smoke=True)
    with pytest.raises(ValueError, match="'off' or 'pim'"):
        dataclasses.replace(cfg, pim_linear_mode="fake")
