"""The decoder reference agrees with the program's own logits on the CPU
at a cut size (two layers, a 512-token vocabulary): the prefill's at the
prompt's last position and each decode step's through the KV cache,
with the FFN and the LM head in float32 and under MultPIM 8-bit
semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_DECODER
import harness
from reference import decoder as ref

decode_sessions = harness.load_module(
    harness.HERE / "drivers" / "decode_sessions.py")


@pytest.mark.parametrize("pim", [None, {"bits": 8, "block_mode": "ffn"}])
def test_reference_matches_program_logits(pim):
    cfg = dict(TINY_DECODER)
    traffic = {"batch": 3, "prompt_len": 6, "decode_len": 5,
               "cache_len": 12, "pim": pim, "limits": {}}
    cell = decode_sessions.Cell(cfg, traffic, seed=2**31 + 5)
    mcfg = cell.model_config()
    from repro.models import build_model
    model = build_model(mcfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = decode_sessions.make_params(shapes, 2**31 + 5, mcfg.d_model)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, mcfg.vocab_size, (3, 6)),
                          jnp.int32)
    with jax.default_matmul_precision("highest"):
        states = model.init_decode_state(3, 12)
        logits, states = model.forward(params, prompts, states=states)
        want = [np.asarray(logits[:, -1])]
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        served = [np.asarray(tok)]
        for i in range(4):
            pos = jnp.full((3, 1), 6 + i, jnp.int32)
            step_logits, states = model.decode_step(params, tok, pos,
                                                    states)
            want.append(np.asarray(step_logits[:, -1]))
            tok = jnp.argmax(step_logits[:, -1:], -1).astype(jnp.int32)
            served.append(np.asarray(tok))
    served = np.concatenate(served, axis=1)            # (3, 5)
    want = np.stack(want, axis=1)                      # (3, 5, V)
    scopes = mcfg.pim_scopes() if pim else ()
    r = ref.readings(params, cfg, prompts, served, pim_scopes=scopes)
    np.testing.assert_allclose(r["best"], want.max(-1), rtol=0, atol=2e-5)
    np.testing.assert_array_equal(r["argmax"], want.argmax(-1))
    np.testing.assert_allclose(r["served"], np.take_along_axis(
        want, served[..., None], -1)[..., 0], rtol=0, atol=2e-5)
