"""End-to-end system tests: training loop convergence, serve loop, and
the paper-claims summary (the 'does the whole thing hang together' suite).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataConfig, make_batch_fn
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train import make_train_step

pytestmark = pytest.mark.system


def test_end_to_end_training_loss_decreases():
    """Real train_step (jit, shardings, microbatching, remat, ZeRO
    specs) on the host mesh: loss must drop on a repeating stream."""
    cfg = get_config("qwen3-8b", smoke=True)
    m = build_model(cfg, remat=True)
    mesh = make_host_mesh()
    step_fn, init_fn, jit_for = make_train_step(
        m, AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=60),
        mesh, microbatches=2)
    params, opt_state, resid = init_fn(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    bf = make_batch_fn(dc)
    fixed = jax.tree.map(jnp.asarray, bf(0))     # overfit one batch
    jit_step = jit_for(params, fixed)
    losses = []
    for _ in range(12):
        params, opt_state, resid, met = jit_step(params, opt_state, resid,
                                                 fixed)
        losses.append(float(met["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_end_to_end_training_with_compression():
    cfg = get_config("deepseek-7b", smoke=True)
    m = build_model(cfg)
    mesh = make_host_mesh()
    step_fn, init_fn, jit_for = make_train_step(
        m, AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=30), mesh,
        compress_grads=True)
    params, opt_state, resid = init_fn(jax.random.PRNGKey(0))
    assert resid is not None
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    bf = make_batch_fn(dc)
    fixed = jax.tree.map(jnp.asarray, bf(0))
    jit_step = jit_for(params, fixed)
    losses = []
    for _ in range(8):
        params, opt_state, resid, met = jit_step(params, opt_state, resid,
                                                 fixed)
        losses.append(float(met["loss"]))
    assert losses[-1] < losses[0]


def test_serve_loop_greedy_decode():
    from repro.train import make_serve_step
    cfg = get_config("gemma2-9b", smoke=True)
    m = build_model(cfg)
    mesh = make_host_mesh()
    serve, jit_for = make_serve_step(m, mesh)
    params = m.init(jax.random.PRNGKey(0))
    states = m.init_decode_state(2, 64)
    tok = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    batch = {"token": tok, "position": pos}
    jit_serve = jit_for(params, states, batch)
    toks = []
    for t in range(6):
        tok, states = jit_serve(params, states, tok, pos + t)
        toks.append(np.asarray(tok))
    assert all(t.shape == (2, 1) for t in toks)
    assert all((t >= 0).all() and (t < cfg.vocab_size).all() for t in toks)


def test_paper_claims_summary():
    """The one-screen reproduction check of every headline number."""
    from repro.core import ALGOS
    from repro.core.matvec import (floatpim_matvec_latency,
                                   matvec_latency_formula)
    lat32 = {k: v["latency"](32) for k, v in ALGOS.items()}
    area32 = {k: v["area"](32) for k, v in ALGOS.items()}
    assert lat32 == {"hajali": 12870, "rime": 2541, "multpim": 611,
                     "multpim-area": 899}                     # Table I
    assert area32 == {"hajali": 635, "rime": 468, "multpim": 441,
                      "multpim-area": 320}                    # Table II
    assert floatpim_matvec_latency(8, 32) == 109616           # Table III
    assert matvec_latency_formula(8, 32) == 4292


def test_serve_cli_smoke_with_depth_override(tmp_path):
    """The serve CLI takes train's ``--override`` JSON, so depth is cut
    through the normal entry point; the run decodes with the PIM LM head
    and holds its compile-once check (a violation exits non-zero). Run
    as a child process: the entry point sets JAX's compilation cache,
    here pointed at a scratch directory."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--override", '{"n_layers": 2}', "--batch", "2",
         "--prompt-len", "8", "--gen", "3", "--cache-len", "16"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stderr + proc.stdout
    assert "n_layers=2 " in out
    assert "compile-once verified" in out
