"""Production training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 100 --seq-len 256 --global-batch 8 --ckpt-dir /tmp/ckpt

Wires together: config registry -> model -> host mesh -> sharded
train_step (remat + microbatching + ZeRO-1 + optional int8-EF gradient
compression) -> deterministic data pipeline -> checkpointing -> the
retrying fault-tolerant runner. The same driver runs the reduced smoke
configs on CPU and the full configs on a real pod (the dry-run proves
the latter lower+compile).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config
from repro.data import DataConfig, make_batch_fn
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime import setup_compile_cache
from repro.train import (RetryingRunner, latest_step, make_train_step,
                         restore_checkpoint)

# No logging side effects at import time: handlers attach only when
# main() calls obs.setup_logging() (see repro.obs.logging).
log = obs.get_logger("train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-file", default="")
    ap.add_argument("--override", default="",
                    help="JSON dict of ModelConfig overrides")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable span tracing and write a Chrome "
                         "trace-event file at exit")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="write the obs metrics snapshot as JSON")
    args = ap.parse_args()
    obs.setup_logging()
    setup_compile_cache()
    if args.trace:
        obs.enable()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.override:
        cfg = cfg.scaled(**json.loads(args.override))
    model = build_model(cfg, remat=True)
    mesh = make_host_mesh(args.model_parallel)
    log.info("arch=%s params~%.1fM mesh=%s", cfg.name,
             cfg.param_count() / 1e6, dict(mesh.shape))

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                          total_steps=args.steps)
    step_fn, init_fn, jit_for = make_train_step(
        model, opt_cfg, mesh, microbatches=args.microbatches,
        compress_grads=args.compress_grads)

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.global_batch)
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = (cfg.n_patches, cfg.d_model)
    if cfg.family == "encdec":
        extra["frames"] = (cfg.enc_frames, cfg.d_model)
    raw_batch_fn = make_batch_fn(dc, extra)

    params, opt_state, resid = init_fn(jax.random.PRNGKey(0))
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        restored, start = restore_checkpoint(
            args.ckpt_dir, {"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        log.info("resumed from step %d", start)

    jit_step = jit_for(params, jax.tree.map(jnp.asarray, raw_batch_fn(0)))

    def batch_fn(step):
        return jax.tree.map(jnp.asarray, raw_batch_fn(step))

    logf = open(args.log_file, "a") if args.log_file else None
    tokens_per_step = args.global_batch * args.seq_len

    if args.ckpt_dir:
        runner = RetryingRunner(step_fn=jit_step, batch_fn=batch_fn,
                                ckpt_dir=args.ckpt_dir,
                                ckpt_every=args.ckpt_every)
        t0 = time.time()
        (params, opt_state, resid), metrics = runner.run(
            (params, opt_state, resid), start, args.steps - start)
        log.info("done: %s (%.1fs)", metrics, time.time() - t0)
    else:
        step_ms = obs.histogram("train.step_ms")
        for step in range(start, args.steps):
            t0 = time.time()
            with obs.span("train.step", step=step):
                params, opt_state, resid, met = jit_step(
                    params, opt_state, resid, batch_fn(step))
                loss = float(met["loss"])
            dt = time.time() - t0
            step_ms.observe(dt * 1e3)
            if step % 10 == 0 or step == args.steps - 1:
                log.info("step %5d loss %.4f  %.2fs/step  %.0f tok/s",
                         step, loss, dt, tokens_per_step / dt)
            if logf:
                logf.write(f"{step},{loss:.5f},{dt:.3f}\n")
                logf.flush()
        obs.gauge("train.tokens_per_sec").set(
            tokens_per_step / max(step_ms.mean / 1e3, 1e-9)
            if step_ms.count else 0.0)
    if logf:
        logf.close()

    if args.trace:
        n_ev = obs.export_trace(args.trace)
        log.info("trace: %d events -> %s", n_ev, args.trace)
    if args.metrics:
        obs.write_metrics(args.metrics)
        log.info("metrics snapshot -> %s", args.metrics)


if __name__ == "__main__":
    main()
