#!/usr/bin/env python3
"""On-chip smoke test: the main paths on one TPU, every result checked.

    python chip_smoke.py [--seed 0]

Runs in one process, from the root of a checkout, in this order:

1. **crossbar** — one ``Engine.compile("multpim", 32)`` pass over 2^20
   crossbar rows (32,768 packed words x 460 columns) on
   ``jax:pack=true`` and on ``pallas:pack=true``, checked bit for bit
   against numpy ``uint64`` products; then one co-scheduled
   ``compile_batch("mac", 8, k)`` pass on both, checked against numpy
   ``a*b + s + c``.
2. **resident** — ``Engine.matvec`` on the device-resident chain at n=8
   with inner-product length 4,096 and 12,288 rows (qwen3-8b's d_model
   and d_ff), on both backends, against a numpy int64 matmul mod 2^16.
3. **traffic** — ``repro.launch.serve`` traffic mode: 64 requests
   through the continuous batcher on resident lanes
   (``--pim-backend jax:pack=true``); every token must equal
   ``repro.serve.sequence.reference_tokens``, with zero recompiles after
   warm-up and no watchdog abort.
4. **model** — ``repro.launch.serve`` for qwen3-8b at its published
   widths, depth cut to 8 layers (the only cut), batch 8, prompt 128,
   cache 256, 16 generated tokens: once with the FFN on the crossbar
   (``--pim --pim-scope ffn``; finite logits, tokens in range, the
   server's compile-once check), once on the float path, whose first
   decode step must pick the same token as a prefill forward over the
   prompt plus that token (``default_matmul_precision("highest")``).

Each phase prints one JSON line (sizes, seconds, checks). Any failed
check or exception exits non-zero. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU, or outside a checkout, it exits non-zero before any
phase runs. Compiled crossbar programs are built from the tracked
sources in this run (the program disk cache is off); JAX's compilation
cache follows ``repro.runtime.setup_compile_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

CROSSBAR_ROWS = 1 << 20          # phase 1: 32,768 packed words
RESIDENT_ROWS, RESIDENT_LENGTH = 12288, 4096   # phase 2: d_ff x d_model
TRAFFIC_REQUESTS = 64            # phase 3
MODEL_ARGS = ["--arch", "qwen3-8b", "--override", '{"n_layers": 8}',
              "--batch", "8", "--prompt-len", "128", "--cache-len", "256",
              "--gen", "16"]


def _line(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def phase_crossbar(seed: int) -> None:
    import numpy as np

    from repro.engine import Engine
    rng = np.random.default_rng(seed)
    eng = Engine(backend="numpy")
    rows = CROSSBAR_ROWS
    t = time.perf_counter()
    exe = eng.compile("multpim", 32)
    compile_s = time.perf_counter() - t
    a = rng.integers(0, 1 << 32, rows, dtype=np.uint64)
    b = rng.integers(0, 1 << 32, rows, dtype=np.uint64)
    want = a * b
    for spec in ("jax:pack=true", "pallas:pack=true"):
        secs = []
        for _ in range(2):       # first call compiles, second is warm
            t = time.perf_counter()
            out = exe.run({"a": a, "b": b}, backend=spec)["out"]
            secs.append(time.perf_counter() - t)
        bad = int(np.count_nonzero(np.asarray(out, np.uint64) != want))
        _line("crossbar", op="multpim", n=32, rows=rows,
              words=rows // 32, cols=exe.packed.init_mask.shape[1],
              cycles=exe.n_cycles, backend=spec,
              program_compile_s=compile_s, first_run_s=secs[0],
              warm_run_s=secs[1], mismatches=bad)
        _check(bad == 0, f"multpim n=32 on {spec}: {bad} wrong products")

    n = 8
    k = eng.effective_coschedule_k("mac", n)
    bex = eng.compile_batch("mac", n, k)
    half = 1 << (2 * n - 1)    # s, c < 2^(2n-1) keeps the u-stream in n bits
    ops = [(rng.integers(0, 1 << n, rows), rng.integers(0, 1 << n, rows),
            rng.integers(0, half, rows), rng.integers(0, half, rows))
           for _ in range(k)]
    group = [eng.mac_inputs(n, *o) for o in ops]
    for spec in ("jax:pack=true", "pallas:pack=true"):
        t = time.perf_counter()
        outs = bex.run(group, backend=spec)
        run_s = time.perf_counter() - t
        bad = 0
        for (x, y, s_in, c_in), out in zip(ops, outs):
            s, c = eng.mac_accumulate(n, out)
            got = np.asarray(s, np.int64) + np.asarray(c, np.int64)
            bad += int(np.count_nonzero(got != x * y + s_in + c_in))
        _line("crossbar", op="mac", n=n, k=k, rows=rows,
              cols=bex.packed.init_mask.shape[1], cycles=bex.n_cycles,
              backend=spec, run_s=run_s, mismatches=bad)
        _check(bad == 0, f"mac n={n} k={k} on {spec}: {bad} wrong sums")


def phase_resident(seed: int) -> None:
    import numpy as np

    from repro.engine import Engine
    rng = np.random.default_rng(seed + 1)
    n, rows, length = 8, RESIDENT_ROWS, RESIDENT_LENGTH
    A = rng.integers(0, 1 << n, (rows, length))
    x = rng.integers(0, 1 << n, length)
    want = (A @ x) % (1 << (2 * n))
    eng = Engine(backend="numpy")
    for spec in ("jax:pack=true", "pallas:pack=true"):
        t = time.perf_counter()
        got, cycles = eng.matvec(A, x, n, backend=spec, k=1, resident=True)
        run_s = time.perf_counter() - t
        bad = int(np.count_nonzero(np.asarray(got, np.int64) != want))
        _line("resident", n=n, rows=rows, length=length, backend=spec,
              simulated_cycles=cycles, run_s=run_s, mismatches=bad)
        _check(bad == 0, f"resident matvec on {spec}: {bad} wrong rows")


def phase_traffic(seed: int) -> None:
    from repro.launch import serve
    t = time.perf_counter()
    rep = serve.main(["--traffic", str(TRAFFIC_REQUESTS),
                      "--traffic-seed", str(seed),
                      "--pim-backend", "jax:pack=true", "--fault-check",
                      "--watchdog", "600"])["report"]
    s = rep.summary()
    _line("traffic", requests=s["n_requests"], tokens=s["n_tokens"],
          backend="jax:pack=true", wall_s=time.perf_counter() - t,
          bit_exact=rep.bit_exact, recompiles=rep.recompiles,
          aborted=rep.aborted)
    _check(s["n_requests"] == TRAFFIC_REQUESTS and s["n_tokens"] > 0,
           f"traffic run served fewer than {TRAFFIC_REQUESTS} requests")
    _check(rep.bit_exact, "traffic tokens differ from reference_tokens")
    _check(rep.recompiles == 0, f"{rep.recompiles} recompiles after warm-up")
    _check(not rep.aborted, "watchdog aborted the traffic run")


def phase_model(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    seed_args = ["--seed", str(seed)]
    t = time.perf_counter()
    out = serve.main(MODEL_ARGS + seed_args + [
        "--pim", "--pim-scope", "ffn", "--pim-backend", "jax:pack=true"])
    vocab, tokens = out["cfg"].vocab_size, out["tokens"]
    finite = bool(np.isfinite(out["last_logits"]).all())
    in_range = bool(((tokens >= 0) & (tokens < vocab)).all())
    _line("model", arch="qwen3-8b", cut="n_layers 36 -> 8", mode="pim ffn",
          backend="jax:pack=true", tokens=list(tokens.shape),
          wall_s=time.perf_counter() - t, logits_finite=finite,
          tokens_in_range=in_range, recompiles=out["recompiles"])
    _check(finite, "PIM prefill logits not finite")
    _check(in_range, "PIM tokens out of range")
    _check(out["recompiles"] == 0, "PIM decode recompiled")
    del out
    gc.collect()

    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        out = serve.main(MODEL_ARGS + seed_args + ["--no-pim"])
        model, params, tokens = out["model"], out["params"], out["tokens"]
        # tokens[:, 0] is the prefill's pick, tokens[:, 1] the first
        # decode step's: a prefill over prompt + tokens[:, 0] must agree.
        ext = jnp.concatenate([out["prompts"], jnp.asarray(tokens[:, :1])],
                              axis=1)
        logits, _ = model.forward(params, ext)
        ref = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
    agree = bool((ref == tokens[:, 1]).all())
    finite = bool(np.isfinite(out["last_logits"]).all())
    _line("model", arch="qwen3-8b", cut="n_layers 36 -> 8", mode="float",
          tokens=list(tokens.shape), wall_s=time.perf_counter() - t,
          logits_finite=finite, decode_matches_prefill=agree,
          decode_tokens=tokens[:, 1].tolist(), prefill_tokens=ref.tolist())
    _check(finite, "float prefill logits not finite")
    _check(agree, "first decode step disagrees with a prefill forward")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every operand, weight and request")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"'{dev.platform}'")
    if not (HERE / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: run it from a checkout of the "
                         "repository (src/repro not found)")
    sys.path.insert(0, str(HERE / "src"))
    os.environ["REPRO_CACHE_DIR"] = "off"    # cold program cache

    from repro.runtime import setup_compile_cache
    _line("setup", device_kind=dev.device_kind,
          compile_cache=setup_compile_cache(), seed=args.seed)
    for phase in (phase_crossbar, phase_resident, phase_traffic,
                  phase_model):
        t = time.perf_counter()
        phase(args.seed)
        _line(phase.__name__[6:], phase_s=time.perf_counter() - t,
              passed=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
