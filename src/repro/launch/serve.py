"""Production serving driver: batched prefill + greedy decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
      --batch 4 --prompt-len 32 --gen 32 --pim-scope full

Traffic mode (``--traffic N``) skips the model build entirely and runs
the :mod:`repro.serve` continuous-batching scheduler against a seeded
Poisson trace of N generate requests — admission control, dynamic-K
grouped passes, SLO percentiles from :mod:`repro.obs`. With
``--traffic-compare`` the same trace replays under per-pass host
round-trip and serial one-request-at-a-time scheduling and the driver
reports both speedups; ``--traffic-check X`` turns the serial ratio
into a hard gate and ``--traffic-resident-check X`` gates the
continuous-over-roundtrip ratio of the device-resident lane path (both
also require zero recompiles after warmup and bit-identical tokens
across schedules):

  PYTHONPATH=src python -m repro.launch.serve --traffic 16 \
      --pim-backend jax:pack=true --traffic-check 3.0 \
      --traffic-resident-check 2.0 \
      --trace /tmp/serve_load.json --metrics /tmp/serve_load_metrics.json

PIM offload: in smoke mode (or with ``--pim``) the LM-head linear runs
as a PIM linear (:func:`repro.pim.linear`): quantized activations times
the weight's codes, planned once, in one integer product. What the
linears cost on the crossbar is accounted on the process-shared
:class:`repro.engine.Engine`, which co-schedules ``--pim-k`` MACs per
crossbar pass (:meth:`repro.engine.Engine.compile_batch`): K independent
carry-save accumulator chains share one wide crossbar in disjoint
partition ranges, so decode issues ~K fewer crossbar passes per inner
product than the sequential path (the driver logs the resulting
cycles-per-MAC).

``--pim-scope`` widens the offload beyond the LM head (full-block
serving): ``head`` is the LM head only, ``ffn`` adds both FFN
projections of every block (incl. the MoE ragged path's per-expert
GEMMs), ``full`` adds the attention q/k/v/o projections. Every scope's
linears are lowered by :func:`repro.pim.planner.plan_block` onto
*heterogeneous co-scheduled crossbar groups*
(:meth:`repro.engine.Engine.compile_group`): each linear owns a
column-budget-weighted number of MAC chains inside one shared crossbar
pass, and the weight-stationary fused schedule is compiled exactly once,
before prefill — the driver logs per-scope cycles/MAC and cycles/token.
Steady-state decode must compile nothing: the driver counts JAX's
compiles (``jax.compiles``) over the decode steps after the first and
exits non-zero on any.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.engine import get_engine
from repro.engine.backends import platform_default_spec
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models.transformer import encode
from repro.runtime import setup_compile_cache
from repro.train import batch_shardings, make_serve_step, state_shardings

# No logging side effects at import time: handlers attach only when
# main() calls obs.setup_logging() (see repro.obs.logging).
log = obs.get_logger("serve")


def _profile_pass(engine, n_bits: int) -> None:
    """One real crossbar pass of the serve MAC group, so the exported
    trace contains the full exec.run -> marshal/pack/kernel/unpack
    breakdown (the jitted decode loop itself runs the MAC *semantics*
    inside XLA, not through Executable.run). Only called under --trace,
    so the untraced serve path pays nothing."""
    with obs.span("serve.profile_pass", n_bits=n_bits):
        rows = 8
        a = np.arange(1, rows + 1, dtype=object)
        zeros = np.zeros(rows, dtype=object)
        batch = engine._mac_inputs(n_bits, a, a, zeros, zeros)
        k = engine.effective_coschedule_k("mac", n_bits)
        if k >= 2:
            engine.compile_batch("mac", n_bits, k).run([batch] * k)
        else:
            engine.compile("mac", n_bits).run(batch)


def _export_waterfalls(engine, plan, n_bits: int) -> None:
    """Merge modeled-cycle waterfall tracks into the trace: one process
    row per co-scheduled plan group (fused program occupancy +
    switching) and one for the LM-head MAC group. Groups placed on a
    device hierarchy (``--device-config``) carry their coordinate as a
    counter-track prefix, so per-channel activity reads directly off
    the trace."""
    pid = 2
    seen = set()
    groups = list(plan.groups) if plan is not None else []
    for g in groups:
        gex = g.executable
        if gex is None or id(gex.program) in seen:
            continue
        seen.add(id(gex.program))
        obs.add_events(obs.waterfall_events(
            gex.program, packed=gex.packed,
            name=f"{g.scope}: {gex.program.name}", pid=pid,
            cycle_ns=engine.crossbar.cycle_ns,
            track=str(g.coord) if g.coord is not None else None))
        pid += 1
    k = engine.effective_coschedule_k("mac", n_bits)
    exe = (engine.compile_batch("mac", n_bits, k) if k >= 2
           else engine.compile("mac", n_bits))
    if id(exe.program) not in seen:
        obs.add_events(obs.waterfall_events(
            exe.program, packed=exe.packed,
            name=f"lm_head MAC: {exe.program.name}", pid=pid,
            cycle_ns=engine.crossbar.cycle_ns))


def _log_report(rep) -> None:
    s = rep.summary()
    log.info("[%s] %d requests, %d tokens in %.3fs -> %.1f tok/s | "
             "%d passes, recompiles=%d, bit_exact=%s",
             rep.mode, s["n_requests"], s["n_tokens"], s["wall_s"],
             s["tokens_per_s"], s["passes"], s["recompiles"],
             s["bit_exact"])
    log.info("[%s] steady-state: TTFT p50=%.0fus p99=%.0fus | "
             "token latency p50=%.0fus p99=%.0fus",
             rep.mode, s["ttft_p50_us"], s["ttft_p99_us"],
             s["token_p50_us"], s["token_p99_us"])


def _run_traffic(args):
    """--traffic mode: continuous-batching load run, no model build.
    Returns the continuous run's :class:`repro.serve.LoadReport`."""
    from repro.engine import get_engine, resolve_backend
    from repro.pim import plan_serve_slots
    from repro.serve import (DECODE_ELEMS, TrafficConfig, compare_modes,
                             generate, run_load)
    engine = get_engine()
    fault_spec = None
    if args.fault_rate is not None:
        # Compose the fault model into the backend spec so the packed
        # executors inject at the device layer; seed it explicitly so a
        # rerun replays the identical fault sequence.
        from repro.faults import get_fault_model
        fault_spec = f"flip@{args.fault_rate:g}@{args.fault_seed}"
        base = args.pim_backend or platform_default_spec()
        sep = "," if ":" in base else ":"
        args.pim_backend = f"{base}{sep}faults={fault_spec}"
        get_fault_model(fault_spec).reset()
        log.info("fault injection: %s (backend %s)", fault_spec,
                 args.pim_backend)
    if args.pim_backend is not None:
        engine.backend = resolve_backend(args.pim_backend)
    n = args.pim_bits
    elems = args.traffic_elems or DECODE_ELEMS
    device = None
    if args.device_config is not None:
        from repro.device import DeviceConfig
        device = DeviceConfig.parse(args.device_config,
                                    crossbar=engine.crossbar)
        log.info("device hierarchy: %s (%d crossbars)", device,
                 device.n_crossbars)
    # --pim-k (deprecated) pins the batch width; otherwise the slot
    # budget comes from the crossbar column budget via the planner
    # (scaled by the device crossbar count under --device-config).
    max_slots = args.pim_k if args.pim_k is not None else args.traffic_slots
    slots = plan_serve_slots(engine, n, max_slots=max_slots, device=device)
    log.info("%s", slots.summary())
    if max_slots is None and device is not None:
        max_slots = slots.max_slots    # device-scaled budget -> scheduler

    cfg = TrafficConfig(n_requests=args.traffic, rate=args.traffic_rate,
                        n_bits=n, seed=args.traffic_seed)
    reqs = generate(cfg)
    log.info("trace: %d requests over %.3fs (Poisson %.0f req/s, seed %d)",
             len(reqs), reqs[-1].arrival if reqs else 0.0,
             args.traffic_rate, args.traffic_seed)

    common = dict(n_bits=n, decode_elems=elems, max_slots=max_slots,
                  priority=args.traffic_priority)
    gating = (args.traffic_check is not None
              or args.traffic_resident_check is not None)
    if (fault_spec is not None or args.fault_check
            or args.watchdog is not None):
        # Fault/watchdog mode is a single continuous run: replaying the
        # trace under other schedules would advance the shared fault
        # model's pass counter, so cross-mode parity is not meaningful
        # under injection — the bit-exactness check is against the
        # plain-int reference tokens instead.
        cont = run_load(engine, reqs, mode="continuous",
                        watchdog_s=args.watchdog, **common)
        _log_report(cont)
        c = obs.dump()["counters"]
        log.info("faults: injected=%d detected=%d (+%d residue) "
                 "recovered=%d unrecovered=%d escaped=%d | restarts=%d "
                 "quarantined=%d displaced=%d rejected=%d",
                 c.get("faults.injected", 0), c.get("faults.detected", 0),
                 c.get("faults.detected_residue", 0),
                 c.get("faults.recovered", 0),
                 c.get("faults.unrecovered", 0),
                 c.get("faults.escaped", 0),
                 c.get("serve.fault.restarts", 0),
                 c.get("serve.fault.quarantined", 0),
                 c.get("serve.fault.displaced", 0),
                 c.get("serve.rejected", 0))
        if args.fault_check:
            fails = []
            if not cont.bit_exact:
                fails.append(f"{cont.escaped_tokens} corrupt token(s) "
                             f"escaped detection")
            if cont.recompiles != 0:
                fails.append(f"recompiles after warmup = {cont.recompiles}"
                             f" (recovery must not recompile)")
            if cont.aborted:
                fails.append("watchdog aborted the run")
            if fails:
                raise SystemExit("fault gate FAILED: " + "; ".join(fails))
            log.info("fault gate passed: bit-exact under %s, zero "
                     "recompiles, no abort",
                     fault_spec or "fault-free serving")
    elif args.traffic_compare or gating:
        res = compare_modes(engine, reqs, **common)
        cont, rt, ser = res["continuous"], res["roundtrip"], res["serial"]
        _log_report(cont)
        _log_report(rt)
        _log_report(ser)
        log.info("continuous batching speedup: %.2fx over serial, "
                 "%.2fx over per-pass round-trip (tokens_match=%s)",
                 res["speedup"], res["resident_speedup"],
                 res["tokens_match"])
        obs.gauge("serve.load.speedup").set(res["speedup"])
        obs.gauge("serve.load.resident_speedup").set(
            res["resident_speedup"])
        if gating:
            fails = []
            if (args.traffic_check is not None
                    and res["speedup"] < args.traffic_check):
                fails.append(f"speedup {res['speedup']:.2f}x < "
                             f"{args.traffic_check:.2f}x over serial")
            if (args.traffic_resident_check is not None
                    and res["resident_speedup"]
                    < args.traffic_resident_check):
                fails.append(
                    f"resident speedup {res['resident_speedup']:.2f}x < "
                    f"{args.traffic_resident_check:.2f}x over round-trip")
            if cont.recompiles != 0:
                fails.append(f"recompiles after warmup = {cont.recompiles}")
            if not res["tokens_match"]:
                fails.append("token mismatch between schedules")
            if fails:
                raise SystemExit("serve load gate FAILED: "
                                 + "; ".join(fails))
            log.info("serve load gate passed: %.2fx over serial, %.2fx "
                     "over round-trip, zero recompiles, bit-exact",
                     res["speedup"], res["resident_speedup"])
    else:
        cont = run_load(engine, reqs, mode="continuous", **common)
        _log_report(cont)
    obs.gauge("serve.load.tokens_per_s").set(cont.tokens_per_s)
    obs.gauge("serve.load.ttft_p99_us").set(
        cont.ttft_us.get("p99", 0.0))
    obs.gauge("serve.load.token_p99_us").set(
        cont.token_latency_us.get("p99", 0.0))

    if args.trace:
        n_ev = obs.export_trace(args.trace)
        log.info("trace: %d events -> %s", n_ev, args.trace)
    if args.metrics:
        obs.write_metrics(args.metrics)
        log.info("metrics snapshot -> %s", args.metrics)
    return cont


def main(argv: Optional[Sequence[str]] = None) -> Optional[Dict[str, Any]]:
    """Run the server from ``argv`` (default ``sys.argv``).

    Traffic mode returns ``{"report": LoadReport}``; model mode returns
    ``{"cfg", "model", "params", "prompts", "tokens", "last_logits",
    "recompiles"}`` — ``tokens`` is the ``(batch, gen)`` greedy output,
    ``last_logits`` the prefill's last-position logits — so a caller in
    the same process can check the run against the model API.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b",
                    help="architecture name (repro.configs registry)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--override", default="",
                    help="JSON dict of ModelConfig overrides, e.g. "
                         "'{\"n_layers\": 8}' to cut depth")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pim", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="run the LM head as a PIM-mode linear through "
                         "the shared engine (default: on under --smoke)")
    ap.add_argument("--pim-bits", type=int, default=8)
    ap.add_argument("--pim-k", type=int, default=None,
                    help="DEPRECATED: pin the co-scheduled batch width. "
                         "Default is load-driven: the serve scheduler "
                         "sizes each pass to the live batch (dynamic K "
                         "over the precompiled pow2 ladder); the model "
                         "path uses the engine's capacity policy. An "
                         "explicit value logs a deprecation warning and "
                         "pins the width.")
    ap.add_argument("--pim-scope", choices=["head", "ffn", "full"],
                    default="head",
                    help="how much of each block the PIM engine serves: "
                         "head = LM head only; ffn = + FFN projections "
                         "(incl. MoE experts); full = + attention "
                         "q/k/v/o — all via co-scheduled crossbar groups")
    ap.add_argument("--pim-backend", default=None,
                    help="execution backend spec for the shared engine, "
                         "e.g. 'jax:pack=true' (bit-plane packed jax "
                         "scan) or 'pallas:pack=true' (packed Pallas "
                         "kernel: Mosaic on a TPU, the interpreter on "
                         "the CPU); default: derived from the platform "
                         "— 'jax:pack=true' on an accelerator, the numpy "
                         "reference on the CPU")
    ap.add_argument("--device-config", default=None, metavar="CxGxBxX",
                    help="model a PIM device hierarchy (repro.device): "
                         "channels x bank-groups x banks x crossbars, "
                         "e.g. '2x2x4x4'. Plan groups are placed onto "
                         "coordinates, the slot budget scales with the "
                         "crossbar count, and the driver logs per-level "
                         "utilization/cost plus fleet sizing")
    ap.add_argument("--traffic", type=int, default=None, metavar="N",
                    help="continuous-batching load mode: serve N "
                         "synthetic requests (seeded Poisson arrivals) "
                         "through the repro.serve scheduler instead of "
                         "building a model")
    ap.add_argument("--traffic-rate", type=float, default=200.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--traffic-seed", type=int, default=0)
    ap.add_argument("--traffic-elems", type=int, default=None,
                    help="decode elements per token (MAC chain length; "
                         "default repro.serve.DECODE_ELEMS)")
    ap.add_argument("--traffic-slots", type=int, default=None,
                    help="clamp the live-sequence slot budget (default: "
                         "the crossbar column-budget capacity)")
    ap.add_argument("--traffic-priority", choices=["prefill", "decode"],
                    default="prefill",
                    help="admission policy: prefill = backfill freed "
                         "slots mid-stream (best TTFT); decode = drain "
                         "the batch before admitting the next wave")
    ap.add_argument("--traffic-compare", action="store_true",
                    help="also replay the trace under serial "
                         "one-request-at-a-time scheduling and report "
                         "the continuous/serial speedup")
    ap.add_argument("--traffic-check", type=float, default=None,
                    metavar="X",
                    help="hard gate (implies --traffic-compare): exit "
                         "nonzero unless speedup >= X, recompiles after "
                         "warmup == 0, and all schedules emit "
                         "bit-identical tokens")
    ap.add_argument("--traffic-resident-check", type=float, default=None,
                    metavar="X",
                    help="hard gate on the device-resident path (implies "
                         "--traffic-compare): exit nonzero unless "
                         "resident continuous batching is >= X faster "
                         "than the per-pass host round-trip on the same "
                         "trace (plus the zero-recompile and bit-parity "
                         "checks)")
    ap.add_argument("--fault-rate", type=float, default=None, metavar="P",
                    help="inject transient device faults: per-gate "
                         "bit-flip probability P, composed into the "
                         "backend spec as faults=flip@P@SEED (traffic "
                         "mode; detection + self-healing recovery run "
                         "automatically on the resident path)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault-model seed (reruns replay the identical "
                         "fault sequence)")
    ap.add_argument("--fault-check", action="store_true",
                    help="hard gate: exit nonzero unless the faulted "
                         "traffic run stays bit-exact against the "
                         "reference tokens with zero recompiles after "
                         "warmup and no watchdog abort")
    ap.add_argument("--watchdog", type=float, default=None, metavar="S",
                    help="stall watchdog budget in seconds: abort the "
                         "traffic run cleanly (partial stats, exit "
                         "report aborted=True) if the scheduler makes "
                         "no progress for S seconds")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable span tracing and write a Chrome "
                         "trace-event file (open in chrome://tracing or "
                         "ui.perfetto.dev) with compile/cache/execute "
                         "spans plus crossbar-waterfall counter tracks")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="write the obs metrics snapshot (counters, "
                         "gauges, latency histograms) as JSON")
    args = ap.parse_args(argv)
    obs.setup_logging()
    setup_compile_cache()
    if args.trace:
        obs.enable()

    if args.pim_k is not None:
        log.warning("--pim-k is deprecated: K is load-driven now (the "
                    "serve scheduler sizes each pass to the live batch); "
                    "an explicit --pim-k pins the batch width to %d",
                    args.pim_k)

    if args.traffic is not None:
        return {"report": _run_traffic(args)}

    pim = args.smoke if args.pim is None else args.pim
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.override:
        cfg = cfg.scaled(**json.loads(args.override))
    log.info("model %s: n_layers=%d d_model=%d d_ff=%d vocab=%d "
             "(%.2fB params)", cfg.name, cfg.n_layers, cfg.d_model,
             cfg.d_ff, cfg.vocab_size, cfg.param_count() / 1e9)
    if pim:
        block_mode = {"head": "none", "ffn": "ffn",
                      "full": "full"}[args.pim_scope]
        cfg = dataclasses.replace(cfg, pim_linear_mode="pim",
                                  pim_linear_bits=args.pim_bits,
                                  pim_block_mode=block_mode)
    model = build_model(cfg)
    mesh = make_host_mesh(args.model_parallel)
    # Jitted so the stacked layer weights are generated in place (an
    # eager init holds every per-layer copy and the stack at once).
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    engine = get_engine()
    if args.pim_k is not None:
        engine.coschedule_k = args.pim_k
    if args.pim_backend is not None:
        from repro.engine import resolve_backend
        engine.backend = resolve_backend(args.pim_backend)

    # Full-block serving plan: lower every enabled scope's linears onto
    # co-scheduled crossbar groups *before* prefill/decode — the fused
    # weight-stationary schedules compile (and verify) exactly once
    # here, and the cost accounting below reads them.
    plan = None
    device = None
    if pim:
        from repro.pim import plan_block
        placer = None
        if args.device_config is not None:
            from repro.device import CoordAllocator, DeviceConfig
            device = DeviceConfig.parse(args.device_config,
                                        crossbar=engine.crossbar)
            placer = CoordAllocator(device).place
            log.info("device hierarchy: %s (%d crossbars, %d banks)",
                     device, device.n_crossbars, device.n_banks)
        # With a real device budget, degrade gracefully on capacity
        # exhaustion: shed the groups that don't fit instead of dying,
        # and say exactly what was lost.
        plan = plan_block(cfg, engine, placer=placer,
                          on_capacity="shed" if device is not None
                          else "raise")
        if plan.shed:
            log.warning("device %s too small for scope plan: shed %d "
                        "group(s): %s (served scopes: %s)",
                        device, len(plan.shed), ", ".join(plan.shed),
                        list(plan.scopes))

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(3, cfg.vocab_size,
                                       (args.batch, args.prompt_len)))

    # prefill: run the full forward leaving KV/recurrent state behind
    states = model.init_decode_state(args.batch, args.cache_len)
    if cfg.family == "encdec":
        frames = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.enc_frames, cfg.d_model)), jnp.float32)
        states["enc_out"] = encode(cfg, params, frames)
    t0 = time.time()
    with obs.span("serve.prefill", batch=args.batch,
                  prompt_len=args.prompt_len):
        logits, states = model.forward(params, prompts, states=states)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        last_logits = np.asarray(logits[:, -1])
        del logits
    log.info("prefill %d x %d: %.2fs", args.batch, args.prompt_len,
             time.time() - t0)

    serve, jit_for = make_serve_step(model, mesh)
    batch_like = {"token": tok, "position": jnp.zeros((args.batch, 1),
                                                      jnp.int32)}
    jit_serve = jit_for(params, states, batch_like)
    # The first step takes its inputs where every later step finds them
    # (the step's own output shardings), so one program serves them all.
    tok = jax.device_put(tok, batch_shardings(mesh, batch_like)["token"])
    states = jax.device_put(states, state_shardings(mesh, states))

    # The first decode step compiles the step and plans the weights;
    # every later step must compile nothing (JAX's backend compiles).
    obs.watch_compiles()
    compiles = obs.counter(obs.COMPILES)
    after_first = None
    out = [np.asarray(tok)]
    tok_lat = obs.histogram("serve.token_latency_us")
    t0 = time.time()
    for t in range(args.gen - 1):
        s0 = time.perf_counter()
        with obs.span("serve.decode_step", step=t):
            pos = jnp.full((args.batch, 1), args.prompt_len + t, jnp.int32)
            tok, states = jit_serve(params, states, tok, pos)
            out.append(np.asarray(tok))    # device sync: real step time
        tok_lat.observe((time.perf_counter() - s0) * 1e6)
        if after_first is None:
            after_first = compiles.value
    dt = time.time() - t0
    post = engine.stats()
    gen = np.concatenate(out, axis=1)
    recompiles = 0 if after_first is None else compiles.value - after_first
    log.info("generated %d x %d tokens in %.2fs (%.1f tok/s/seq)",
             args.batch, args.gen, dt, (args.gen - 1) / max(dt, 1e-9))
    if args.gen > 1:
        log.info("decode latency/token: p50=%.1fus p90=%.1fus p99=%.1fus",
                 tok_lat.percentile(0.50), tok_lat.percentile(0.90),
                 tok_lat.percentile(0.99))
    obs.gauge("serve.tokens_per_sec").set((args.gen - 1) / max(dt, 1e-9))
    obs.gauge("serve.cache_hits").set(post["hits"])
    obs.gauge("serve.cache_misses").set(post["misses"])
    obs.gauge("serve.engine_runs").set(post["runs"])
    log.info("sample: %s", gen[0][:16].tolist())
    if pim:
        log.info("engine cache: hits=%d misses=%d disk_hits=%d entries=%d "
                 "| JAX compiles after the first decode step=%d",
                 post["hits"], post["misses"], post["disk_hits"],
                 post["entries"], recompiles)
        if recompiles != 0:
            raise SystemExit(
                f"PIM serve path violated compile-once: {recompiles} JAX "
                f"compile(s) after the first decode step")
        log.info("PIM LM head: %d-bit MultPIM-MAC, planned on the shared "
                 "engine (backend=%s%s), compile-once verified",
                 cfg.pim_linear_bits, engine.backend.name,
                 ":pack" if getattr(engine.backend, "pack", False) else "")
        # The co-scheduled K-MAC group the decode loop is accounted at:
        # one fused crossbar pass serves K MACs (disjoint partition
        # ranges), up to K-fold fewer passes than sequential MACs. A MAC
        # too wide to co-schedule (capacity < 2) stays on the plain path.
        k = engine.effective_coschedule_k("mac", cfg.pim_linear_bits)
        if k >= 2:
            cost = engine.compile_batch("mac", cfg.pim_linear_bits,
                                        k).cost()
            log.info("PIM LM head co-schedule: K=%d MACs/pass, "
                     "%d cycles/pass -> %.1f cycles/MAC (sequential: %d), "
                     "up to %.0fx fewer crossbar passes per inner product",
                     cost.programs, cost.cycles, cost.cycles_per_program,
                     cost.cycles, float(cost.programs))
        elif engine.coschedule_k < 2:
            log.info("PIM LM head co-schedule: off (requested K=%d; "
                     "sequential passes)", engine.coschedule_k)
        else:
            log.info("PIM LM head co-schedule: off (MAC width %d fills "
                     "the crossbar; sequential passes)",
                     cfg.pim_linear_bits)
        # Per-scope accounting for the full-block path: which linears
        # share a crossbar pass, with how many chains, at what
        # cycles/MAC (scope="head" is the LM head group; "ffn"/"attn"
        # appear under --pim-scope ffn|full).
        log.info("PIM scope=%s: %d co-scheduled group(s) over scopes %s",
                 args.pim_scope, len(plan.groups), list(plan.scopes))
        for scope, row in plan.scope_metrics().items():
            log.info("PIM scope [%s]: %s on %d crossbar(s) | chains=%s "
                     "-> %d MACs/pass @ %d cyc/pass = %.1f cycles/MAC | "
                     "%d passes/token, %s cycles/token "
                     "(row util %.0f%%)",
                     scope, ",".join(row["linears"]), row["crossbars"],
                     row["chains"], row["macs_per_pass"],
                     row["pass_cycles"], row["cycles_per_mac"],
                     row["passes_per_token"],
                     f"{row['cycles_per_token']:,}",
                     100 * row["row_utilization"])
        if plan.groups:
            us = plan.cycles_per_token * engine.crossbar.cycle_ns / 1e3
            log.info("PIM block plan: %s cycles/token end-to-end "
                     "(%.1f us @ %.0f ns/cycle), weight-stationary "
                     "layouts reused across all %d decode steps",
                     f"{plan.cycles_per_token:,}", us,
                     engine.crossbar.cycle_ns, args.gen - 1)
            obs.gauge("serve.cycles_per_token").set(plan.cycles_per_token)
        if device is not None and plan.groups:
            from repro.device import block_trace, charge
            rep = charge(block_trace(plan, device))
            for line in rep.summary().splitlines():
                log.info("%s", line)
            obs.gauge("serve.device.latency_us").set(rep.latency_us)
            obs.gauge("serve.device.tokens_per_sec").set(
                rep.tokens_per_sec)

    if args.trace:
        if pim:
            _profile_pass(engine, cfg.pim_linear_bits)
            _export_waterfalls(engine, plan, cfg.pim_linear_bits)
        n_ev = obs.export_trace(args.trace)
        log.info("trace: %d events -> %s", n_ev, args.trace)
    if args.metrics:
        obs.write_metrics(args.metrics)
        log.info("metrics snapshot -> %s", args.metrics)
    return {"cfg": cfg, "model": model, "params": params,
            "prompts": prompts, "tokens": gen, "last_logits": last_logits,
            "recompiles": recompiles}


if __name__ == "__main__":
    main()
