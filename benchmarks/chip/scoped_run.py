#!/usr/bin/env python3
"""Device time per decode step by program scope, for one cell and seed.

    python benchmarks/chip/scoped_run.py --workload <cell> --seed <n> \\
        --seconds <s>

From the root of a checkout, on the chip, in one process: the cell's
set-up, one window with tracing off, then one under the profiler and
the program's spans (the harness's ``--trace 1`` window), reduced with
``op_seconds`` (:mod:`scope_time`). Prints one JSON line: both windows'
end-to-end metrics (the tracing overhead), busy and window seconds, the
steps, the five per-scope metrics (``metrics/*_ms_per_step.py``), the
seconds ``repro.obs.device_scopes()`` took after the window, and the
compiles counted (``jax.compiles``) and spanned (``jax.compile``) in the
traced window, and the ops with the most device time per step, each
with its scope. The benchmark itself does not report the per-scope
metrics: its trace reduction (``xplane.reduce_profile``) would need to
return ``op_seconds``.
"""
import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TOP = 25


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))

    import harness
    import scope_time
    import xplane
    from repro import obs
    from repro.runtime import setup_compile_cache

    setup_compile_cache()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl, _, config, traffic = harness.find_cell(bench, args.workload)
    device = harness.device_info(wl["chips"])
    driver = harness.load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    cell = driver.Cell(config, traffic, args.seed)
    cell.setup()

    untraced, _ = cell.window(args.seconds)
    compiles = obs.counter(obs.COMPILES)
    before = compiles.value
    xplane.reduce_dir = scope_time.reduce_dir
    e2e, counts, spans, dev = harness._traced_window(cell, args.seconds)
    in_window = compiles.value - before
    t0 = time.perf_counter()
    obs.device_scopes()
    map_s = time.perf_counter() - t0

    run = harness.Run(e2e, counts, spans, dev, {})
    names = [*scope_time.SCOPE_METRICS, scope_time.UNSCOPED_METRIC]
    metrics = {n: harness.reader(n).read(run) for n in names}
    op_map = scope_time.program_scopes()
    ops = (dev or {}).get("op_seconds") or {}
    per_step = sorted(((1e3 * s / counts["steps"], op, op_map.get(op))
                       for op, s in ops.items()
                       if op_map.get(op) != scope_time.CONTAINER),
                      reverse=True)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "untraced": untraced, "traced": e2e,
        "busy_s": dev and dev["busy_s"], "window_s": dev and dev["window_s"],
        "steps": counts["steps"], "metrics": metrics,
        "scope_map_s": map_s, "compiles_in_window": in_window,
        "compile_spans": sum(e.get("name") == "jax.compile" for e in spans),
        "idle_gaps": dev and dev["idle_gaps"],
        "top_ops_ms_per_step": per_step[:TOP],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
