#!/usr/bin/env python3
"""On-chip benchmark: one run of one cell, one JSON line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, in one process: set-up (build, warm every
shape the cell's traffic uses), a measured window of ``--seconds``, the
comparison with the plain reference, and as the last stdout line
``{"correct", "attempted", "failed", "metrics", "device", ...}``. With
``--trace 0`` the metrics are the cell's end-to-end ones (host clock);
with ``--trace 1`` its per-layer ones, read from the JAX profiler's
trace and the program's spans over the same window. Without an
accelerator, or outside a checkout of the program, it exits non-zero
and prints no result. Cells, configurations, mixes and metrics are
listed in ``BENCHMARK.json`` at the checkout root.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("benchmark: run it from a checkout of the "
                         "program (src/repro not found)")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    # The TPU runtime's logs go under this run's temporary directory,
    # not to a fixed path shared by every run on the machine.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))

    import harness
    from repro.runtime import setup_compile_cache

    setup_compile_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
