#!/usr/bin/env python3
"""Readings for a cell's limits: the program's and each control's.

    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds S]

For each seed, in one process: the cell's set-up, a window of
``--seconds``, the check of the program, and the same check with each
of the cell's controls put in the program's place (``Cell.check``). A
control is the plain reference one precision step below what the
configuration states: each of the traffic file's ``controls`` (the
PIM linears' bits, or the float products' precision). Prints
one JSON line per seed and check: whether it came out correct, and
every number it compared beside its limit. The benchmark's own runs
never run a control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]

    import harness
    from repro.runtime import setup_compile_cache

    harness.device_info(1)
    setup_compile_cache()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, _, config, traffic = harness.find_cell(bench, args.workload)
    driver = harness.load_module(HERE / "drivers"
                                 / f"{traffic['driver']}.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        cell = driver.Cell(config, traffic, seed)
        cell.setup()
        cell.window(args.seconds)
        cell.release()
        for name in [None, *cell.controls]:
            chk = cell.check(name)
            print(json.dumps({"seed": seed, "check": name or "program",
                              "correct": chk.correct,
                              "compared": chk.as_dict(),
                              "readings": chk.readings,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
