"""Benchmark harness: one section per paper table + roofline extraction.

Prints ``name,us_per_call,derived`` CSV (the harness contract) and, so
the perf trajectory is tracked across PRs, writes a machine-readable
JSON (``--json``, default ``BENCH_pr10.json``) mapping each section to
its rows::

    {"sections": {"table1": [[name, us_per_call, derived], ...], ...},
     "errors": {"section": "repr(exc)"}}

  PYTHONPATH=src python -m benchmarks.run [--section table1|table2|table3|
                                           fa|opt|sim|throughput|resident|
                                           block_pim|serve_load|device|
                                           faults|obs|roofline|all|
                                           sec1,sec2,...]
                                          [--json BENCH_pr10.json|off]
                                          [--trace OUT.json]
                                          [--metrics OUT.json]
"""
from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all")
    ap.add_argument("--dryrun-json", default="dryrun_results.json")
    ap.add_argument("--json", default="BENCH_pr10.json",
                    help="machine-readable output path ('off' disables)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable span tracing and write a Chrome "
                         "trace-event file at exit")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="write the obs metrics snapshot as JSON")
    args = ap.parse_args()

    from repro import obs
    from repro.runtime import setup_compile_cache
    setup_compile_cache()
    if args.trace:
        obs.enable()

    from . import tables
    from .roofline import roofline_rows

    sections = {
        "table1": tables.table1_latency,
        "table2": tables.table2_area,
        "table3": tables.table3_matvec,
        "fa": tables.fa_comparison,
        "opt": tables.opt_pipeline,
        "sim": tables.sim_throughput,
        "throughput": tables.throughput,
        "resident": tables.resident_chain,
        "pim_plan": tables.pim_plan_sweep,
        "block_pim": tables.block_pim_plan,
        "serve_load": tables.serve_load,
        "device": tables.device_hierarchy,
        "faults": tables.faults_table,
        "energy": tables.energy_table,
        "obs": tables.obs_metrics,
        "roofline": lambda: roofline_rows(args.dryrun_json),
    }
    names = (list(sections) if args.section == "all"
             else args.section.split(","))
    print("name,us_per_call,derived")
    collected = {}
    errors = {}
    for name in names:
        try:
            rows = sections[name]()
            collected[name] = [[r[0], r[1], r[2]] for r in rows]
            for row in rows:
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
        except Exception as e:    # noqa: BLE001
            errors[name] = repr(e)
            print(f"{name},0.0,ERROR={e!r}", file=sys.stderr)
    if args.json != "off":
        with open(args.json, "w") as f:
            json.dump({"sections": collected, "errors": errors}, f, indent=1)
        print(f"wrote {args.json} ({len(collected)} sections)",
              file=sys.stderr)
    if args.trace:
        n_ev = obs.export_trace(args.trace)
        print(f"trace: {n_ev} events -> {args.trace}", file=sys.stderr)
    if args.metrics:
        obs.write_metrics(args.metrics)
        print(f"metrics snapshot -> {args.metrics}", file=sys.stderr)
    sys.exit(1 if errors else 0)


if __name__ == "__main__":
    main()
