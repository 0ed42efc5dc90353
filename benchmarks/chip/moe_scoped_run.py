#!/usr/bin/env python3
"""Device time per decode step by program scope for a cell with MoE
layers: ``scoped_run.py`` with the MoE scopes.

    python benchmarks/chip/moe_scoped_run.py --workload <cell> \\
        --seed <n> --seconds <s>

Runs ``scoped_run.py`` (one process, the same two windows) with
``moe_route_ms_per_step`` and ``moe_experts_ms_per_step`` among its
per-scope metrics, so the unscoped remainder leaves them out and the
seven add up to busy time per step, and adds
``moe_experts_hbm_roofline``: the held experts' least bytes a step
(:mod:`counting_mla_moe`) over their ``moe.experts`` time at the
device's published HBM bandwidth (None for a cell without experts).
Prints ``scoped_run.py``'s JSON line with those metrics in it.
"""
import contextlib
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
MOE_SCOPES = {"moe_route_ms_per_step": "moe.route",
              "moe_experts_ms_per_step": "moe.experts"}


def main(argv=None) -> int:
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import counting_mla_moe
    import harness
    import scope_time
    import scoped_run

    scope_time.SCOPE_METRICS.update(MOE_SCOPES)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = scoped_run.main(argv)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, _, config, traffic = harness.find_cell(bench, line["workload"])
    bits = (traffic.get("pim") or {}).get("bits", 0)
    roofline = harness.reader("moe_experts_hbm_roofline")
    line["metrics"]["moe_experts_hbm_roofline"] = roofline.share(
        "n_routed_experts" in config
        and counting_mla_moe.expert_bytes(config, bits),
        line["metrics"].get("moe_experts_ms_per_step"),
        harness.peaks_for(line["device"]["kind"])["hbm_bytes_per_s"])
    print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
