"""The whole decode step's share of the chip's bf16 peak, in %: the
FLOPs the served tokens require (every linear's weights twice, plus
attention over each token's context; :mod:`counting`) over the traced
window's time."""


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    if not peak or not run.counts.get("flops"):
        return None
    rate = run.counts["flops"] / run.counts["window_s"]
    return 100 * rate / peak
