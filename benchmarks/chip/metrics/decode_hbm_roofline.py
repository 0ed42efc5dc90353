"""The whole decode step's share of its HBM roofline, in %: the bytes
each step needs at the configuration's stated precisions (float
weights once, PIM linears at their bit width, the KV cache up to the
step's position; :mod:`counting`) at the published HBM bandwidth, over
the traced window's time."""


def read(run):
    peak = run.peaks.get("hbm_bytes_per_s")
    if not peak or not run.counts.get("bytes"):
        return None
    least_s = run.counts["bytes"] / peak
    return 100 * least_s / run.counts["window_s"]
