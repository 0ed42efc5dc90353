"""The held experts' share of their HBM roofline, in %: the least bytes
a step reads of the held expert stacks (``expert_bytes`` over the
window's steps; :mod:`counting_mla_moe`) at the published HBM bandwidth,
over the device time a step of the ops in the ``moe.experts`` scope."""
import scope_time


def share(bytes_per_step, ms_per_step, peak):
    """% of ``peak`` bytes/s that ``bytes_per_step`` in ``ms_per_step``
    reach; None where a number is missing."""
    if not peak or not bytes_per_step or not ms_per_step:
        return None
    return 100 * bytes_per_step / peak / (ms_per_step / 1e3)


def read(run):
    steps = run.counts.get("steps")
    if not steps or not run.counts.get("expert_bytes"):
        return None
    return share(run.counts["expert_bytes"] / steps,
                 scope_time.read(run, "moe.experts"),
                 run.peaks.get("hbm_bytes_per_s"))
