"""Device time per decode step, in ms, of the ops in the program's
``attention`` scope: the q/k/v/o projections, rope, scores, softmax and
weighted sum (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "attention")
