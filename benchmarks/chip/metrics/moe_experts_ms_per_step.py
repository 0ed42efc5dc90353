"""Device time per decode step, in ms, of the ops in the program's
``moe.experts`` scope: the held experts' grouped products with their
quantization (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "moe.experts")
