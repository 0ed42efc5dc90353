"""Device time per decode step, in ms, of the ops in the program's
``pim.matmul`` scope: the PIM linears' integer products and zero-point
corrections (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "pim.matmul")
