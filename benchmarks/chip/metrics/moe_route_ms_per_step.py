"""Device time per decode step, in ms, of the ops in the program's
``moe.route`` scope: the router's product, the gates' softmax and top-k,
the sort by expert, the gather of the routed rows and the combine
(:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "moe.route")
