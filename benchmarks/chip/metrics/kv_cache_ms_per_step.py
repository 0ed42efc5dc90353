"""Device time per decode step, in ms, of the ops in the program's
``kv_cache`` scope: the decode caches' reads and writes, in the layer
scan and for the new token (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "kv_cache")
