"""Device time per decode step, in ms, in none of the program's scopes:
busy time per step less the four scoped metrics, so the five add up to
busy time per step. If it grows, a scope is missing (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, None)
