"""Device time per decode step, in ms, of the ops in the program's
``pim.quantize`` scope: the PIM linears' quantize, dequantize and scale
reductions, of activations and weights (:mod:`scope_time`)."""
import scope_time


def read(run):
    return scope_time.read(run, "pim.quantize")
