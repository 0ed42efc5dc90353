"""Model zoo substrate: layers, blocks, assembly, public API."""
from .model import Model, build_model, input_specs
from .transformer import (decode_step, forward, init_decode_state,
                          init_params, plan_weights, stack_plan)

__all__ = ["Model", "build_model", "input_specs", "forward", "decode_step",
           "init_params", "init_decode_state", "stack_plan",
           "plan_weights"]
